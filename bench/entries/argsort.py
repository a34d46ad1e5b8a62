"""Drives ``repro.core.argsort`` on one chip, as a library caller does.

The window calls the public entry on a device array and gets back the
unready permutation; nothing is wrapped in a jit of the benchmark's
own, so the entry's host work (key encoding, plan lookup, dispatch of
the jitted executor) is part of every call, as it is for a caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import argsort, bucket_sort, guard
from repro.core.sort_config import SortConfig


class Entry:
    def __init__(self, config: dict, devices, n: int):
        self.cfg = SortConfig(**config["sort_config"])
        self.dtype = jnp.dtype(config["dtype"])
        self.sharding = jax.sharding.SingleDeviceSharding(devices[0])
        self.n = n

    def __call__(self, x: jax.Array) -> jax.Array:
        return argsort(x, self.cfg)

    def permutation(self, out: jax.Array) -> np.ndarray:
        return np.asarray(out)

    def trace_count(self) -> int:
        return bucket_sort.trace_count()

    def faults(self) -> dict[str, int]:
        """Counts that make a run unsound, each of which must be 0."""
        plan = bucket_sort.resolve_plan(self.n, self.dtype, self.cfg)
        return {
            "non_native_plans": int(plan.impl != "pallas"
                                    or plan.interpret is not False),
            "degradations": len(guard.degradation_log()),
        }


def build(config: dict, devices, n: int) -> Entry:
    return Entry(config, devices, n)
