"""Drives ``repro.core.make_sharded_sort`` over a mesh of the cell's chips.

The keys are sharded evenly over the mesh's one axis.  The run wrapper
that ``make_sharded_sort`` returns is called as a caller calls it; its
``last_stats`` are read after every call, so a retry or a fall-back to
the host in the window shows as a fault of the run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed_sort, guard, make_sharded_sort
from repro.core.sort_config import SortConfig

_PHASES = ("run_plan", "dealt_plan", "sample_plan", "bucket_plan")


class Entry:
    def __init__(self, config: dict, devices, n: int):
        mesh_cfg = config["mesh"]
        chips = int(np.prod(mesh_cfg["shape"]))
        self.mesh = jax.make_mesh(
            tuple(mesh_cfg["shape"]), tuple(mesh_cfg["axes"]),
            devices=devices[:chips],
            axis_types=(jax.sharding.AxisType.Auto,) * len(mesh_cfg["axes"]))
        self.axis = mesh_cfg["axes"][0]
        self.sharding = NamedSharding(self.mesh, P(self.axis))
        self.run, self.plan = make_sharded_sort(
            self.mesh, self.axis, n, SortConfig(**config["sort_config"]),
            config["oversample"], dtype=jnp.dtype(config["dtype"]),
            pair_align=config["pair_align"])
        self.n = n
        self.retries = 0
        self.degraded = 0

    def __call__(self, x: jax.Array):
        out = self.run(x)
        stats = self.run.last_stats
        self.retries += stats["retries"]
        self.degraded += int(stats["degraded"])
        return out

    def permutation(self, out) -> np.ndarray:
        """Global permutation: each shard's valid payload prefix, in order."""
        _, vals, counts, _ = out
        vals, counts = np.asarray(vals), np.asarray(counts)
        cap = self.plan.out_cap
        return np.concatenate(
            [vals[i * cap:i * cap + counts[i]] for i in range(len(counts))])

    def trace_count(self) -> int:
        return distributed_sort.trace_count()

    def faults(self) -> dict[str, int]:
        """Counts that make a run unsound, each of which must be 0."""
        plans = [self.plan] + [getattr(self.plan, p) for p in _PHASES]
        return {
            "non_native_plans": sum(
                int(p.impl != "pallas" or p.interpret is not False)
                for p in plans),
            "degradations": len(guard.degradation_log()),
            "mesh_retries": self.retries,
            "mesh_degraded": self.degraded,
        }


def build(config: dict, devices, n: int) -> Entry:
    return Entry(config, devices, n)
