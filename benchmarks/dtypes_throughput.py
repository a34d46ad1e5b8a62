"""Key-codec dtype sweep: 64-bit vs 32-bit sorting throughput.

The §6 cost model predicts 64-bit keys cost up to ~1.5× the 32-bit
wall time (3 words/element moved instead of 2; the extra compare chain
is VPU noise) and ``descending`` costs nothing (codec-level
complement).  This suite records both ratios so the prediction is a
tracked number, not a claim.

Measurement discipline: CPU medians drift ~20% over a multi-minute
suite run (thermal/load), which swamps the effects being measured —
so every (dtype × order) cell is timed ROUND-ROBIN: one call per cell
per round, per-cell medians across rounds.  Drift then hits all cells
alike and the ratios stay honest.  CPU/xla wall-times are proxies for
the TPU target — the RATIO is the reproduced quantity.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bucket_sort
from repro.core.sort_config import SortConfig

CFG = SortConfig(tile=4096, s=64, direct_max=8192, impl="xla")
CFG_DESC = SortConfig(tile=4096, s=64, direct_max=8192, impl="xla",
                      descending=True)

DTYPES = ("int32", "float32", "bfloat16", "int64", "uint64", "float64")
DESC_DTYPES = ("int32", "int64")


def _keys(dtype: str, n: int, rng: np.random.Generator):
    if dtype == "int32":
        return rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    if dtype == "float32":
        return rng.normal(size=n).astype(np.float32)
    if dtype == "bfloat16":
        return rng.normal(size=n).astype(np.float32)  # cast at jnp boundary
    if dtype == "int64":
        return rng.integers(-(2**63), 2**63 - 1, n, dtype=np.int64)
    if dtype == "uint64":
        return rng.integers(0, 2**64, n, dtype=np.uint64)
    if dtype == "float64":
        return rng.normal(size=n).astype(np.float64)
    raise KeyError(dtype)


def run(n=1048576, repeats=3):
    rng = np.random.default_rng(0)
    rows = []
    with jax.enable_x64(True):
        cells = [(dt, False) for dt in DTYPES] + [
            (dt, True) for dt in DESC_DTYPES
        ]
        arrays, fns, samples = {}, {}, {}
        for dt, desc in cells:
            x = jnp.asarray(_keys(dt, n, rng))
            if dt == "bfloat16":
                x = x.astype(jnp.bfloat16)
            cfg = CFG_DESC if desc else CFG
            arrays[(dt, desc)] = x
            fns[(dt, desc)] = jax.jit(
                lambda a, c=cfg: bucket_sort.sort(a, c)
            )
            samples[(dt, desc)] = []
            jax.block_until_ready(fns[(dt, desc)](x))  # warmup/compile
        for _ in range(repeats):  # round-robin: drift hits cells alike
            for cell in cells:
                t0 = time.perf_counter()
                jax.block_until_ready(fns[cell](arrays[cell]))
                samples[cell].append(time.perf_counter() - t0)
        med = {c: float(np.median(s)) for c, s in samples.items()}
        for dt in DTYPES:
            t = med[(dt, False)]
            words = 2 if dt in ("int64", "uint64", "float64") else 1
            rows.append(dict(
                name=f"dtypes/sort_{dt}",
                us_per_call=t * 1e6,
                derived=f"rate={n / t / 1e6:.2f}Mkeys/s words={words} n={n}",
            ))
        for dt in DESC_DTYPES:
            t = med[(dt, True)]
            rows.append(dict(
                name=f"dtypes/sort_{dt}_descending",
                us_per_call=t * 1e6,
                derived=f"vs_ascending={t / med[(dt, False)]:.2f}x "
                        "(round-robin paired)",
            ))
    rows.append(dict(
        name="dtypes/ratio_64bit_vs_32bit",
        us_per_call=0.0,
        derived=(
            f"int64/int32={med[('int64', False)] / med[('int32', False)]:.2f}x "
            f"float64/float32="
            f"{med[('float64', False)] / med[('float32', False)]:.2f}x "
            "(§6 model: <=1.5x data movement)"
        ),
    ))
    return rows
