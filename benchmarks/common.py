"""Benchmark utilities: timing + distribution generators (paper §5)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timeit(fn, *args, repeats: int = 3, warmup: int = 1):
    """Median wall time of fn(*args) with block_until_ready."""
    return timeit_stats(fn, *args, repeats=repeats, warmup=warmup)[0]


def timeit_stats(fn, *args, repeats: int = 3, warmup: int = 1):
    """Median-of-k timing with warmup-discard: run ``warmup`` calls
    (compile + cache effects, discarded), then ``repeats`` timed calls.

    Returns ``(median_s, spread)`` where spread is the relative
    half-range ``(max - min) / (2 * median)`` of the timed samples — a
    cheap noise indicator for rank-sensitive measurements (the autotune
    suite records it so flipped winners are attributable to timer
    noise rather than model error).
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    med = float(np.median(ts))
    spread = float((max(ts) - min(ts)) / (2 * med)) if med > 0 else 0.0
    return med, spread


def forced_host_child(script: str, flag: str, d: int, *args) -> dict:
    """Run ``script flag d *args`` in a child whose CPU backend has ``d``
    forced host devices; return the dict it prints on its ``RESULT``
    line.  CPU only: the device count is fixed before jax is imported,
    and on a TPU the child would find the chips held by this process."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={d}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_ROOT, os.path.join(_ROOT, "src"),
                    env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run(
        [sys.executable, script, flag, str(d), *map(str, args)],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{os.path.basename(script)} child d={d} failed:\n"
            f"{proc.stderr[-2000:]}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def spearman(a, b) -> float:
    """Spearman rank correlation of two equal-length sequences (no
    scipy dependency; average ranks are not needed for the distinct
    predicted costs the autotune suite feeds in)."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    n = len(a)
    if n < 2:
        return 1.0

    def _ranks(v):
        r = np.empty(n)
        r[np.argsort(v, kind="stable")] = np.arange(n)
        return r

    ra, rb = _ranks(a), _ranks(b)
    return float(1.0 - 6.0 * np.sum((ra - rb) ** 2) / (n * (n * n - 1)))


# The six input distributions of Leischner et al. (the randomized sample
# sort paper) which the deterministic algorithm is immune to (C2).
def make_distribution(name: str, n: int, rng: np.random.Generator):
    if name == "uniform":
        return rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    if name == "gaussian":
        return (rng.normal(0, 2**29, n)).astype(np.int32)
    if name == "zipf":
        return (rng.zipf(1.3, n) % (2**31 - 1)).astype(np.int32)
    if name == "sorted":
        return np.sort(rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32))
    if name == "reverse":
        return np.sort(rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32))[::-1].copy()
    if name == "all-equal":
        return np.full(n, 123456789, np.int32)
    if name == "bucket-killer":
        # many duplicates of a few values — worst case for naive splitters
        return rng.choice(np.array([3, 7, 11], np.int32), n)
    raise KeyError(name)


DISTRIBUTIONS = ["uniform", "gaussian", "zipf", "sorted", "reverse", "all-equal"]
