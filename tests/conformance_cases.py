"""Shared cells, dtypes and key generators of the differential
conformance suite (``tests/test_conformance*.py``).  The suite is
split over several files so that parallel test workers, which take
whole files, share it."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sort_config import SortConfig

XLA = SortConfig(tile=256, s=16, direct_max=512, impl="xla")
PAL = SortConfig(tile=128, s=8, direct_max=256, impl="pallas", interpret=True)

CELLS = [
    pytest.param(XLA, id="xla-gather"),
    pytest.param(dataclasses.replace(XLA, relocation="scatter"),
                 id="xla-scatter"),
    pytest.param(PAL, id="pallas-gather"),
    pytest.param(dataclasses.replace(PAL, relocation="scatter"),
                 id="pallas-scatter"),
]

# Crosses both cells' tile (128/256) and direct_max (256/512) boundaries.
SIZES = [1, 5, 127, 128, 255, 256, 511, 512, 513, 1500]

DTYPES = ["int32", "uint32", "float32"]
# Key-codec satellites: two-word 64-bit keys, widened bf16/bool.  Run
# through the SAME assertions as the core 32-bit dtypes.
WIDE_DTYPES = ["int64", "uint64", "float64", "bfloat16", "bool"]
ALL_DTYPES = DTYPES + WIDE_DTYPES

ORDERS = ["asc", "desc"]


def dtype_ctx(dtype):
    """enable_x64 context for the 64-bit dtypes, no-op otherwise."""
    if dtype in ("int64", "uint64", "float64"):
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def order_cfg(cfg, order):
    return dataclasses.replace(cfg, descending=(order == "desc"))


def make_keys(dtype, n, rng, *, signed_zero=True):
    """Adversarial-ish keys: full-range ints / floats spiked with the
    special values (NaN always np.nan — see module docstring)."""
    if dtype == "int32":
        return rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    if dtype == "uint32":
        return rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    if dtype == "int64":
        return rng.integers(-(2**63), 2**63 - 1, n, dtype=np.int64)
    if dtype == "uint64":
        return rng.integers(0, 2**64, n, dtype=np.uint64)
    if dtype == "bool":
        return rng.integers(0, 2, n).astype(bool)
    # bfloat16 is generated as float32 (specials included below) and cast
    # at the jnp boundary by make_jnp_keys — NaN/±inf/±0.0 are exact in
    # bf16 and finite normals round to valid bf16 ties.
    ftype = np.float64 if dtype == "float64" else np.float32
    big = 1e300 if dtype == "float64" else 1e30
    x = (rng.normal(size=n) * rng.choice([1.0 / big, 1.0, big], n)).astype(
        ftype
    )
    specials = [np.nan, np.inf, -np.inf, 0.0] + ([-0.0] if signed_zero else [])
    idx = rng.integers(0, n, min(n, 25))
    x[idx] = np.asarray(specials, ftype)[
        rng.integers(0, len(specials), len(idx))
    ]
    return x


def npc(a):
    """numpy view for comparisons: bfloat16 -> float32 (numpy's NaN-aware
    assert helpers don't understand ml_dtypes scalars; the f32 embedding
    is exact, so equality semantics are unchanged)."""
    a = np.asarray(a)
    if a.dtype == jnp.bfloat16:
        return a.astype(np.float32)
    return a


def make_jnp_keys(dtype, n, rng, *, signed_zero=True):
    """jnp array of ``dtype`` (inside the right x64 context)."""
    x = jnp.asarray(make_keys(dtype, n, rng, signed_zero=signed_zero))
    if dtype == "bfloat16":
        x = x.astype(jnp.bfloat16)
    return x
