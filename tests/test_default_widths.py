"""The sort at the widths every benchmark cell runs: the default
``SortConfig`` geometry (tile 4096, s 64, ``direct_max`` 16384, 128-lane
blocked compaction) on the pure-jnp path, against numpy's stable
argsort.

The sizes sit on both sides of the tile and of ``direct_max``, and go
up to two bucket levels' worth of rows (131072 = 2^17, the MoE cell's
size).  The conformance suites use tiles of 128-256 keys; this file is
the one that runs a whole bucket round at the chip's widths.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conformance_cases import make_keys
from repro.core import bucket_sort
from repro.core.plan import build_plan
from repro.core.sort_config import SortConfig

CFG = SortConfig(impl="xla")

SIZES = [4095, 4096, 4097, 16383, 16384, 16385, 65537, 131072]


def _x64(dtype):
    return jax.enable_x64(True) if dtype == "int64" else contextlib.nullcontext()


def test_default_geometry():
    assert (CFG.tile, CFG.s, CFG.direct_max) == (4096, 64, 16384)
    root = build_plan(16385, "int32", CFG).root
    assert root.kind == "bucket" and root.compact_block == 128


@pytest.mark.parametrize("dtype", ["int32", "int64", "float32"])
@pytest.mark.parametrize("n", SIZES)
def test_argsort_matches_numpy_stable(n, dtype, rng):
    kind = "direct" if n <= CFG.direct_max else "bucket"
    assert build_plan(n, dtype, CFG).root.kind == kind
    with _x64(dtype):
        x = make_keys(dtype, n, rng, signed_zero=False)
        got = np.asarray(bucket_sort.argsort(jnp.asarray(x), CFG))
    np.testing.assert_array_equal(got, np.argsort(x, kind="stable"))


@pytest.mark.parametrize("n", SIZES)
def test_sort_kv_carries_index_payload(n, rng):
    x = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    idx = np.arange(n, dtype=np.int32)
    keys, vals = bucket_sort.sort_kv(jnp.asarray(x), jnp.asarray(idx), CFG)
    perm = np.argsort(x, kind="stable")
    np.testing.assert_array_equal(np.asarray(keys), x[perm])
    np.testing.assert_array_equal(np.asarray(vals), perm)


def test_moe_expert_ids_keep_token_order(rng):
    """The MoE cell's key shape: 16384 tokens, each routed to 8 distinct
    experts of 256, flattened token-major into 131072 expert ids (about
    512 ties a key).  Within an expert the slots stay in token order."""
    tokens, experts, top_k = 16384, 256, 8
    ids = np.argsort(rng.random((tokens, experts)), axis=1)[:, :top_k]
    x = ids.reshape(-1).astype(np.int32)
    got = np.asarray(bucket_sort.argsort(jnp.asarray(x), CFG))
    np.testing.assert_array_equal(got, np.argsort(x, kind="stable"))
    assert np.bincount(x, minlength=experts).max() > 400
    sorted_ids = x[got]
    ties = sorted_ids[1:] == sorted_ids[:-1]
    assert ties.sum() == x.size - experts
    assert (np.diff(got)[ties] > 0).all()
