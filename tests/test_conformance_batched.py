"""Differential conformance, part 3: batched, segmented and top-k
entry points (see ``tests/test_conformance.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conformance_cases import (
    ALL_DTYPES,
    CELLS,
    DTYPES,
    ORDERS,
    WIDE_DTYPES,
    dtype_ctx,
    make_jnp_keys,
    make_keys,
    npc,
    order_cfg,
)
from repro.core import bucket_sort, partial_sort


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("length", [40, 700])  # direct path + bucket round
def test_batched_matches_jnp_rows(rng, cfg, dtype, length):
    b = 5  # odd batch: exercises the row_pad path on pallas cells
    x = np.stack([make_keys(dtype, length, rng, signed_zero=False)
                  for _ in range(b)])
    xj = jnp.asarray(x)
    got = np.asarray(bucket_sort.sort_batched(xj, cfg))
    np.testing.assert_array_equal(got, np.asarray(jnp.sort(xj, axis=-1)))
    gotp = np.asarray(bucket_sort.argsort_batched(xj, cfg))
    np.testing.assert_array_equal(
        gotp, np.asarray(jnp.argsort(xj, axis=-1, stable=True))
    )


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ALL_DTYPES)
@pytest.mark.parametrize("order", ORDERS)
def test_batched_all_dtypes_both_orders(rng, cfg, dtype, order):
    """sort_batched/argsort_batched over the full codec matrix."""
    b, length = 5, 700
    desc = order == "desc"
    with dtype_ctx(dtype):
        x = jnp.stack([make_jnp_keys(dtype, length, rng, signed_zero=False)
                       for _ in range(b)])
        c = order_cfg(cfg, order)
        got = npc(bucket_sort.sort_batched(x, c))
        want = npc(jnp.sort(x, axis=-1, descending=desc))
        gotp = np.asarray(bucket_sort.argsort_batched(x, c))
        wantp = np.asarray(
            jnp.argsort(x, axis=-1, stable=True, descending=desc)
        )
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(gotp, wantp)


@pytest.mark.parametrize("cfg", CELLS)
def test_sort_kv_batched_matches_jnp_rows(rng, cfg):
    b, length = 4, 700
    x = rng.integers(0, 50, (b, length)).astype(np.int32)  # heavy ties
    vals = rng.normal(size=(b, length, 2)).astype(np.float32)
    sk, sv = bucket_sort.sort_kv_batched(
        jnp.asarray(x), jnp.asarray(vals), cfg
    )
    perm = np.argsort(x, axis=-1, kind="stable")
    np.testing.assert_array_equal(np.asarray(sk), np.sort(x, axis=-1))
    np.testing.assert_array_equal(
        np.asarray(sv), np.take_along_axis(vals, perm[:, :, None], axis=1)
    )


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_segmented_matches_jnp_per_segment(rng, cfg, dtype):
    n = 1200
    x = make_keys(dtype, n, rng, signed_zero=False)
    # empty, single-element, and > direct_max segments
    off = [0, 0, 1, 5, 600, 600, 900, n]
    xj = jnp.asarray(x)
    got = np.asarray(bucket_sort.segment_sort(xj, off, cfg))
    gotp = np.asarray(bucket_sort.segment_argsort(xj, off, cfg))
    for lo, hi in zip(off, off[1:]):
        seg = jnp.asarray(x[lo:hi])
        np.testing.assert_array_equal(got[lo:hi], np.asarray(jnp.sort(seg)))
        np.testing.assert_array_equal(
            gotp[lo:hi], lo + np.asarray(jnp.argsort(seg, stable=True))
        )


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ["int64", "float64", "bool"])
@pytest.mark.parametrize("order", ORDERS)
def test_segmented_wide_dtypes_both_orders(rng, cfg, dtype, order):
    """segment_sort/segment_argsort over codec satellites + descending."""
    n = 1200
    desc = order == "desc"
    off = [0, 0, 1, 5, 600, 600, 900, n]
    with dtype_ctx(dtype):
        x = make_jnp_keys(dtype, n, rng, signed_zero=False)
        c = order_cfg(cfg, order)
        got = np.asarray(bucket_sort.segment_sort(x, off, c))
        gotp = np.asarray(bucket_sort.segment_argsort(x, off, c))
        want, wantp = [], []
        for lo, hi in zip(off, off[1:]):
            want.append(np.asarray(jnp.sort(x[lo:hi], descending=desc)))
            wantp.append(lo + np.asarray(
                jnp.argsort(x[lo:hi], stable=True, descending=desc)
            ))
    for (lo, hi), w, wp in zip(zip(off, off[1:]), want, wantp):
        np.testing.assert_array_equal(got[lo:hi], w)
        np.testing.assert_array_equal(gotp[lo:hi], wp)


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("n", [300, 1500])  # direct path + partial round
def test_topk_matches_lax(rng, cfg, dtype, n):
    k = 16
    if dtype == "int32":
        x = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    else:
        x = rng.normal(size=n).astype(np.float32)
        x[rng.integers(0, n, 5)] = np.asarray(
            [np.inf, -np.inf, 0.0, 1.0, -1.0], np.float32
        )
    tv, ti = partial_sort.topk(jnp.asarray(x), k, cfg)
    lv, li = jax.lax.top_k(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(lv))
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(li))


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", WIDE_DTYPES)
@pytest.mark.parametrize("n", [300, 1500])  # direct path + partial round
def test_topk_wide_dtypes_matches_lax(rng, cfg, dtype, n):
    """topk over the codec satellites (two-word 64-bit, bf16, bool —
    bool is ALL ties: pure index-tiebreak conformance)."""
    k = 16
    with dtype_ctx(dtype):
        x = make_jnp_keys(dtype, n, rng, signed_zero=False)
        tv, ti = partial_sort.topk(x, k, cfg)
        lv, li = jax.lax.top_k(x, k)
        got = (npc(tv), np.asarray(ti))
        want = (npc(lv), np.asarray(li))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("n", [300, 1500])  # direct path + partial round
def test_topk_batched_matches_lax(rng, cfg, dtype, n):
    b, k = 5, 16
    if dtype == "int32":
        x = rng.integers(0, 40, (b, n)).astype(np.int32)  # heavy ties
    else:
        x = rng.normal(size=(b, n)).astype(np.float32)
    tv, ti = partial_sort.topk_batched(jnp.asarray(x), k, cfg)
    lv, li = jax.lax.top_k(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(lv))
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(li))


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", WIDE_DTYPES)
def test_topk_batched_wide_dtypes_matches_lax(rng, cfg, dtype):
    b, k, n = 5, 16, 1500
    with dtype_ctx(dtype):
        x = jnp.stack([make_jnp_keys(dtype, n, rng, signed_zero=False)
                       for _ in range(b)])
        tv, ti = partial_sort.topk_batched(x, k, cfg)
        lv, li = jax.lax.top_k(x, k)
        got = (npc(tv), np.asarray(ti))
        want = (npc(lv), np.asarray(li))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
