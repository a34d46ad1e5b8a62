"""Differential conformance, part 2: sort and argsort over every
supported dtype in both orders (see ``tests/test_conformance.py``),
and the local sort on both sides of each cell's direct_max."""

import jax.numpy as jnp
import numpy as np
import pytest

from conformance_cases import (
    ALL_DTYPES,
    CELLS,
    ORDERS,
    PAL,
    XLA,
    dtype_ctx,
    make_jnp_keys,
    make_keys,
    npc,
    order_cfg,
)
from repro.core import bucket_sort


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ALL_DTYPES)
@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("n", [5, 700])  # direct path + bucket round
def test_sort_all_dtypes_both_orders(rng, cfg, dtype, order, n):
    """The key-codec matrix: every codec dtype, ascending and
    descending, vs the jnp.sort oracle (values; NaN/±inf/-0.0 in)."""
    desc = order == "desc"
    with dtype_ctx(dtype):
        x = make_jnp_keys(dtype, n, rng)
        got = npc(bucket_sort.sort(x, order_cfg(cfg, order)))
        want = npc(jnp.sort(x, descending=desc))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ALL_DTYPES)
@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("n", [5, 700])
def test_argsort_all_dtypes_both_orders(rng, cfg, dtype, order, n):
    """Exact stable permutations for the full codec matrix (signed
    zeros dropped — see module docstring)."""
    desc = order == "desc"
    with dtype_ctx(dtype):
        x = make_jnp_keys(dtype, n, rng, signed_zero=False)
        got = np.asarray(bucket_sort.argsort(x, order_cfg(cfg, order)))
        want = np.asarray(jnp.argsort(x, stable=True, descending=desc))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cfg", [pytest.param(XLA, id="xla"),
                                 pytest.param(PAL, id="pallas-interpret")])
@pytest.mark.parametrize("dtype", ["int32", "uint32", "int64", "float32"])
def test_local_sort_conformance(cfg, dtype, rng):
    """The small size stays on the direct path; the large one crosses
    the cell's direct_max into a bucket round: values and stable
    permutations against numpy."""
    sizes = (127, 1500) if cfg.impl == "xla" else (63, 300)
    for n in sizes:
        with dtype_ctx(dtype):
            x = make_keys(dtype, n, rng, signed_zero=False)
            out = np.asarray(bucket_sort.sort(jnp.asarray(x), cfg))
            np.testing.assert_array_equal(out, np.sort(x))
            perm = np.asarray(bucket_sort.argsort(jnp.asarray(x), cfg))
            np.testing.assert_array_equal(perm, np.argsort(x, kind="stable"))
