"""Differential conformance suite: one parametrized harness asserting
every public sort API agrees with the jnp oracles (jnp.sort /
jnp.argsort / jax.lax.top_k) across

  * dtypes: int32 / uint32 / float32 / int64 / uint64 / float64 /
    bfloat16 / bool incl. NaN, +/-inf, -0.0 (64-bit dtypes run under
    the enable_x64 context — see the ``x64`` fixture);
  * ascending AND descending (``SortConfig.descending``, vs the
    ``jnp.sort(..., descending=True)`` oracles);
  * sizes crossing every cell's ``direct_max`` and tile boundaries;
  * both relocation paths (scatter-free gather + legacy scatter);
  * impl="xla" and interpreted Pallas.

No xfails anywhere: every (api, dtype, order, impl, relocation) cell
must pass.

Float caveats, pinned down so the oracle comparison is EXACT:
  * Our total order ranks sign-bit ("negative") NaNs first; jnp.sort
    follows numpy and puts ALL NaNs last.  Inputs here use np.nan — a
    positive quiet NaN — whose single bit pattern both orders place
    last (first when descending), stably by index.
  * Our total order ranks -0.0 < +0.0 strictly; numpy/jnp treat them as
    equal (stable) keys.  Value comparisons are unaffected
    (assert_array_equal treats -0.0 == +0.0), so ``sort`` inputs
    include -0.0; exact PERMUTATION comparisons (argsort) drop it.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from conformance_cases import (
    ALL_DTYPES,
    CELLS,
    DTYPES,
    ORDERS,
    SIZES,
    dtype_ctx,
    make_jnp_keys,
    make_keys,
    npc,
    order_cfg,
)
from repro.core import bucket_sort


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n", SIZES)
def test_sort_matches_jnp(rng, cfg, dtype, n):
    x = make_keys(dtype, n, rng)
    got = np.asarray(bucket_sort.sort(jnp.asarray(x), cfg))
    want = np.asarray(jnp.sort(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n", SIZES)
def test_argsort_matches_jnp(rng, cfg, dtype, n):
    x = make_keys(dtype, n, rng, signed_zero=False)
    got = np.asarray(bucket_sort.argsort(jnp.asarray(x), cfg))
    want = np.asarray(jnp.argsort(jnp.asarray(x), stable=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cfg", CELLS)
@pytest.mark.parametrize("dtype", ALL_DTYPES)
@pytest.mark.parametrize("order", ORDERS)
def test_sort_kv_matches_jnp_permutation(rng, cfg, dtype, order):
    n = 700  # crosses both cells' direct_max
    desc = order == "desc"
    with dtype_ctx(dtype):
        x = make_jnp_keys(dtype, n, rng, signed_zero=False)
        vals = rng.normal(size=(n, 3)).astype(np.float32)
        sk, sv = bucket_sort.sort_kv(x, jnp.asarray(vals),
                                     order_cfg(cfg, order))
        perm = np.asarray(jnp.argsort(x, stable=True, descending=desc))
        want_k = npc(jnp.sort(x, descending=desc))
        got_k, got_v = npc(sk), np.asarray(sv)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, vals[perm])


# ----------------------------------------------------------------------
# Key-codec property: encode/decode is an order-preserving bijection
# ----------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ALL_DTYPES + ["float16", "int16", "int8",
                                                "uint16", "uint8"])
@pytest.mark.parametrize("order", ORDERS)
def test_codec_roundtrip_and_order(rng, dtype, order):
    """For every codec dtype and both orders:

      * decode(encode(x)) == x elementwise (bijection on values;
        NaN == NaN under assert_array_equal);
      * lexicographic unsigned order of the encoded words + index
        tiebreak reproduces jnp's stable (arg)sort exactly
        (order preservation), signed zeros excluded as ties.
    """
    from repro.core.key_codec import codec_for

    desc = order == "desc"
    n = 403
    with dtype_ctx(dtype):
        if dtype in ("float16", "int16", "int8", "uint16", "uint8"):
            base = rng.normal(size=n).astype(np.float32) * 100
            x = jnp.asarray(base).astype(dtype)
        else:
            x = make_jnp_keys(dtype, n, rng, signed_zero=False)
        codec = codec_for(x.dtype, desc)
        assert codec.dtype == x.dtype and codec.num_words in (1, 2)
        words = codec.encode(x)
        assert len(words) == codec.num_words
        assert all(w.dtype == jnp.uint32 and w.shape == x.shape
                   for w in words)
        back = codec.decode(words)
        assert back.dtype == x.dtype
        np.testing.assert_array_equal(npc(back), npc(x))
        # Order preservation: lexsort(words, index) == stable argsort.
        wnp = [np.asarray(w) for w in words]
        perm = np.lexsort(tuple([np.arange(n)] + list(reversed(wnp))))
        want = np.asarray(jnp.argsort(x, stable=True, descending=desc))
    np.testing.assert_array_equal(perm, want)
