"""Hypothesis property tests on the system's core invariants:

  * sort output is sorted AND a permutation of the input (any dtype/dist)
  * stability (equal keys keep input order)
  * the paper's guaranteed bucket bound: every round's max bucket fill
    <= capacity and the relocation scatter never drops an element
  * partial top-k == lax.top_k for arbitrary inputs
  * batched sort of B rows == B independent 1-D sorts (DESIGN.md §5)
  * segmented sort never leaks an element across a segment boundary,
    and stability holds per segment
  * key-codec encode/decode is a sorted-order-preserving bijection for
    every dtype (64-bit two-word encodings included, DESIGN.md §6)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dev dep (pip install -e '.[test]')"
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import bucket_sort, partial_sort
from repro.core.key_codec import codec_for
from repro.core.sort_config import SortConfig

CFG = SortConfig(tile=128, s=8, direct_max=256, impl="xla")

ints = st.lists(
    st.integers(min_value=-(2**31), max_value=2**31 - 1), min_size=1, max_size=3000
)
small_ints = st.lists(st.integers(min_value=0, max_value=5), min_size=1, max_size=3000)
floats = st.lists(
    st.floats(width=32, allow_nan=True, allow_infinity=True),
    min_size=1, max_size=2000,
)


@settings(max_examples=25, deadline=None)
@given(ints)
def test_sort_is_sorted_permutation(xs):
    x = np.asarray(xs, np.int32)
    out = np.asarray(bucket_sort.sort(jnp.asarray(x), CFG))
    np.testing.assert_array_equal(out, np.sort(x))


@settings(max_examples=25, deadline=None)
@given(small_ints)
def test_sort_stable_under_duplicates(xs):
    x = np.asarray(xs, np.int32)
    perm = np.asarray(bucket_sort.argsort(jnp.asarray(x), CFG))
    np.testing.assert_array_equal(perm, np.argsort(x, kind="stable"))


@settings(max_examples=15, deadline=None)
@given(floats)
def test_sort_floats_total_order(xs):
    x = np.asarray(xs, np.float32)
    out = np.asarray(bucket_sort.sort(jnp.asarray(x), CFG))
    ref = np.sort(x)  # numpy: NaNs last; ours: -NaN first, +NaN last
    a = np.sort(out[~np.isnan(out)])
    b = ref[~np.isnan(ref)]
    np.testing.assert_array_equal(a, b)
    assert np.isnan(out).sum() == np.isnan(ref).sum()


@settings(max_examples=15, deadline=None)
@given(small_ints)
def test_bucket_bound_guarantee(xs):
    """The paper's core claim: deterministic sampling => bucket fill is
    bounded by the static capacity, for ANY input (worst cases included)."""
    x = np.asarray(xs, np.int32)
    if len(x) <= CFG.direct_max:
        x = np.tile(x, (CFG.direct_max // max(len(x), 1)) + 2)[: CFG.direct_max * 3]
    srt, perm, stats = bucket_sort.sort_with_stats(jnp.asarray(x), CFG)
    assert len(stats) >= 1
    for stt in stats:
        max_fill = int(np.asarray(stt["totals"]).max())
        assert max_fill <= stt["capacity"], (max_fill, stt["capacity"])
        assert int(np.asarray(stt["max_within"])) < stt["capacity"]
    np.testing.assert_array_equal(np.asarray(srt), np.sort(x))


@settings(max_examples=20, deadline=None)
@given(
    st.lists(st.floats(width=32, allow_nan=False, allow_infinity=False),
             min_size=1, max_size=1500),
    st.integers(min_value=1, max_value=64),
)
def test_partial_topk_matches_lax(xs, k):
    x = np.asarray(xs, np.float32)
    k = min(k, len(x))
    tv, ti = partial_sort.topk(jnp.asarray(x), k, CFG)
    lv, li = jax.lax.top_k(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(li))
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(lv))


# ----------------------------------------------------------------------
# Key codec (DESIGN.md §6)
# ----------------------------------------------------------------------


int64s = st.lists(
    st.integers(min_value=-(2**63), max_value=2**63 - 1),
    min_size=1, max_size=500,
)
floats64 = st.lists(
    st.floats(allow_nan=True, allow_infinity=True),
    min_size=1, max_size=500,
)


def _codec_bijection_case(x, descending):
    """encode/decode roundtrips exactly AND the lexicographic unsigned
    word order (index tiebreak) == jnp's stable (arg)sort order."""
    codec = codec_for(x.dtype, descending)
    words = codec.encode(x)
    back = codec.decode(words)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    wnp = [np.asarray(w) for w in words]
    n = x.shape[0]
    perm = np.lexsort(tuple([np.arange(n)] + list(reversed(wnp))))
    want = np.asarray(jnp.argsort(x, stable=True, descending=descending))
    np.testing.assert_array_equal(perm, want)


@settings(max_examples=25, deadline=None)
@given(int64s, st.booleans())
def test_codec_int64_bijection_preserves_order(xs, descending):
    with jax.enable_x64(True):
        _codec_bijection_case(jnp.asarray(np.asarray(xs, np.int64)),
                              descending)


@settings(max_examples=25, deadline=None)
@given(floats64, st.booleans())
def test_codec_float64_bijection_preserves_order(xs, descending):
    """Full float64 range incl. NaN/±inf; signed zeros normalized to
    +0.0 and NaNs to +NaN (our total order ranks -0.0 < +0.0 strictly
    and -NaN first, numpy ties the zeros and puts every NaN last — the
    conformance suite pins the value-level agreement)."""
    x = np.asarray(xs, np.float64)
    x[x == 0.0] = 0.0
    x[np.isnan(x)] = np.nan
    with jax.enable_x64(True):
        _codec_bijection_case(jnp.asarray(x), descending)


# ----------------------------------------------------------------------
# Batched & segmented layer (DESIGN.md §5)
# ----------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    st.lists(st.integers(min_value=-(2**31), max_value=2**31 - 1),
             min_size=1, max_size=800),
    st.integers(min_value=1, max_value=5),
)
def test_batched_sort_equals_independent_sorts(xs, b):
    """sort_batched of B rows == B independent 1-D sorts, bit for bit
    (values AND stable permutations)."""
    row = np.asarray(xs, np.int32)
    x = np.stack([np.roll(row, 13 * i) for i in range(b)])  # distinct rows
    got = np.asarray(bucket_sort.sort_batched(jnp.asarray(x), CFG))
    gotp = np.asarray(bucket_sort.argsort_batched(jnp.asarray(x), CFG))
    for i in range(b):
        np.testing.assert_array_equal(
            got[i], np.asarray(bucket_sort.sort(jnp.asarray(x[i]), CFG))
        )
        np.testing.assert_array_equal(
            gotp[i], np.asarray(bucket_sort.argsort(jnp.asarray(x[i]), CFG))
        )


def _offsets_from_cuts(n, cuts):
    return np.asarray([0] + sorted(c % (n + 1) for c in cuts) + [n], np.int64)


@settings(max_examples=20, deadline=None)
@given(
    st.lists(st.integers(min_value=-(2**31), max_value=2**31 - 1),
             min_size=1, max_size=800),
    st.lists(st.integers(min_value=0, max_value=2**31 - 1), max_size=6),
)
def test_segment_sort_never_leaks_across_boundaries(xs, cuts):
    """Every segment of the output is a sorted PERMUTATION OF THE SAME
    SEGMENT of the input — no element crosses a boundary (empty and
    duplicate offsets included)."""
    x = np.asarray(xs, np.int32)
    off = _offsets_from_cuts(len(x), cuts)
    got = np.asarray(bucket_sort.segment_sort(jnp.asarray(x), off, CFG))
    for lo, hi in zip(off, off[1:]):
        np.testing.assert_array_equal(got[lo:hi], np.sort(x[lo:hi]))


@settings(max_examples=20, deadline=None)
@given(
    st.lists(st.integers(min_value=0, max_value=3), min_size=1, max_size=800),
    st.lists(st.integers(min_value=0, max_value=2**31 - 1), max_size=6),
)
def test_segment_argsort_stable_per_segment(xs, cuts):
    """Heavy duplicates: the per-segment permutation must equal numpy's
    stable argsort of that segment (global indices)."""
    x = np.asarray(xs, np.int32)
    off = _offsets_from_cuts(len(x), cuts)
    perm = np.asarray(bucket_sort.segment_argsort(jnp.asarray(x), off, CFG))
    for lo, hi in zip(off, off[1:]):
        np.testing.assert_array_equal(
            perm[lo:hi], lo + np.argsort(x[lo:hi], kind="stable")
        )
