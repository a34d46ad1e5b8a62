"""LSD radix-rank local sort over canonical uint32 key words.

The "radix" entry of the hybrid strategy dispatch (DESIGN.md §8): the
GPU sorting surveys (arXiv 1709.02520; arXiv 1511.03404) show radix
ranking dominating comparison networks on narrow integer keys, and the
key-codec layer (DESIGN.md §6) reduces EVERY dtype to canonical uint32
word tuples — so one radix formulation covers them all.  Multi-word
keys are handled word by word from the LEAST significant word: each
full word is consumed in ``32 / radix_bits`` stable digit passes, and
LSD stability makes the composition lexicographic over the words.

STRATEGY CONTRACT (shared with kernels/merge.py): this is a STABLE sort
keyed on the key words ONLY — the int32 payload rides along but does
not participate in comparisons.  Inside the pipeline that is exactly
equivalent to the bitonic path's lexicographic ``(*words, payload)``
order, because the executor maintains the invariant that equal-key
elements always arrive in increasing-payload order (entry payloads are
per-row ``arange``; relocation, sampling, padding and compaction all
preserve relative order of equal keys).  Callers outside the pipeline
must pass payloads that respect that invariant (e.g. ``arange`` rows).

Digit ranking is SCATTER-FREE (the DESIGN.md §4 rule): a pass never
builds a destination scatter.  Per (block_rows, T) block it computes,
for every DESTINATION slot, the source element that lands there:

  1. pack per-segment digit counts into uint32 counters (C = 8 elements
     per segment, one 4-bit field per digit, ``ceil(D/8)`` counter
     words) and Hillis-Steele-scan them WITHIN each segment — 4-bit
     fields cannot overflow since a segment holds 8 elements;
  2. unpack segment totals to (rows, S, D) counts and scan across the
     S segments, giving every (segment, digit) an inclusive prefix;
  3. per destination slot: find its digit (compare against the D
     exclusive digit starts), then its source segment (binary search of
     the inclusive segment prefixes — ``ceil(log2(S+1))`` steps), then
     its source element within the segment (binary search of the packed
     intra-segment prefix fields — ``ceil(log2(C))`` steps), and gather.

The same pure-jnp formulation is the Pallas kernel body (via
``bitonic.tile_sort_call``) and is directly differential-testable.  On
the xla path a documented STAND-IN is used instead (the same precedent
as the bitonic path's ``lax.sort`` oracle, kernels/ref.py): each digit
pass sorts the composite key ``(digit << log2(T)) | position`` with a
single-key ``lax.sort`` — stable by construction, and measured ~2.5x
faster than the two-key oracle on CPU at (256, 4096) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitonic import as_words, like_words, tile_sort_call

# Elements per scan segment: one packed uint32 holds 8 x 4-bit digit
# counters, and a segment of 8 elements can never overflow a field.
_SEG = 8


def _hillis(x, n: int, axis: int = -1):
    """Inclusive Hillis-Steele prefix sum of length-n axis (log2(n)
    shifted adds — branch-free, no gathers)."""
    k = 1
    while k < n:
        pad = jnp.zeros_like(jax.lax.slice_in_dim(x, 0, k, axis=axis))
        shifted = jnp.concatenate(
            [pad, jax.lax.slice_in_dim(x, 0, x.shape[axis] - k, axis=axis)],
            axis=axis,
        )
        x = x + shifted
        k *= 2
    return x


def digit_rank(d: jax.Array, num_digits: int) -> jax.Array:
    """Source permutation of one stable counting pass.

    Args:
        d: (rows, T) int32 digits in [0, num_digits); T a power of two.
        num_digits: D <= 16 (so D 4-bit counters fit two uint32 words).
    Returns:
        (rows, T) int32 ``src`` with ``take(x, src)`` = x stably sorted
        by digit (equal digits keep their order).
    """
    rows, t = d.shape
    assert t & (t - 1) == 0, t
    assert 2 <= num_digits <= 16, num_digits
    if t == 1:
        return jnp.zeros((rows, 1), jnp.int32)
    c = min(_SEG, t)
    s = t // c
    n_arr = (num_digits + _SEG - 1) // _SEG  # packed counter words

    # 1. packed per-segment counters + intra-segment inclusive scan.
    fld = ((d & (_SEG - 1)) << 2).astype(jnp.uint32)
    enc = jnp.uint32(1) << fld
    arr_id = d >> 3
    pres = [
        _hillis(
            jnp.where(arr_id == a, enc, jnp.uint32(0)).reshape(rows, s, c), c
        )
        for a in range(n_arr)
    ]  # (rows, S, C) each
    sh4 = (jnp.arange(_SEG, dtype=jnp.uint32) << 2)[None, None, :]

    # 2. unpack segment totals -> (rows, S, D) counts, scan across segments.
    cnt = jnp.concatenate(
        [((p[:, :, -1:] >> sh4) & 15).astype(jnp.int32) for p in pres],
        axis=2,
    )[:, :, :num_digits]
    inc_seg = _hillis(cnt, s, axis=1)  # (rows, S, D) inclusive over segments
    tot = inc_seg[:, -1, :]  # (rows, D)
    start = jnp.cumsum(tot, axis=1) - tot  # (rows, D) exclusive digit starts

    # 3a. digit of each destination slot: last k with start[k] <= p.
    # D compares instead of a searchsorted gather (kernel-friendly).
    p = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    j = -jnp.ones((rows, t), jnp.int32)
    for k in range(num_digits):
        j = j + (start[:, k:k + 1] <= p).astype(jnp.int32)
    q = p - jnp.take_along_axis(start, j, axis=1)

    # 3b. source segment: first seg with inclusive count > q.  The
    # unknown interval [lo, hi) over [0, S] needs ceil(log2(S+1)) =
    # S.bit_length() halvings; the answer is always < S (q < tot), so
    # mid stays in bounds throughout.
    flat = inc_seg.reshape(rows, s * num_digits)
    lo = jnp.zeros((rows, t), jnp.int32)
    hi = jnp.full((rows, t), s, jnp.int32)
    for _ in range(s.bit_length()):
        mid = (lo + hi) >> 1
        cmid = jnp.take_along_axis(flat, mid * num_digits + j, axis=1)
        gt = cmid > q
        hi = jnp.where(gt, mid, hi)
        lo = jnp.where(gt, lo, mid + 1)
    seg = lo
    excl = jnp.where(
        seg > 0,
        jnp.take_along_axis(
            flat, jnp.maximum(seg - 1, 0) * num_digits + j, axis=1
        ),
        0,
    )
    qs = q - excl  # rank within the source segment

    # 3c. source element within the segment: first c with packed
    # intra-segment prefix field > qs (inclusive-range search with an
    # update mask, ceil(log2(C)) steps).
    if c == 1:
        return seg
    pcat = jnp.concatenate([pr.reshape(rows, t) for pr in pres], axis=1)
    fldj = ((j & (_SEG - 1)) << 2).astype(jnp.uint32)
    base = (j >> 3) * t + seg * c
    lo2 = jnp.zeros((rows, t), jnp.int32)
    hi2 = jnp.full((rows, t), c - 1, jnp.int32)
    for _ in range((c - 1).bit_length()):
        mid = (lo2 + hi2) >> 1
        pv = jnp.take_along_axis(pcat, base + mid, axis=1)
        cmid = ((pv >> fldj) & jnp.uint32(15)).astype(jnp.int32)
        gt = cmid > qs
        upd = lo2 < hi2
        hi2 = jnp.where(upd & gt, mid, hi2)
        lo2 = jnp.where(upd & ~gt, mid + 1, lo2)
    return seg * c + lo2


def radix_sort_rows(keys, vals: jax.Array, *, radix_bits: int = 4):
    """Stable LSD radix sort of each row of (rows, T) by the key words.

    The shared strategy formulation: the Pallas kernel body AND the
    reference implementation.  ``32 / radix_bits`` digit passes per
    word, least-significant word first; each pass is a scatter-free
    rank (:func:`digit_rank`) + one gather per array.

    Args:
        keys: (rows, T) uint32 word array or tuple (msw first).
        vals: (rows, T) int32 payloads (carried, NOT compared — see the
            strategy contract in the module docstring).
        radix_bits: digit width in {1, 2, 4}.
    Returns:
        (sorted keys in the input structure, payloads moved alongside).
    """
    assert radix_bits in (1, 2, 4), radix_bits
    words = as_words(keys)
    rows, t = words[0].shape
    if t == 1:
        return like_words(words, keys), vals
    num_digits = 1 << radix_bits
    parts = list(words) + [vals]
    for wi in reversed(range(len(words))):  # least significant word first
        for sh in range(0, 32, radix_bits):
            d = (
                (parts[wi] >> jnp.uint32(sh)) & jnp.uint32(num_digits - 1)
            ).astype(jnp.int32)
            src = digit_rank(d, max(num_digits, 2))
            parts = [jnp.take_along_axis(x, src, axis=1) for x in parts]
    return like_words(tuple(parts[:-1]), keys), parts[-1]


# ----------------------------------------------------------------------
# Pallas entry points (mirror kernels/bitonic.py)
# ----------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("radix_bits", "block_rows", "interpret")
)
def sort_tiles_kv(
    keys,
    vals: jax.Array,
    *,
    radix_bits: int = 4,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Row-blocked Pallas radix sort of (m, T) tiles (strategy="radix").

    Args/Returns: as ``bitonic.sort_tiles_kv``, but rows are sorted by
    the radix rank-gather passes (stable, key words only — see the
    strategy contract above).
    """
    words = as_words(keys)
    out = tile_sort_call(
        words, vals, 0, block_rows, interpret,
        sort_rows=functools.partial(radix_sort_rows, radix_bits=radix_bits),
        name="radix_tile_sort",
    )
    return like_words(tuple(out[:-1]), keys), out[-1]


@functools.partial(
    jax.jit,
    static_argnames=("num_samples", "radix_bits", "block_rows", "interpret"),
)
def sort_tiles_sample_kv(
    keys,
    vals: jax.Array,
    *,
    num_samples: int,
    radix_bits: int = 4,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Radix tile sort with the Step-3 sample epilogue fused in
    (same layout contract as ``bitonic.sort_tiles_sample_kv``)."""
    words = as_words(keys)
    nw = len(words)
    out = tile_sort_call(
        words, vals, num_samples, block_rows, interpret,
        sort_rows=functools.partial(radix_sort_rows, radix_bits=radix_bits),
        name="radix_tile_sort",
    )
    return (
        like_words(tuple(out[:nw]), keys),
        out[nw],
        like_words(tuple(out[nw + 1:2 * nw + 1]), keys),
        out[2 * nw + 1],
    )


# ----------------------------------------------------------------------
# xla stand-in: composite-key single-key lax.sort passes
# ----------------------------------------------------------------------


def composite_sort_rows(keys, vals: jax.Array):
    """Stable LSD radix sort via composite single-key ``lax.sort`` passes
    — the documented xla STAND-IN for the radix strategy (the same
    proxy pattern as ref.py for bitonic; see the module docstring).

    Each pass sorts ``(digit << log2(T)) | position`` as ONE uint32 key:
    the position bits make the pass stable and directly encode the
    source permutation, which is composed across passes and applied
    once at the end.  Digit width is ``min(16, 32 - log2(T))`` bits, so
    a 32-bit word costs 2 passes for tiles up to 2^16.
    """
    words = as_words(keys)
    rows, t = words[0].shape
    if t == 1:
        return like_words(words, keys), vals
    assert t & (t - 1) == 0, t
    pb = (t - 1).bit_length()  # log2(T) position bits
    db = min(16, 32 - pb)
    assert db >= 1, f"tile width {t} too large for composite radix"
    pos = jax.lax.broadcasted_iota(jnp.uint32, (rows, t), 1)
    mask_pos = jnp.uint32(t - 1)
    src_total = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    for wi in reversed(range(len(words))):  # least significant word first
        w = words[wi]
        for sh in range(0, 32, db):
            bits = min(db, 32 - sh)
            cur = jnp.take_along_axis(w, src_total, axis=1)
            d = (cur >> jnp.uint32(sh)) & jnp.uint32((1 << bits) - 1)
            comp = (d << jnp.uint32(pb)) | pos
            comp = jax.lax.sort(comp, dimension=1)
            src = (comp & mask_pos).astype(jnp.int32)
            src_total = jnp.take_along_axis(src_total, src, axis=1)
    out_words = tuple(
        jnp.take_along_axis(w, src_total, axis=1) for w in words
    )
    return (
        like_words(out_words, keys),
        jnp.take_along_axis(vals, src_total, axis=1),
    )


def composite_sort_sample_rows(keys, vals: jax.Array, *, num_samples: int):
    """Stand-in for the fused sort+sample entry: composite radix sort,
    then the s equidistant samples by reshape + slice (as ref.py)."""
    sk, sv = composite_sort_rows(keys, vals)
    words = as_words(sk)
    m, t = words[0].shape
    assert t % num_samples == 0, (t, num_samples)
    chunk = t // num_samples
    samples = tuple(
        a.reshape(m, num_samples, chunk)[:, :, -1] for a in words + (sv,)
    )
    return sk, sv, like_words(tuple(samples[:-1]), keys), samples[-1]
