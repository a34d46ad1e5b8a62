"""Merge-path local sort: sorted-run formation + pairwise run merging.

The "merge" entry of the hybrid strategy dispatch (DESIGN.md §8).  The
parallel-sort comparisons (arXiv 1511.03404) show merge-based local
sorts winning on nearly-sorted data: once runs are formed, a merge
level moves every element at most once, whereas the bitonic network
always runs its full O(T log^2 T) compare-exchange schedule.

Algorithm per (block_rows, T) block:

  1. RUN FORMATION: reshape each row into T/r0 runs of ``merge_run``
     elements and sort them with the bitonic network (payload tiebreak
     — runs inherit the full lexicographic order).
  2. MERGE LEVELS: for L = r0, 2*r0, ... < T, merge adjacent run pairs
     (A, B) of length L with MERGE-PATH DIAGONAL PARTITIONING: every
     destination slot p binary-searches its split a in [max(0, p-L),
     min(p, L)] along the diagonal a + b = p — ``ceil(log2(L+2))``
     guarded lexicographic probes — then gathers its source element.
     Scatter-free, O(T log T / log(r0)-ish) data movement, and each
     level is a batched two-pointer merge with NO sequential scan.

Ties go to A (the left run), which preserves stability: the merge is a
STABLE sort keyed on the key words ONLY, the same STRATEGY CONTRACT as
kernels/radix.py — the int32 payload rides along but is not compared
in the merge levels, so callers must supply payloads that increase
within equal keys (the pipeline executor guarantees this; `arange`
payload rows satisfy it trivially).

The pure-jnp formulation below is BOTH the Pallas kernel body (via
``bitonic.tile_sort_call``) and the differential-test reference.  The
xla path uses a documented STAND-IN (the ref.py precedent): runs are
formed with the composite-key radix passes of kernels/radix.py and
merged with bitonic-merge network stages (reverse the right run, then
log2(2L) all-ascending compare-exchange passes with payload tiebreak)
— measured faster than both the two-key ``lax.sort`` oracle and the
full bitonic network on CPU at (256, 4096) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitonic import (
    as_words,
    bitonic_network_rows,
    lex_gt,
    like_words,
    tile_sort_call,
)


def _merge_level(parts, run: int):
    """One merge level: every adjacent pair of sorted length-``run``
    runs in each (rows, T) row of ``parts`` (key words + payload) is
    merged via merge-path diagonal search.  Key-words-only comparison,
    ties to the left run (stable)."""
    words, vals = parts[:-1], parts[-1]
    rows, t = words[0].shape
    pairs = t // (2 * run)
    # Flatten run pairs into rows: (rows * pairs, 2*run).
    wr = [w.reshape(rows * pairs, 2 * run) for w in words]
    vr = vals.reshape(rows * pairs, 2 * run)
    a_w = [w[:, :run] for w in wr]
    b_w = [w[:, run:] for w in wr]
    p = jax.lax.broadcasted_iota(jnp.int32, (rows * pairs, 2 * run), 1)

    def probe(side, idx):
        return [jnp.take_along_axis(w, idx, axis=1) for w in side]

    # Diagonal binary search: find a = #elements taken from A for slot p.
    lo = jnp.maximum(0, p - run)
    hi = jnp.minimum(p, run)
    for _ in range((run + 1).bit_length()):
        mid = (lo + hi) >> 1
        bidx = p - mid - 1
        a_v = probe(a_w, jnp.minimum(mid, run - 1))
        b_v = probe(b_w, jnp.clip(bidx, 0, run - 1))
        take_a = ~lex_gt(a_v, b_v)  # A[mid] <= B[bidx]: ties to A
        take_a = jnp.where(bidx >= run, True, take_a)
        take_a = jnp.where((mid >= run) | (bidx < 0), False, take_a)
        upd = lo < hi
        lo = jnp.where(upd & take_a, mid + 1, lo)
        hi = jnp.where(upd & ~take_a, mid, hi)
    a = lo
    b = p - a
    a_v = probe(a_w, jnp.minimum(a, run - 1))
    b_v = probe(b_w, jnp.clip(b, 0, run - 1))
    take_a = (b >= run) | ((a < run) & ~lex_gt(a_v, b_v))
    src = jnp.where(
        take_a, jnp.minimum(a, run - 1), run + jnp.clip(b, 0, run - 1)
    )
    out = [
        jnp.take_along_axis(x, src, axis=1).reshape(rows, t)
        for x in wr + [vr]
    ]
    return out


def merge_sort_rows(keys, vals: jax.Array, *, merge_run: int = 512):
    """Stable merge-path sort of each row of (rows, T): bitonic-network
    run formation + merge-path levels (the shared strategy formulation:
    Pallas kernel body AND reference implementation).

    Args:
        keys: (rows, T) uint32 word array or tuple (msw first); T a
            power of two.
        vals: (rows, T) int32 payloads (compared only inside the run
            formation; the merge levels carry them — strategy contract).
        merge_run: initial run length r0 (clamped to T).
    Returns:
        (sorted keys in the input structure, payloads moved alongside).
    """
    words = as_words(keys)
    rows, t = words[0].shape
    assert t & (t - 1) == 0, t
    r0 = min(merge_run, t)
    if r0 > 1:
        wr = tuple(w.reshape(-1, r0) for w in words)
        vr = vals.reshape(-1, r0)
        wr, vr = bitonic_network_rows(wr, vr)
        words = tuple(w.reshape(rows, t) for w in wr)
        vals = vr.reshape(rows, t)
    parts = list(words) + [vals]
    run = r0
    while run < t:
        parts = _merge_level(parts, run)
        run *= 2
    return like_words(tuple(parts[:-1]), keys), parts[-1]


# ----------------------------------------------------------------------
# Pallas entry points (mirror kernels/bitonic.py)
# ----------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("merge_run", "block_rows", "interpret")
)
def sort_tiles_kv(
    keys,
    vals: jax.Array,
    *,
    merge_run: int = 512,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Row-blocked Pallas merge-path sort of (m, T) tiles
    (strategy="merge").  Args/Returns: as ``bitonic.sort_tiles_kv``."""
    words = as_words(keys)
    out = tile_sort_call(
        words, vals, 0, block_rows, interpret,
        sort_rows=functools.partial(merge_sort_rows, merge_run=merge_run),
        name="merge_tile_sort",
    )
    return like_words(tuple(out[:-1]), keys), out[-1]


@functools.partial(
    jax.jit,
    static_argnames=("num_samples", "merge_run", "block_rows", "interpret"),
)
def sort_tiles_sample_kv(
    keys,
    vals: jax.Array,
    *,
    num_samples: int,
    merge_run: int = 512,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Merge-path tile sort with the Step-3 sample epilogue fused in
    (same layout contract as ``bitonic.sort_tiles_sample_kv``)."""
    words = as_words(keys)
    nw = len(words)
    out = tile_sort_call(
        words, vals, num_samples, block_rows, interpret,
        sort_rows=functools.partial(merge_sort_rows, merge_run=merge_run),
        name="merge_tile_sort",
    )
    return (
        like_words(tuple(out[:nw]), keys),
        out[nw],
        like_words(tuple(out[nw + 1:2 * nw + 1]), keys),
        out[2 * nw + 1],
    )


# ----------------------------------------------------------------------
# xla stand-in: composite run formation + bitonic-merge network stages
# ----------------------------------------------------------------------


def _bitonic_merge_stage(parts, run: int):
    """Merge adjacent sorted run pairs with the bitonic merge network:
    reverse the right run of each pair (making each 2*run window a
    bitonic sequence), then log2(2*run) all-ascending compare-exchange
    passes.  Comparison is lexicographic on (*words, payload), which
    both resolves ties deterministically and lands exactly on the
    stable order (the pipeline's payload invariant)."""
    rows = parts[0].shape[0]
    width = 2 * run
    rs = []
    for x in parts:
        q = x.reshape(rows, -1, width)
        rs.append(
            jnp.concatenate([q[:, :, :run], q[:, :, run:][:, :, ::-1]], axis=2)
        )
    d = run
    while d >= 1:
        q3 = [q.reshape(rows, -1, width // (2 * d), 2, d) for q in rs]
        los = [q[..., 0, :] for q in q3]
        his = [q[..., 1, :] for q in q3]
        gt = lex_gt(los, his)
        rs = [
            jnp.stack(
                (jnp.where(gt, hi, lo), jnp.where(gt, lo, hi)), axis=-2
            ).reshape(rows, -1, width)
            for lo, hi in zip(los, his)
        ]
        d //= 2
    t = parts[0].shape[1]
    return [q.reshape(rows, t) for q in rs]


def hybrid_sort_rows(keys, vals: jax.Array, *, merge_run: int = 512):
    """The documented xla STAND-IN for the merge strategy (module
    docstring): composite-key radix run formation + bitonic-merge
    network stages with payload tiebreak."""
    from repro.kernels import radix as _radix

    words = as_words(keys)
    rows, t = words[0].shape
    if t == 1:
        return like_words(words, keys), vals
    assert t & (t - 1) == 0, t
    r0 = min(merge_run, t)
    if r0 > 1:
        wr = tuple(w.reshape(-1, r0) for w in words)
        vr = vals.reshape(-1, r0)
        wr, vr = _radix.composite_sort_rows(wr, vr)
        words = tuple(w.reshape(rows, t) for w in as_words(wr))
        vals = vr.reshape(rows, t)
    parts = list(words) + [vals]
    run = r0
    while run < t:
        parts = _bitonic_merge_stage(parts, run)
        run *= 2
    return like_words(tuple(parts[:-1]), keys), parts[-1]


def hybrid_sort_sample_rows(keys, vals: jax.Array, *, num_samples: int,
                            merge_run: int = 512):
    """Stand-in for the fused sort+sample entry: hybrid merge sort, then
    the s equidistant samples by reshape + slice (as ref.py)."""
    sk, sv = hybrid_sort_rows(keys, vals, merge_run=merge_run)
    words = as_words(sk)
    m, t = words[0].shape
    assert t % num_samples == 0, (t, num_samples)
    chunk = t // num_samples
    samples = tuple(
        a.reshape(m, num_samples, chunk)[:, :, -1] for a in words + (sv,)
    )
    return sk, sv, like_words(tuple(samples[:-1]), keys), samples[-1]
