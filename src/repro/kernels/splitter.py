"""Pallas TPU kernel: splitter ranks per sorted tile (Step 6, Sample Indexing).

The paper locates the s global samples in each sorted sublist with log(s)
rounds of parallel binary search, carefully staggered to avoid *shared-
memory bank conflicts* on 2010-era GPUs.  TPU VMEM has no bank conflicts
and the VPU is 8x128 wide, so the TPU-idiomatic equivalent is a single
broadcast compare-and-reduce: for every splitter j, its rank in the tile
is  sum_i [ (k_i, v_i) < (sk_j, sv_j) ]  — one lexicographic compare of
the tile against the broadcast splitter, reduced over the lanes.  The
kernel walks the S splitters one at a time, so VMEM holds only the tile
rows and one (rows, T) predicate.  Branch-free, no serialization.

Two entry points (see DESIGN.md §3), one kernel:
  * ``splitter_partition`` — the FUSED epilogue used by the hot path:
    one read of the tiles produces both the ranks AND the per-tile
    bucket counts (Step 7's input), so the count derivation never
    touches HBM again.  It is row-blocked: one grid program
    partitions ``block_rows`` tiles.
  * ``splitter_ranks`` — ranks only (top-k threshold, distributed
    splitter ranks); folds rows wider than 4096 into several rows.

Comparison is lexicographic on ``(*key_words, value)`` to match the sort
kernel: keys are one or more canonical uint32 word arrays (msw first —
see ``core/key_codec``), each extra word adds one cmp+select level to
the comparison matrix.  Both entries accept a bare uint32 array (the
one-word fast path) or a tuple of word arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic import (
    as_words,
    compiler_params,
    for_each_row_group,
    largest_pow2_divisor,
    lex_gt,
    row_grid,
    tpu_block_rows,
)

# VMEM budget for the tile rows of one partition block (double-buffered
# inputs; the per-splitter temporaries of one 8-row group are small).
_PART_BUDGET_BYTES = 4 * 1024 * 1024
# Widest row the ranks entry hands the kernel; wider rows are folded
# into several rows of this width (counting does not need whole rows).
_MAX_RANK_WIDTH = 4096


def partition_block_rows(
    m: int, t: int, s: int, *, num_words: int = 1,
    block_rows: int | None = None,
) -> int:
    """Resolve the fused-partition kernel's tiles-per-grid-program.

    The single source of truth for the kernel's VMEM model, shared with
    the plan builder (``core/plan.py``) so plans carry the exact block
    geometry the kernel will run — idempotent: feeding a resolved value
    back returns it unchanged.

    Args:
        m: tile count; t: tile width; s: splitters per tile.
        num_words: uint32 key words per element.
        block_rows: optional upper bound (e.g. a plan-carried value).
    Returns:
        The largest power-of-two divisor of ``m`` whose double-buffered
        tile rows fit a 4 MiB VMEM budget, made TPU-legal (a multiple of
        8 rows, or all rows).
    """
    del s  # splitters are walked one at a time: no (T x S) matrix
    per_row = 2 * 4 * t * (num_words + 1)
    limit = max(_PART_BUDGET_BYTES // per_row, 1)
    if block_rows is not None:
        limit = min(limit, block_rows)
    return tpu_block_rows(m, largest_pow2_divisor(m, limit))


def _partition_kernel(*refs, num_words: int, block_rows: int):
    """refs: num_words+1 tile refs (block_rows, T), num_words+1 splitter
    refs (block_rows, S), then the ranks (.., S) and counts (.., S+1)
    outputs.  Walks the S splitters one at a time: splitter j's rank is
    a lane reduction of one (rows, T) lexicographic compare, so no
    (T x S) matrix is ever formed."""
    nw1 = num_words + 1
    tile_refs, sp_refs = refs[:nw1], refs[nw1:2 * nw1]
    ranks_ref, counts_ref = refs[-2], refs[-1]
    t = tile_refs[0].shape[1]
    s = sp_refs[0].shape[1]

    def body(rows):
        parts = tuple(r[rows, :] for r in tile_refs)
        sp_parts = tuple(r[rows, :] for r in sp_refs)
        g = parts[0].shape[0]
        lane_r = jax.lax.broadcasted_iota(jnp.int32, (g, s), 1)
        lane_c = jax.lax.broadcasted_iota(jnp.int32, (g, s + 1), 1)
        ranks = jnp.zeros((g, s), jnp.int32)
        counts = jnp.zeros((g, s + 1), jnp.int32)
        prev = jnp.zeros((g, 1), jnp.int32)
        for j in range(s):
            spj = [p[:, j:j + 1] for p in sp_parts]  # (g, 1)
            below = lex_gt(spj, parts)  # element < splitter j
            rank = jnp.sum(jnp.where(below, 1, 0), axis=1, keepdims=True)
            ranks = jnp.where(lane_r == j, rank, ranks)
            # Bucket j of a sorted tile is [rank_{j-1}, rank_j): its size
            # is the rank difference, so Step 7 never re-reads the tiles.
            counts = jnp.where(lane_c == j, rank - prev, counts)
            prev = rank
        ranks_ref[rows, :] = ranks
        counts_ref[rows, :] = jnp.where(lane_c == s, t - prev, counts)

    for_each_row_group(block_rows, body)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def splitter_partition(
    keys,
    vals: jax.Array,
    sp_keys,
    sp_vals: jax.Array,
    *,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Fused Step 6+7 epilogue: splitter ranks AND bucket counts per tile.

    Args:
        keys: (m, T) uint32 canonical key words (bare array or tuple,
            msw first); vals: (m, T) int32 payloads.
        sp_keys/sp_vals: (m, S) per-tile splitters in the same key
            structure as ``keys``.
        block_rows: tiles partitioned per grid program (None = auto; see
            :func:`partition_block_rows`).
    Returns:
        ranks  (m, S)   int32 — #elements of tile i strictly less
                        (lexicographically) than splitter (i, j), and
        counts (m, S+1) int32 — size of bucket j in tile i (sums to T),
        from a single HBM read of the tiles.  Ranks are monotone in j
        when splitters are sorted; the tile need not be sorted (counting,
        not searching) — sortedness only matters for relocation.
    """
    words = as_words(keys)
    sp_words = as_words(sp_keys)
    nw = len(words)
    assert len(sp_words) == nw
    m, t = words[0].shape
    s = sp_words[0].shape[1]
    assert all(w.shape == (m, t) and w.dtype == jnp.uint32 for w in words)
    assert all(w.shape == (m, s) and w.dtype == jnp.uint32 for w in sp_words)
    assert vals.dtype == jnp.int32 and sp_vals.dtype == jnp.int32
    block_rows = partition_block_rows(
        m, t, s, num_words=nw, block_rows=block_rows
    )
    tile_spec = pl.BlockSpec((block_rows, t), lambda i: (i, 0))
    sp_spec = pl.BlockSpec((block_rows, s), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(
            _partition_kernel, num_words=nw, block_rows=block_rows
        ),
        grid=row_grid(m, block_rows),
        in_specs=[tile_spec] * (nw + 1) + [sp_spec] * (nw + 1),
        out_specs=[
            pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, s + 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, s), jnp.int32),
            jax.ShapeDtypeStruct((m, s + 1), jnp.int32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*words, vals, *sp_words, sp_vals)


@functools.partial(jax.jit, static_argnames=("interpret",))
def splitter_ranks(
    keys,
    vals: jax.Array,
    sp_keys,
    sp_vals: jax.Array,
    *,
    interpret: bool = True,
):
    """Rank of each splitter in each (sorted or unsorted) row.

    Args/Returns: as :func:`splitter_partition`, ranks only.  Rows wider
    than 4096 (a whole shard in the distributed sort) are folded into
    4096-wide rows, ranked, and the partial ranks summed back, so the
    kernel's block always fits VMEM.
    """
    words = as_words(keys)
    sp_words = as_words(sp_keys)
    m, t = words[0].shape
    w = largest_pow2_divisor(t, _MAX_RANK_WIDTH)
    f = t // w
    if f > 1:
        words = tuple(x.reshape(m * f, w) for x in words)
        vals = vals.reshape(m * f, w)
        sp_words = tuple(jnp.repeat(x, f, axis=0) for x in sp_words)
        sp_vals = jnp.repeat(sp_vals, f, axis=0)
    ranks, _ = splitter_partition(
        words, vals, sp_words, sp_vals, interpret=interpret
    )
    return ranks.reshape(m, f, -1).sum(axis=1, dtype=jnp.int32)
