"""GPU BUCKET SORT (Dehne & Zaboli 2010, Algorithm 1) — TPU-native, static shapes.

Single-device deterministic sample sort.  The paper's nine steps map to
(full file:symbol table in docs/paper_map.md):

  step 1  split into tiles            -> reshape (rows, L) -> (rows*m, T)
  step 2  local sort per SM           -> row-blocked Pallas bitonic sort
                                         (block_rows tiles per grid program)
  step 3  s equidistant local samples -> strided slice of step 2's output
  step 4  sort all samples            -> recursive call on the sample array
  step 5  s equidistant global samples-> strided slice of sorted samples
  step 6  sample indexing             -> fused Pallas splitter-partition
                                         kernel (ranks + bucket counts)
  step 7  column-major prefix sum     -> cumsums over (rows, m, s) counts
  step 8  data relocation             -> gather: source index per bucket
                                         slot, then one `take`
  step 9  sublist sort                -> recursion on bucket rows, then a
                                         gather-based compaction back to
                                         dense rows, by whole lane blocks

NAMES IN A TRACE: every executor node opens ``jax.named_scope`` of its
level (``sort.level0`` for the root, d + 1 for a sample or bucket
child), and inside it one scope per step, so a compiled op's
``op_name`` metadata reads ``jit(...)/sort.level<d>/.../sort.<step>/...``:

  sort.local_sort   steps 1-3 (and a direct node's single-tile sort)
  sort.splitters    step 5
  sort.partition    steps 6-7 (splitter partition, cumsums, fills)
  sort.relocate     step 8
  sort.compact      step 9's compaction
  sort.pad          column padding of a node
  (steps 4 and 9's recursion are the child node's own level scope)

The Pallas kernels are named ``bitonic_tile_sort`` and
``splitter_partition``.  On the host, each public entry opens the span
``sort.<entry>`` and inside it ``sort.plan``, ``sort.encode``, one
``sort.launch`` per attempt and ``sort.decode`` (``core/telemetry.py``).

PLANNER / EXECUTOR SPLIT (DESIGN.md §7): deterministic regular
sampling makes the whole multi-level schedule — recursion levels,
per-level rows x tile geometry, s_round, capacities, pad budgets,
kernel block sizes — a pure function of (shape, dtype, config).
``core/plan.build_plan`` computes it ONCE as a frozen ``SortPlan``
tree; the ``_run_node`` executor below merely walks it, and the jit'd
canonical entry takes the plan as its static argument, so equal plans
(the memoized builder object, or a plan reloaded from the
``core/autotune`` persistent cache) share one compiled executable:
same-signature calls trace exactly once and a plan-cache hit retraces
zero times (``trace_count`` exposes the counter; tests assert it).
``SortConfig.plan`` selects how plans are obtained ("default" /
"autotune" / a plan-file path); ``sort_planned`` executes an explicit
plan.

TPU adaptation (see DESIGN.md §2): buckets live in a DENSE (rows*s, B)
array with static capacity B = L/s_round + L/s — the deterministic
regular-sampling bound makes this capacity *guaranteed*, which is what
lets the whole sort be expressed with static shapes (a hard requirement
under XLA).  Randomized sample sort admits no such static capacity.

The guarantee holds PER ROW, so the same machinery sorts many
independent arrays in one launch (DESIGN.md §5): the batched entry
points put B independent sorts on the rows of one (B, L) array and run
the whole batch through a single `_sort_rows` recursion — one kernel
launch per pipeline step for the entire batch, no vmap over the 1-D
entry point, no per-row retracing.

DTYPE GENERICITY (DESIGN.md §6): the engine is dtype-agnostic — it
sorts tuples of canonical uint32 KEY WORDS (most significant first)
lexicographically, with the int32 payload as the final tiebreak.  A
``core/key_codec`` codec maps each user dtype to that domain: one word
for <= 32-bit dtypes (int32/uint32/float32, widened bool/8/16-bit),
hi/lo pairs for int64/uint64/float64, and an order-reversing complement
for ``SortConfig.descending``.  Every public entry point below supports
every codec dtype; 64-bit dtypes need x64 mode enabled.

Relocation/compaction move the data by gather on the default path
(DESIGN.md §4): both passes compute, for every destination slot, the
source index it must read (per-chunk bases expanded over the slots by
``_chunk_values``) and gather with `take`.  Compaction's sources are
whole sorted buckets, so it gathers one index per block of
``compact_block`` lanes of a rotated bucket row instead
(``_compact_blocked``).  XLA serializes large 1-D scatters; gathers it
vectorizes.  ``cfg.relocation="scatter"`` keeps the legacy
destination-scatter formulation as a reference path.

Correctness invariants (tested, incl. hypothesis properties):
  * elements are (key, payload) pairs, payload = original index within
    the row => all pairs are unique PER ROW (rows never compare against
    each other) => the capacity bound holds for ANY input (duplicates
    included) and the sort is STABLE;
  * pad elements introduced anywhere in the recursion draw payloads
    from one monotone per-row range (threaded ``pad_base``): pad
    payloads are unique within their row, exceed every real payload in
    the row, sort after every real element, and nothing is ever
    silently dropped (asserted in tests).  ``pad_base`` advances by
    per-row amounts, so the int32 payload budget is independent of the
    batch size.

Usage (see docs/api.md for the full reference)::

    from repro.core import bucket_sort
    from repro.core.sort_config import SortConfig

    y = bucket_sort.sort(x)                    # 1-D, ascending, stable
    perm = bucket_sort.argsort(x)              # == np.argsort(x, kind="stable")
    sk, sv = bucket_sort.sort_kv(x, payload)   # payload rides along
    y = bucket_sort.sort(x, SortConfig(descending=True))   # stable desc

    # Batched: B independent sorts in ONE launch (B, L) -> (B, L).
    ys = bucket_sort.sort_batched(xs)
    perms = bucket_sort.argsort_batched(xs)
    sk, sv = bucket_sort.sort_kv_batched(xs, payloads)

    # Segmented (ragged): sort within [off[i], off[i+1]) independently.
    # segment_offsets must be host-known ints (static shapes under XLA).
    y = bucket_sort.segment_sort(x, [0, 3, 3, 10, len(x)])
    perm = bucket_sort.segment_argsort(x, offsets)   # global indices

    # Bound introspection (paper's capacity guarantee):
    y, perm, stats = bucket_sort.sort_with_stats(x)          # 1-D
    ys, perms, stats = bucket_sort.sort_batched_with_stats(xs)
    # stats: one dict per bucket round; [] when the input fits
    # cfg.direct_max (single-tile path, no bucket round).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guard, telemetry
from repro.core import plan as plan_mod
from repro.core.key_codec import codec_for
from repro.core.plan import LevelPlan, SortPlan, build_plan
from repro.core.sort_config import DEFAULT_CONFIG, SortConfig, round_up
from repro.kernels import ops

_MAXU = jnp.uint32(0xFFFFFFFF)
_INT_MAX = 2**31 - 1


def trace_count() -> int:
    """Number of times the canonical packed entry has been TRACED in
    this process (the ``sort.traces`` counter).  ``tests/test_plan.py``
    asserts the compile-count discipline with it: same (shape, dtype,
    cfg) => one trace; a plan-cache hit => zero new traces."""
    return telemetry.counts().get("sort.traces", 0)


def _entry_span(fn):
    """Run a public entry inside the host span ``sort.<its name>``."""
    name = f"sort.{fn.__name__}"

    @functools.wraps(fn)
    def entry(*args, **kwargs):
        with telemetry.span(name):
            return fn(*args, **kwargs)

    return entry


def _pad_cols(kw, vals, new_len, pad_base):
    """Pad the last axis to new_len with (all-ones words, pad_base + j).

    Args:
        kw: tuple of (r, L) uint32 key-word arrays (msw first).
        vals: (r, L) int32 payloads.
    Returns:
        (padded kw, padded vals, advanced pad_base).

    Pad payloads are unique PER ROW (rows never compare against each
    other) and >= pad_base > every real payload in the row, so pads
    sort after all real elements and the pad budget is independent of
    the row count.
    """
    r, length = kw[0].shape
    extra = new_len - length
    if extra == 0:
        return kw, vals, pad_base
    with jax.named_scope("sort.pad"):
        pk = jnp.full((r, extra), _MAXU, jnp.uint32)
        pv = jnp.int32(pad_base) + jax.lax.broadcasted_iota(
            jnp.int32, (r, extra), 1
        )
        kw = tuple(jnp.concatenate([w, pk], axis=1) for w in kw)
        vals = jnp.concatenate([vals, pv], axis=1)
    return kw, vals, pad_base + extra


def _direct_sort(kw, vals, node: LevelPlan, impl, interpret, pad_base):
    """Single-tile bitonic sort of each row (rows, L), L <= direct_max;
    all geometry (pow2-padded width, kernel block size) is
    plan-carried."""
    length = kw[0].shape[1]
    kw, vals, pad_base = _pad_cols(kw, vals, node.lp, pad_base)
    with jax.named_scope("sort.local_sort"):
        sk, sv = ops.sort_tiles(
            kw, vals, impl=impl, interpret=interpret,
            block_rows=node.block_rows,
        )
        return tuple(w[:, :length] for w in sk), sv[:, :length], pad_base


def _chunk_values(offsets, values, length: int):
    """Expand per-chunk values to per-position values, row by row.

    Args:
        offsets: (Q, C) non-decreasing exclusive chunk starts
            (offsets[:, 0] == 0); starts at or past ``length`` are
            ignored.
        values: (Q, C) int32 value of each chunk.
        length: positions per row.
    Returns:
        (Q, length) int32 with out[q, p] = values[q, j] for the LAST
        chunk j starting at or before p, which skips empty chunks (ties
        in ``offsets``).  The differences between consecutive chunk
        values are added at the chunk starts and summed along the row:
        Q*C small updates plus one cumsum over the output.  A binary
        search per position instead gathers from the (Q, C) table at
        every probe, which costs the TPU far more than the data it
        selects.
    """
    q, c = offsets.shape
    step = jnp.concatenate(
        [values[:, :1], values[:, 1:] - values[:, :-1]], axis=1
    )
    row = jax.lax.broadcasted_iota(jnp.int32, (q, c), 0)
    marks = jnp.zeros((q, length), jnp.int32).at[row, offsets].add(
        step, mode="drop"
    )
    return jnp.cumsum(marks, axis=1, dtype=jnp.int32)


def _relocate_gather(tkw, tv, starts, tile_off, totals, r, m, s_round, t, cap,
                     pad_base):
    """Step 8, scatter-free (DESIGN.md §4): for every slot of the dense
    (r*s_round, cap) bucket array compute the SOURCE element it receives,
    then gather (one `take` per key word + one for the payload).

    Bucket row q = r'*s_round + j receives, tile by tile, the elements
    of tile i = 0..m-1 of data row r' that fall in key range j; tile i's
    chunk lands at offset tile_off[r', i, j] and is read from the sorted
    tile starting at starts[r'*m + i, j].  Slot p of bucket row q
    therefore reads from the tile whose chunk covers p, at
    chunk-relative position p - chunk offset (:func:`_chunk_values`
    expands the per-chunk source bases over the slots).  Slots past the
    true fill (p >= totals) become fresh pads, unique within their
    bucket row.
    """
    # Per-bucket-row views: (r*s_round, m) chunk offsets / tile starts.
    offs = tile_off.transpose(0, 2, 1).reshape(r * s_round, m)
    st = starts.reshape(r, m, s_round).transpose(0, 2, 1).reshape(r * s_round, m)
    # Slot p of chunk i reads flat element (row*m + i)*t + st - offs + p.
    tile = jax.lax.broadcasted_iota(jnp.int32, (r * s_round, m), 1)
    row_base = (
        jax.lax.broadcasted_iota(jnp.int32, (r * s_round, m), 0) // s_round
    ) * m
    base = (row_base + tile) * t + st - offs
    p = jax.lax.broadcasted_iota(jnp.int32, (r * s_round, cap), 1)
    src = p + _chunk_values(offs, base, cap)
    valid = p < totals.reshape(r * s_round, 1)
    src = jnp.where(valid, src, 0)
    srcf = src.reshape(-1)
    bkw = tuple(
        jnp.where(
            valid, jnp.take(w.reshape(-1), srcf).reshape(src.shape), _MAXU
        )
        for w in tkw
    )
    gv = jnp.take(tv.reshape(-1), srcf).reshape(src.shape)
    pad_v = jnp.int32(pad_base) + p
    bv = jnp.where(valid, gv, pad_v)
    return bkw, bv


def _relocate_scatter(tkw, tv, ranks, starts, tile_off, r, m, s_round, t, cap,
                      pad_base):
    """Step 8, legacy destination-scatter reference path: compute each
    ELEMENT's destination slot and scatter.  XLA serializes the
    full-size 1-D scatters; kept only for cfg.relocation="scatter"."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (r * m, t), 1)
    ind = jnp.zeros((r * m, t + 1), jnp.int32)
    ind = ind.at[
        jax.lax.broadcasted_iota(jnp.int32, ranks.shape, 0), ranks
    ].add(1)
    bucket_id = jnp.cumsum(ind, axis=1, dtype=jnp.int32)[:, :t]  # (r*m, T)
    p_rel = pos - jnp.take_along_axis(starts, bucket_id, axis=1)
    within = (
        jnp.take_along_axis(tile_off.reshape(r * m, s_round), bucket_id, axis=1)
        + p_rel
    )
    row_id = jax.lax.broadcasted_iota(jnp.int32, (r * m, t), 0) // m
    dest = (row_id * s_round + bucket_id) * cap + within
    # The capacity bound guarantees within < cap; tests assert no drops.
    dest = jnp.where(within < cap, dest, r * s_round * cap)
    destf = dest.reshape(-1)

    # Unwritten slots hold the same per-row pads as the gather path.
    bkw = tuple(
        jnp.full((r * s_round * cap,), _MAXU, jnp.uint32)
        .at[destf].set(w.reshape(-1), mode="drop")
        .reshape(r * s_round, cap)
        for w in tkw
    )
    bv = (
        jnp.int32(pad_base)
        + jax.lax.broadcasted_iota(jnp.int32, (r * s_round, cap), 1)
    ).reshape(-1)
    bv = bv.at[destf].set(tv.reshape(-1), mode="drop")
    return bkw, bv.reshape(r * s_round, cap)


def _rotate_rows(x, shift, w: int):
    """Rotate each row of ``x`` right by ``shift[q]`` (0 <= shift < w,
    w a power of two), with the row held as (R, blocks, w).

    A barrel shifter of log2(w) static lane rolls inside each block,
    each kept where its bit of the shift is set, then one select that
    takes lanes below the shift from the block before.  No gather, and
    the (R, blocks, w) view is the layout of the (R*blocks, w) rows the
    blocks are gathered from."""
    for k in range(w.bit_length() - 1):
        bit = (shift >> k) & 1
        x = jnp.where(bit == 1, jnp.roll(x, 1 << k, axis=2), x)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    return jnp.where(lane >= shift, x, jnp.roll(x, 1, axis=1))


def _overlay(a, b):
    """Scan operator of the boundary repair: each element is (first
    lane, w-lane row); b's lanes from its first lane on cover a's."""
    (sa, va), (sb, vb) = a, b
    lane = jax.lax.broadcasted_iota(jnp.int32, vb.shape, vb.ndim - 1)
    return jnp.minimum(sa, sb), jnp.where(lane >= sb, vb, va)


def _compact_blocked(ckw, cv, totals, r, s_round, cap, lp, w):
    """Step 9 compaction, scatter-free, by whole w-lane blocks
    (DESIGN.md §4): dense row r' is the concatenation of each bucket's
    first ``totals[r', j]`` elements.  Bucket fills sum to lp per row,
    so every dense slot has exactly one source — no pads.

    Bucket j of data row r' (bucket row q = r'*s_round + j) lands at
    ``off = bucket_off[r', j]``.  Rotating bucket row q right by
    ``off % w`` makes every output block that lies wholly inside the
    bucket an aligned block of the rotated row: output block b is
    rotated block ``b - off // w``.  The rest are boundary blocks, where
    a bucket j >= 1 starts at a lane > 0: at most ``r * (s_round - 1)``
    of them.  Candidate row k = r'*(s_round-1) + j-1 is the block where
    bucket j starts, built from rotated blocks: the tail of bucket j-1,
    then the first block of each bucket that starts inside it, laid
    over one another in bucket order by a scan.  A boundary block reads
    the candidate row of the last bucket that starts inside it.  The
    candidate rows sit below the rotated rows, so one row gather with
    one index per block writes the output.
    """
    bucket_off = jnp.cumsum(totals, axis=1, dtype=jnp.int32) - totals  # (r, s_round)
    nb, cb, k = lp // w, cap // w, s_round - 1
    bucket = jax.lax.broadcasted_iota(jnp.int32, (r, s_round), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (r, s_round), 0)
    first_row = (row * s_round + bucket) * cb  # bucket's block 0, rotated
    start, lane = bucket_off // w, bucket_off % w
    # Block b from ceil(off / w) on reads rotated block row first_row + b - start.
    b = jax.lax.broadcasted_iota(jnp.int32, (r, nb), 1)
    src_row = b + _chunk_values(-(-bucket_off // w), first_row - start, nb)
    # Boundary blocks: the last bucket j >= 1 that starts at a lane > 0.
    last = jnp.zeros((r, nb), jnp.int32).at[row, start].max(
        jnp.where(lane > 0, bucket, 0), mode="drop"
    )
    cand = jax.lax.broadcasted_iota(jnp.int32, (r, nb), 0) * k + last - 1
    idx = jnp.where(last > 0, r * s_round * cb + cand, src_row).reshape(-1)
    # Candidate j: bucket j-1's rotated block over block start_j (mod cb:
    # a bucket that fills its cap wraps its last lanes to block 0), then
    # bucket j's block 0 from lane_j on.  Where bucket j-1 also starts in
    # block start_j, the scan's earlier rows supply the lanes below lane_j;
    # a bucket starting at lane 0 leaves the scan as it was.
    head = first_row[:, 1:]
    tail = first_row[:, :-1] + (start[:, 1:] - start[:, :-1]) % cb
    lane_j = lane[:, 1:, None]
    opens = bucket_off[:, :-1, None] <= start[:, 1:, None] * w
    from_lane = jnp.where(lane_j == 0, w, jnp.where(opens, 0, lane_j))
    shift = lane.reshape(r * s_round, 1, 1)

    def compact(x):
        rot = _rotate_rows(x.reshape(r * s_round, cb, w), shift, w).reshape(
            r * s_round * cb, w)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (r, k, w), 2)
        pieces = jnp.where(lanes >= lane_j, rot[head], rot[tail])
        _, cand_rows = jax.lax.associative_scan(
            _overlay, (from_lane, pieces), axis=1)
        rows = jnp.concatenate([rot, cand_rows.reshape(r * k, w)])
        return jnp.take(rows, idx, axis=0).reshape(r, lp)

    return tuple(compact(x) for x in ckw), compact(cv)


def _compact_scatter(ckw, cv, totals, r, s_round, cap, lp):
    """Step 9 compaction, legacy scatter reference path."""
    bucket_off = jnp.cumsum(totals, axis=1, dtype=jnp.int32) - totals  # (r, s_round)
    p = jax.lax.broadcasted_iota(jnp.int32, (r * s_round, cap), 1)
    valid = p < totals.reshape(r * s_round, 1)
    drow = jax.lax.broadcasted_iota(jnp.int32, (r * s_round, cap), 0) // s_round
    dcol = bucket_off.reshape(r * s_round, 1) + p
    dflat = jnp.where(valid, drow * lp + dcol, r * lp).reshape(-1)
    okw = tuple(
        jnp.full((r * lp,), _MAXU, jnp.uint32)
        .at[dflat].set(w.reshape(-1), mode="drop")
        .reshape(r, lp)
        for w in ckw
    )
    ov = jnp.full((r * lp,), jnp.int32(_INT_MAX))
    ov = ov.at[dflat].set(cv.reshape(-1), mode="drop")
    return okw, ov.reshape(r, lp)


def _run_node(kw, vals, node: LevelPlan, impl: str, interpret: bool,
              pad_base: int, stats: list | None, depth: int = 0):
    """EXECUTOR: sort each row of (rows, L) canonical key words / int32
    payloads by walking one node of the plan tree.

    Every static quantity — padded lengths, tile counts, ``s_round``,
    capacities, kernel block sizes, fusion/relocation choices — is read
    off the :class:`repro.core.plan.LevelPlan`; the executor derives
    NOTHING (the planner/executor split, DESIGN.md §7).

    Args:
        kw: tuple of (rows, L) uint32 key-word arrays (msw first).
        vals: (rows, L) int32 payloads, unique per row.
        node: the plan node matching (rows, L) exactly.
        depth: the node's level in the plan tree (0 at the root), which
            names its ``sort.level<depth>`` scope.
    Returns:
        (sorted kw, sorted vals, pad_base) with dense sorted rows of the
        input shape.  Static walk: every shape is trace-time known;
        ``pad_base`` is a trace-time python int tracking the per-row pad
        payload high-water mark (batch-size independent, DESIGN.md §5).
    """
    r, length = kw[0].shape
    assert (r, length) == (node.rows, node.length), (
        f"plan/data mismatch: data {(r, length)} vs plan node "
        f"{(node.rows, node.length)}"
    )
    with jax.named_scope(f"sort.level{depth}"):
        if node.kind == "direct":
            return _direct_sort(kw, vals, node, impl, interpret, pad_base)
        return _bucket_round(kw, vals, node, impl, interpret, pad_base,
                             stats, depth)


def _bucket_round(kw, vals, node: LevelPlan, impl: str, interpret: bool,
                  pad_base: int, stats: list | None, depth: int):
    """Steps 1-9 of one bucket node (see :func:`_run_node`)."""
    r, length = kw[0].shape
    t, sper, lp, m = node.tile, node.s, node.lp, node.m
    s_round, cap = node.s_round, node.cap
    kw, vals, pad_base = _pad_cols(kw, vals, lp, pad_base)

    # Steps 1-3: row-blocked local tile sort, sample extraction fused in.
    with jax.named_scope("sort.local_sort"):
        tkw = tuple(w.reshape(r * m, t) for w in kw)
        tv = vals.reshape(r * m, t)
        tkw, tv, samp_kw, samp_v = ops.sort_tiles_sample(
            tkw, tv, num_samples=sper, impl=impl,
            interpret=interpret, block_rows=node.block_rows,
        )
        samples_kw = tuple(w.reshape(r, m * sper) for w in samp_kw)
        samples_v = samp_v.reshape(r, m * sper)

    # Step 4: sort all samples (recursive; sample array is L*s/T << L).
    sskw, ssv, pad_base = _run_node(
        samples_kw, samples_v, node.sample_plan, impl, interpret, pad_base,
        None, depth + 1,
    )

    # Step 5: s_round - 1 equidistant global splitters.
    with jax.named_scope("sort.splitters"):
        total_samples = m * sper
        sp_idx = (
            jnp.arange(1, s_round, dtype=jnp.int32) * total_samples
        ) // s_round
        spkw = tuple(w[:, sp_idx] for w in sskw)  # (r, s_round-1) each
        spv = ssv[:, sp_idx]

    # Steps 6-7: splitter ranks + per-tile bucket counts (fused epilogue),
    # then the column-major prefix sums over (rows, m, s_round).
    with jax.named_scope("sort.partition"):
        spkw_t = tuple(jnp.repeat(w, m, axis=0) for w in spkw)  # (r*m, s_round-1)
        spv_t = jnp.repeat(spv, m, axis=0)
        ranks, counts2 = ops.splitter_partition(
            tkw, tv, spkw_t, spv_t, impl=impl, interpret=interpret,
            block_rows=node.part_block_rows,
        )  # ranks (r*m, s_round-1); counts2 (r*m, s_round)
        starts = jnp.concatenate(
            [jnp.zeros((r * m, 1), jnp.int32), ranks], axis=1
        )  # (r*m, s_round): start of bucket j within tile i
        counts = counts2.reshape(r, m, s_round)
        # offset of tile i's chunk within bucket j of its row (exclusive cumsum):
        tile_off = jnp.cumsum(counts, axis=1, dtype=jnp.int32) - counts  # (r, m, s_round)
        totals = counts.sum(axis=1, dtype=jnp.int32)  # (r, s_round) true bucket fills
        if stats is not None:
            stats.append(
                dict(
                    level_len=lp,
                    rows=r,
                    s_round=s_round,
                    capacity=cap,
                    totals=totals,
                    # every bucket's elements sit at 0..fill-1 of their row
                    max_within=jnp.max(totals) - 1,
                )
            )

    # Step 8: relocation into the dense (r*s_round, cap) bucket array.
    with jax.named_scope("sort.relocate"):
        if node.relocation == "gather":
            bkw, bv = _relocate_gather(
                tkw, tv, starts, tile_off, totals, r, m, s_round, t, cap,
                pad_base,
            )
        else:
            bkw, bv = _relocate_scatter(
                tkw, tv, ranks, starts, tile_off, r, m, s_round, t, cap,
                pad_base,
            )
    pad_base += cap

    # Step 9: sort every bucket row (recursion), then compact to dense rows.
    ckw, cv, pad_base = _run_node(
        bkw, bv, node.bucket_plan, impl, interpret, pad_base, stats,
        depth + 1,
    )

    # Compaction: first totals[q, j] entries of bucket row (q, j) are exactly
    # the elements this level relocated there (fresh pads sort after them).
    with jax.named_scope("sort.compact"):
        if node.relocation == "gather":
            okw, ov = _compact_blocked(ckw, cv, totals, r, s_round, cap, lp,
                                       node.compact_block)
        else:
            okw, ov = _compact_scatter(ckw, cv, totals, r, s_round, cap, lp)
        return tuple(w[:, :length] for w in okw), ov[:, :length], pad_base


def _sort_rows(kw, vals, cfg: SortConfig, pad_base: int, stats: list | None):
    """Plan-building shim over the executor for callers holding canonical
    word tuples mid-trace (``distributed_sort`` local sorts): builds the
    words-plan for the (rows, L) shape through the same builder and
    walks it."""
    r, length = kw[0].shape
    p = plan_mod.build_words_plan(length, len(kw), cfg, rows=r)
    return _run_node(kw, vals, p.root, p.impl, p.interpret, pad_base, stats)


@functools.partial(
    jax.jit, static_argnames=("plan", "pad_base0", "with_stats")
)
def _sort_canonical_packed(keys_words, vals, plan: SortPlan, pad_base0: int,
                           with_stats: bool = False):
    """Row-native canonical entry: (B, L) key words + int32 payloads.

    ``plan`` is a STATIC argument: equal plans (e.g. the same memoized
    object, or a plan reloaded from the persistent cache) hash to the
    same jit cache entry, so repeated same-signature calls trace and
    compile exactly once (asserted in tests/test_plan.py).

    Args:
        keys_words: tuple of (B, L) uint32 key-word arrays (msw first),
            with B == plan.rows_padded and L == plan.length.
        vals: (B, L) int32 payloads.
        plan: the static schedule to walk (see ``core/plan.py``).
        pad_base0: must exceed every payload already present in ``vals``
            (per row) so recursion-introduced pads sort after real
            elements.
    Returns:
        (sorted words, sorted vals[, stats]).
    """
    telemetry.count("sort.traces")  # python side effect: once per TRACE
    stats: list | None = [] if with_stats else None
    kw = tuple(keys_words)
    skw, sv, _ = _run_node(
        kw, vals, plan.root, plan.impl, plan.interpret, pad_base0, stats
    )
    if with_stats:
        return skw, sv, stats
    return skw, sv


def resolve_plan(length: int, dtype, cfg: SortConfig, *, rows: int = 1,
                 pad_rows: bool = False) -> SortPlan:
    """Obtain the plan for a sort signature per ``cfg.plan``:

      * ``"default"``  — :func:`repro.core.plan.build_plan` (memoized);
      * ``"autotune"`` — measured-best plan via ``core/autotune``
        (persistent on-disk cache; tunes on the first miss);
      * a path — a plan file saved by ``autotune.save_plan``; its
        signature must match (ValueError otherwise).
    """
    if cfg.plan == "default":
        return build_plan(length, dtype, cfg, rows=rows, pad_rows=pad_rows)
    from repro.core import autotune  # deferred: autotune imports us

    if cfg.plan == "autotune":
        return autotune.plan_for(
            length, dtype, cfg, rows=rows, pad_rows=pad_rows
        )
    return autotune.load_plan(
        cfg.plan, length=length, dtype=dtype, cfg=cfg, rows=rows,
        pad_rows=pad_rows,
    )


@jax.jit
def _reference_sort_packed(kw, vals):
    """Last chain link of the degradation ladder (DESIGN.md §11): one
    ``jax.lax.sort`` over (key words..., payload) — no pallas, no plan
    machinery, the same formulation as ``baselines.xla_sort``.  Correct
    for any canonical input; slower (no tiling, no fused steps)."""
    out = jax.lax.sort(tuple(kw) + (vals,), dimension=1,
                       num_keys=len(kw) + 1)
    return tuple(out[:-1]), out[-1]


def _fallback_plan(plan: SortPlan) -> SortPlan | None:
    """Stage-2 degradation target: a default-config xla stand-in plan
    for the same (rows, length) canonical-words signature.  ``None``
    when it would equal the failing plan (nothing left to vary before
    the reference path)."""
    alt = plan_mod.build_words_plan(
        plan.length, plan.num_words,
        SortConfig(impl="xla", interpret=False),
        rows=plan.rows_padded,
    )
    return None if alt == plan else alt


def _execute_packed(kw, vals, plan: SortPlan, pad_base0: int, *,
                    n_keys: int,
                    check: str = "off", degrade: bool = True,
                    with_stats: bool = False):
    """Guarded, degrading funnel every packed entry point runs through.

    Executes ``plan`` via the jit'd canonical entry, then applies the
    ``check`` invariants (``core/guard.py``): ``'bounds'`` verifies the
    paper's capacity bound on the measured bucket fills of each round,
    ``'full'`` adds permutation checksums + sortedness on the output.

    With ``degrade=True`` a recoverable failure (``guard.RECOVERABLE``:
    an injected fault from ``core/faults.py`` or a check violation)
    walks the degradation chain (DESIGN.md §11):

      1. the resolved plan as given;
      2. a default-config ``impl='xla'`` stand-in plan (fresh trace —
         failed traces are never cached, so a transient launch fault
         does not poison the chain);
      3. the ``jax.lax.sort`` reference (no plan machinery at all).

    Each step re-runs the checks; events land in
    ``guard.degradation_log()``.  The call is counted once: ``n_keys``
    real keys (``sort.keys``; padded rows and columns left out) and the
    plan's ``sort.moved_elements``.  Each attempt opens its own
    ``sort.launch`` span.  Under an outer ``jax.jit`` this body runs
    once per trace, so the counters and spans then count traces, not
    calls.  Any other error — a kernel Mosaic
    refuses, a lowering error — propagates: the chain never hides a run
    that left its device path.  ``degrade=False`` (the explicit-plan
    API) propagates the structured error too.  Returns
    (kw, vals[, stats]); a run degraded to the reference path reports
    ``stats == []`` (the reference has no bucket rounds).
    """
    guard.validate_check(check)
    check_pad_budget(plan, pad_base0)
    want_stats = with_stats or check != "off"
    telemetry.count("sort.keys", n_keys)
    telemetry.count("sort.moved_elements", plan.moved_elements)

    def run(p: SortPlan):
        with telemetry.span("sort.launch"):
            out = _sort_canonical_packed(kw, vals, p, pad_base0, want_stats)
        skw, sv, stats = out if want_stats else (out[0], out[1], [])
        if check != "off":
            guard.check_bounds(p, stats)
        if check == "full":
            guard.check_full(p, kw, vals, skw, sv)
        return skw, sv, stats

    try:
        skw, sv, stats = run(plan)
    except guard.RECOVERABLE as e1:
        if not degrade:
            raise
        alt = _fallback_plan(plan)
        skw = None
        if alt is not None:
            guard.record_degradation(
                guard.plan_site(plan), "fallback", f"impl={plan.impl} plan",
                "default xla stand-in plan", e1)
            try:
                skw, sv, stats = run(alt)
            except guard.RECOVERABLE as e2:
                e1 = e2
        if skw is None:
            guard.record_degradation(
                guard.plan_site(plan), "fallback",
                "plan execution", "jax.lax.sort reference", e1)
            with telemetry.span("sort.launch"):
                skw, sv = _reference_sort_packed(kw, vals)
            stats = []
            if check == "full":
                guard.check_full(plan, kw, vals, skw, sv)
    if with_stats:
        return skw, sv, stats
    return skw, sv


def check_pad_budget(plan: SortPlan, pad_base0: int) -> None:
    """Raise before tracing when ``plan`` does not fit the int32 budget.

    Payloads and flat indices are int32.  Real elements carry indices
    below ``pad_base0``; every pad the recursion introduces draws the
    next payload above it (``LevelPlan.pad_span``); and relocation and
    compaction index the flattened bucket arrays, which grow about 2x
    per bucket round (``LevelPlan.max_elements``).  Both must stay
    below 2**31 - 1.

    Raises:
        ValueError: naming the int32 payload budget and the plan.
    """
    top = pad_base0 + plan.root.pad_span()
    biggest = plan.root.max_elements()
    if max(top, biggest) >= _INT_MAX:
        raise ValueError(
            f"sort of rows={plan.rows} x length={plan.length} exceeds the "
            f"int32 payload budget: pad payloads reach {top} and the "
            f"largest bucket array holds {biggest} elements, both must "
            f"stay below 2**31 - 1; split the input or sort it on a mesh"
        )


def _resolve_1d(keys, cfg: SortConfig):
    """The codec and plan of a 1-D entry, inside the ``sort.plan`` span."""
    with telemetry.span("sort.plan"):
        codec = codec_for(keys.dtype, cfg.descending)
        return codec, resolve_plan(keys.shape[0], keys.dtype, cfg)


def _sort_canonical(keys, codec, plan: SortPlan, with_stats: bool = False,
                    check: str = "off"):
    """1-D canonical entry: ``keys`` encoded as the single row of the
    batched path, payload = original index.  Returns the (1, n) packed
    (words, perm[, stats]); the caller decodes."""
    n = keys.shape[0]
    with telemetry.span("sort.encode"):
        kw = tuple(w[None, :] for w in codec.encode(keys))
        vals = jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32)[None, :], (1, n))
    return _execute_packed(kw, vals, plan, n, n_keys=n, check=check,
                           with_stats=with_stats)


def _pad_rows(kw, vals, plan: SortPlan):
    """Batch-aware block_rows auto-pick (DESIGN.md §5): pad the row
    count to the plan's ``rows_padded`` with all-pad rows so
    ``auto_block_rows`` always finds a power-of-two divisor >= row_pad
    and the row-blocked kernels get dense sublane blocks (the planner
    applies the rule only on the pallas path).  Returns (kw, vals);
    callers slice [:plan.rows] out.
    """
    b, length = kw[0].shape
    extra = plan.rows_padded - b
    if extra <= 0:
        return kw, vals
    pk = jnp.full((extra, length), _MAXU, jnp.uint32)
    pv = jnp.broadcast_to(
        jnp.arange(length, dtype=jnp.int32)[None, :], (extra, length)
    )
    return (
        tuple(jnp.concatenate([w, pk], axis=0) for w in kw),
        jnp.concatenate([vals, pv], axis=0),
    )


# ----------------------------------------------------------------------
# Public 1-D API
# ----------------------------------------------------------------------


@_entry_span
def sort(keys: jax.Array, cfg: SortConfig = DEFAULT_CONFIG) -> jax.Array:
    """Deterministic sample sort of a 1-D array (stable, total order).

    Args:
        keys: 1-D array of any codec dtype — int8/16/32/64, uint8/16/32/64,
            float16/bfloat16/float32/float64, bool (64-bit dtypes need
            x64 mode).  Floats follow the IEEE total order (NaN last
            ascending).
        cfg: pipeline knobs; ``cfg.descending`` flips the order
            (stable, codec-level — see SortConfig).
    Returns:
        Sorted array, same shape/dtype.

    Example:
        >>> import jax.numpy as jnp
        >>> from repro.core import bucket_sort
        >>> bucket_sort.sort(jnp.asarray([3, 1, 2]))
        Array([1, 2, 3], dtype=int32)
    """
    if keys.shape[0] <= 1:
        return keys
    codec, plan = _resolve_1d(keys, cfg)
    su, _ = _sort_canonical(keys, codec, plan, check=cfg.check)
    with telemetry.span("sort.decode"):
        return codec.decode(tuple(w[0] for w in su))


@_entry_span
def argsort(keys: jax.Array, cfg: SortConfig = DEFAULT_CONFIG) -> jax.Array:
    """Stable argsort via deterministic sample sort.

    Args:
        keys: 1-D array of any codec dtype (see :func:`sort`).
        cfg: pipeline knobs; ``cfg.descending`` gives the stable
            descending permutation (ties keep input order), matching
            ``jnp.argsort(x, descending=True, stable=True)``.
    Returns:
        int32 permutation, == ``np.argsort(keys, kind="stable")`` when
        ascending.

    Example:
        >>> import jax.numpy as jnp
        >>> from repro.core import bucket_sort
        >>> bucket_sort.argsort(jnp.asarray([30.0, 10.0, 20.0]))
        Array([1, 2, 0], dtype=int32)
    """
    if keys.shape[0] <= 1:
        return jnp.arange(keys.shape[0], dtype=jnp.int32)
    codec, plan = _resolve_1d(keys, cfg)
    _, perm = _sort_canonical(keys, codec, plan, check=cfg.check)
    with telemetry.span("sort.decode"):
        return perm[0]


@_entry_span
def sort_kv(keys: jax.Array, values: jax.Array, cfg: SortConfig = DEFAULT_CONFIG):
    """Stable (keys, values) sort by keys.

    Args:
        keys: 1-D array of any codec dtype (see :func:`sort`), length n.
        values: any array with leading dim n; permuted along axis 0.
        cfg: pipeline knobs (``descending`` supported).
    Returns:
        (sorted_keys, values[perm]).
    """
    assert keys.ndim == 1 and values.shape[0] == keys.shape[0]
    n = keys.shape[0]
    if n <= 1:
        return keys, values
    codec, plan = _resolve_1d(keys, cfg)
    su, perm = _sort_canonical(keys, codec, plan, check=cfg.check)
    with telemetry.span("sort.decode"):
        return (codec.decode(tuple(w[0] for w in su)),
                jnp.take(values, perm[0], axis=0))


@_entry_span
def sort_with_stats(keys: jax.Array, cfg: SortConfig = DEFAULT_CONFIG):
    """Sort + per-round stats (capacities, bucket fills) for bound tests.

    Args:
        keys: 1-D array of any codec dtype.
    Returns:
        (sorted, perm, stats).  ``stats`` has one dict per bucket round
        (keys: level_len, rows, s_round, capacity, totals, max_within).
        Inputs that fit ``cfg.direct_max`` take the single-tile bitonic
        path and run ZERO bucket rounds: stats is a well-defined EMPTY
        list — callers must check before indexing.
    """
    n = keys.shape[0]
    if n <= 1:
        return keys, jnp.arange(n, dtype=jnp.int32), []
    codec, plan = _resolve_1d(keys, cfg)
    su, perm, stats = _sort_canonical(
        keys, codec, plan, with_stats=True, check=cfg.check
    )
    with telemetry.span("sort.decode"):
        return codec.decode(tuple(w[0] for w in su)), perm[0], stats


@_entry_span
def sort_planned(keys: jax.Array, plan: SortPlan,
                 check: str = "off") -> jax.Array:
    """Sort with an EXPLICIT :class:`~repro.core.plan.SortPlan`.

    The autotuner's measurement entry and the zero-retrace serving
    path: the plan is the jit static argument, so every call carrying
    an equal plan (the memoized builder object, or one reloaded from
    the persistent cache) reuses one compiled executable.

    Unlike the config-driven entries, an explicit plan is executed
    WITHOUT degradation (``degrade=False``): the caller asked for this
    exact schedule, so a failure — including a ``check`` invariant
    violation (:class:`repro.core.guard.SortRuntimeError`) — raises
    rather than silently substituting a different plan.

    Args:
        keys: 1-D (plan.rows == 1) or 2-D (B, L) array whose
            shape/dtype match the plan signature.
        plan: a plan from :func:`repro.core.plan.build_plan`,
            ``autotune.plan_for``, or ``autotune.load_plan``.
        check: runtime invariant mode, ``'off' | 'bounds' | 'full'``
            (see DESIGN.md §11).
    Returns:
        Sorted array of keys' shape/dtype (each row independently for
        2-D), descending iff the plan was built from a descending cfg.
    Raises:
        ValueError: when keys' shape or dtype do not match the plan.
        repro.core.guard.SortRuntimeError: when ``check`` detects an
            invariant violation for this plan.
    """
    shape = (
        (1, keys.shape[0]) if keys.ndim == 1
        else (keys.shape[0], keys.shape[1])
    )
    if shape != (plan.rows, plan.length) or (
        jnp.dtype(keys.dtype).name != plan.dtype_name
    ):
        raise ValueError(
            f"keys {keys.shape}/{jnp.dtype(keys.dtype).name} do not match "
            f"plan signature rows={plan.rows} length={plan.length} "
            f"dtype={plan.dtype_name}"
        )
    if plan.length <= 1:
        return keys
    codec = codec_for(keys.dtype, plan.descending)
    with telemetry.span("sort.encode"):
        if keys.ndim == 1:
            kw = tuple(w[None, :] for w in codec.encode(keys))
            vals = jnp.broadcast_to(
                jnp.arange(plan.length, dtype=jnp.int32)[None, :],
                (1, plan.length),
            )
        else:
            vals = jnp.broadcast_to(
                jnp.arange(plan.length, dtype=jnp.int32)[None, :],
                keys.shape,
            )
            kw, vals = _pad_rows(codec.encode(keys), vals, plan)
    sk, _ = _execute_packed(kw, vals, plan, plan.length,
                            n_keys=plan.rows * plan.length,
                            check=check, degrade=False)
    with telemetry.span("sort.decode"):
        if keys.ndim == 1:
            return codec.decode(tuple(w[0] for w in sk))
        return codec.decode(tuple(w[:plan.rows] for w in sk))


# ----------------------------------------------------------------------
# Batched API: B independent sorts on the rows of (B, L), one launch
# ----------------------------------------------------------------------


def _batched_entry(keys, cfg: SortConfig):
    """Shared batched preamble: plan resolution, canonical key words,
    per-row index payloads, row_pad alignment.  Returns
    (codec, plan, kw, vals, b) — slice results [:b]."""
    b, length = keys.shape
    with telemetry.span("sort.plan"):
        codec = codec_for(keys.dtype, cfg.descending)
        plan = resolve_plan(length, keys.dtype, cfg, rows=b, pad_rows=True)
    with telemetry.span("sort.encode"):
        kw, vals = _pad_rows(
            codec.encode(keys),
            jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None, :],
                             (b, length)),
            plan,
        )
    return codec, plan, kw, vals, b


@_entry_span
def sort_batched(keys: jax.Array, cfg: SortConfig = DEFAULT_CONFIG) -> jax.Array:
    """Sort each row of a (B, L) array independently (stable).

    Equivalent to B independent 1-D ``sort`` calls, but the whole batch
    enters the row-native pipeline with rows=B: one kernel launch per
    pipeline step for the entire batch (DESIGN.md §5).

    Args:
        keys: (B, L) array of any codec dtype (see :func:`sort`).
        cfg: pipeline knobs (``descending`` supported).
    Returns:
        (B, L) array, every row sorted.
    """
    assert keys.ndim == 2, keys.shape
    b, length = keys.shape
    if b == 0 or length <= 1:
        return keys
    codec, plan, kw, vals, b = _batched_entry(keys, cfg)
    sk, _ = _execute_packed(kw, vals, plan, length, n_keys=b * length,
                            check=cfg.check)
    with telemetry.span("sort.decode"):
        return codec.decode(tuple(w[:b] for w in sk))


@_entry_span
def argsort_batched(keys: jax.Array, cfg: SortConfig = DEFAULT_CONFIG):
    """Per-row stable argsort of (B, L): row i of the result is
    ``np.argsort(keys[i], kind="stable")`` (descending via cfg).

    Args:
        keys: (B, L) array of any codec dtype.
    Returns:
        (B, L) int32 permutations.
    """
    assert keys.ndim == 2, keys.shape
    b, length = keys.shape
    if b == 0 or length <= 1:
        return jnp.broadcast_to(
            jnp.arange(length, dtype=jnp.int32)[None, :], (b, length)
        )
    _, plan, kw, vals, b = _batched_entry(keys, cfg)
    _, perm = _execute_packed(kw, vals, plan, length, n_keys=b * length,
                              check=cfg.check)
    with telemetry.span("sort.decode"):
        return perm[:b]


@_entry_span
def sort_kv_batched(keys: jax.Array, values: jax.Array,
                    cfg: SortConfig = DEFAULT_CONFIG):
    """Per-row stable (keys, values) sort of (B, L) keys by keys.

    Args:
        keys: (B, L) array of any codec dtype.
        values: (B, L, ...) — any trailing shape; permuted along axis 1
            with each row's permutation.
    Returns:
        (sorted_keys (B, L), permuted values).
    """
    assert keys.ndim == 2 and values.shape[:2] == keys.shape, (
        keys.shape, values.shape
    )
    b, length = keys.shape
    if b == 0 or length <= 1:
        return keys, values
    codec, plan, kw, vals, b = _batched_entry(keys, cfg)
    sk, perm = _execute_packed(kw, vals, plan, length, n_keys=b * length,
                               check=cfg.check)
    with telemetry.span("sort.decode"):
        sk, perm = tuple(w[:b] for w in sk), perm[:b]
        idx = perm.reshape(perm.shape + (1,) * (values.ndim - 2))
        sv = jnp.take_along_axis(values, idx, axis=1)
        return codec.decode(sk), sv


@_entry_span
def sort_batched_with_stats(keys: jax.Array, cfg: SortConfig = DEFAULT_CONFIG):
    """Batched sort + per-round stats over the WHOLE batch.

    Each stats entry's ``totals`` covers every row of that recursion
    level (top level: the B batch rows, plus all-pad alignment rows on
    the pallas path — pads obey the same bound).  Like
    ``sort_with_stats``, stats is [] when L fits ``cfg.direct_max``.
    """
    assert keys.ndim == 2, keys.shape
    b, length = keys.shape
    if b == 0 or length <= 1:
        perm = jnp.broadcast_to(
            jnp.arange(length, dtype=jnp.int32)[None, :], (b, length)
        )
        return keys, perm, []
    codec, plan, kw, vals, b = _batched_entry(keys, cfg)
    sk, perm, stats = _execute_packed(
        kw, vals, plan, length, n_keys=b * length, with_stats=True,
        check=cfg.check
    )
    with telemetry.span("sort.decode"):
        return codec.decode(tuple(w[:b] for w in sk)), perm[:b], stats


# ----------------------------------------------------------------------
# Segmented API: ragged independent sorts, packed into padded rows
# ----------------------------------------------------------------------


def _segment_layout(n: int, segment_offsets):
    """Host-side (trace-time) packing layout for ragged segments.

    segment_offsets: host-known non-decreasing ints, off[0] == 0 and
    off[-1] == n (a traced array raises — static shapes require the
    segmentation to be known at trace time).

    Returns (off, lens, W, valid, src, unpack_src, seg_of_pos) — all
    numpy; W is the padded row width (max segment length).
    """
    off = np.asarray(segment_offsets)
    assert off.ndim == 1 and off.size >= 1, (
        "segment_offsets must be a 1-D sequence [0, ..., n]"
    )
    off = off.astype(np.int64)
    lens = np.diff(off)
    assert off[0] == 0 and off[-1] == n and (lens >= 0).all(), (
        "segment_offsets must be non-decreasing with off[0]=0, off[-1]=n"
    )
    w = int(lens.max()) if lens.size else 0
    col = np.arange(max(w, 1))
    valid = col[None, :] < lens[:, None]  # (S, W)
    src = np.where(valid, off[:-1, None] + col[None, :], 0).astype(np.int32)
    pos = np.arange(n)
    seg_of_pos = np.searchsorted(off, pos, side="right") - 1  # skips empties
    unpack_src = (seg_of_pos * max(w, 1) + (pos - off[seg_of_pos])).astype(
        np.int32
    )
    return off, lens, w, valid, src, unpack_src, seg_of_pos


def _segment_sorted_packed(x: jax.Array, segment_offsets, cfg: SortConfig):
    """Shared segment pipeline: pack ragged segments of 1-D x into a
    padded (S, W) batch (scatter-free gather), run the row-native sort,
    and return (codec, sorted words (S, W), local_perm (S, W), layout).

    Packing rule (DESIGN.md §5): row i holds segment i left-justified;
    columns past the segment length hold (all-ones words, W + j) pads —
    unique per row, above every real payload (local indices < W), so
    they sort last and the per-row capacity bound is untouched.
    """
    n = x.shape[0]
    with telemetry.span("sort.plan"):
        layout = _segment_layout(n, segment_offsets)
        _, lens, w, valid, src, _, _ = layout
        s_orig = lens.size
        codec = codec_for(x.dtype, cfg.descending)
        plan = resolve_plan(
            max(w, 1), x.dtype, cfg, rows=s_orig, pad_rows=True
        )
    with telemetry.span("sort.encode"):
        kw = codec.encode(x)
        validj = jnp.asarray(valid)
        srcj = jnp.asarray(src)
        col = jnp.asarray(np.arange(max(w, 1)), jnp.int32)[None, :]
        pkw = tuple(jnp.where(validj, u[srcj], _MAXU) for u in kw)
        pv = jnp.where(validj, col, jnp.int32(w) + col)
        pkw, pv = _pad_rows(pkw, pv, plan)
    skw, sv = _execute_packed(pkw, pv, plan, 2 * max(w, 1), n_keys=n,
                              check=cfg.check)
    return codec, tuple(u[:s_orig] for u in skw), sv[:s_orig], layout


@_entry_span
def segment_sort(x: jax.Array, segment_offsets,
                 cfg: SortConfig = DEFAULT_CONFIG) -> jax.Array:
    """Sort each segment x[off[i]:off[i+1]] independently, in place.

    Args:
        x: 1-D array of any codec dtype (see :func:`sort`).
        segment_offsets: host-known ints (python ints / numpy / concrete
            array), non-decreasing, off[0] = 0, off[-1] = len(x): the
            padded row width is a static shape.  Empty segments are fine.
        cfg: pipeline knobs (``descending`` sorts every segment
            descending).
    Returns:
        Array of x's shape; one launch for all segments; no element
        crosses a segment boundary (tested).

    Example:
        >>> import jax.numpy as jnp
        >>> from repro.core import bucket_sort
        >>> bucket_sort.segment_sort(jnp.asarray([3, 1, 9, 7, 8]), [0, 2, 5])
        Array([1, 3, 7, 8, 9], dtype=int32)
    """
    assert x.ndim == 1, x.shape
    n = x.shape[0]
    if n == 0:
        _segment_layout(n, segment_offsets)  # still validate offsets
        return x
    codec, skw, _, layout = _segment_sorted_packed(x, segment_offsets, cfg)
    with telemetry.span("sort.decode"):
        unpack = jnp.asarray(layout[5])
        return codec.decode(
            tuple(jnp.take(u.reshape(-1), unpack) for u in skw))


@_entry_span
def segment_argsort(x: jax.Array, segment_offsets,
                    cfg: SortConfig = DEFAULT_CONFIG) -> jax.Array:
    """Per-segment stable argsort with GLOBAL indices: out[off[i]:off[i+1]]
    is a permutation of [off[i], off[i+1]) and x[out] == segment_sort(x).

    Args/Returns: as :func:`segment_sort`, but an int32 permutation.
    """
    assert x.ndim == 1, x.shape
    n = x.shape[0]
    if n == 0:
        _segment_layout(n, segment_offsets)
        return jnp.arange(0, dtype=jnp.int32)
    _, _, sv, layout = _segment_sorted_packed(x, segment_offsets, cfg)
    with telemetry.span("sort.decode"):
        off, _, _, _, _, unpack_src, seg_of_pos = layout
        local = jnp.take(sv.reshape(-1), jnp.asarray(unpack_src))
        return jnp.asarray(off[seg_of_pos].astype(np.int32)) + local
