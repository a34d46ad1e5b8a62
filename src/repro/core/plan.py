"""Sort-plan IR: the static schedule of GPU BUCKET SORT as data.

The paper's deterministic regular sampling makes every quantity of the
multi-level pipeline a *static* function of ``(shape, dtype, config)``:
recursion levels, per-level ``rows x tile`` geometry, ``s_round``,
bucket capacities, pad budgets, kernel block sizes, fusion and
relocation choices.  Nothing is data-dependent — that is the theorem
that lets the whole sort run under XLA's static shapes (DESIGN.md §2).

This module reifies that schedule as a frozen, hashable IR
(:class:`SortPlan` / :class:`LevelPlan`) computed ONCE by
:func:`build_plan` and merely *walked* by the executor in
``core/bucket_sort.py``.  The split buys three things (DESIGN.md §7):

  * the executor's step functions take plan fields instead of
    re-deriving geometry, so one mechanism drives the 1-D, batched,
    segmented, partial (top-k) and distributed entry points;
  * plans are jit static arguments — equal plans hit the same compiled
    executable, so a plan-cache hit means ZERO retraces;
  * plans serialize (:func:`plan_to_dict` / :func:`plan_from_dict`)
    byte-stably, which is what the ``core/autotune.py`` persistent plan
    cache stores and reloads.

``build_plan`` is pure and deterministic: the same
``(length, dtype, cfg, rows)`` produces a byte-identical plan
(property-tested in ``tests/test_plan.py``).  The only environment
inputs are the resolved backend/impl/interpret defaults, which are part
of the plan's identity (and of the autotune cache key).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json

import jax

from repro.core.key_codec import codec_for
from repro.core.sort_config import SortConfig, next_pow2, round_up

# Static recursion depth guard: the level count shrinks geometrically
# (cap < lp and m*s < lp for s < tile), so real plans are < 8 levels
# deep; hitting this means a degenerate config (e.g. s == tile with
# length > direct_max, where the sample array never shrinks).
_MAX_DEPTH = 64
# Lanes per block of the blocked compaction: one TPU vreg row.
COMPACT_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class LevelPlan:
    """One node of the static recursion tree (all trace-time ints).

    ``kind == "direct"``: single-tile bitonic sort of each (rows, lp)
    row, lp = next_pow2(length).  ``kind == "bucket"``: one bucket
    round — local tile sort, sample recursion (``sample_plan``),
    splitter partition, relocation into the dense (rows*s_round, cap)
    bucket array, bucket recursion (``bucket_plan``), compaction.

    Attributes:
        kind: "direct" | "bucket".
        rows: row count entering this level.
        length: row length entering this level (pre-padding).
        lp: padded row length (direct: next power of two; bucket:
            rounded up to a tile multiple).
        block_rows: resolved tiles-per-grid-program for the level's
            bitonic sort (None on the xla path) — plan-carried kernel
            geometry, already a power-of-two divisor of the tile count.
        tile / s: the level's tile width T and samples per tile
            (bucket levels only; 0 for direct).
        m: tiles per row (lp // tile).
        s_round: buckets this round (equidistant global splitters + 1).
        cap: static per-bucket capacity — the paper's regular-sampling
            bound round_up(lp/s_round + lp/s, 128) (DESIGN.md §2).
        part_block_rows: resolved block size of the fused
            splitter-partition kernel (None when unfused / xla).
        fuse_sampling / fuse_ranking / relocation: per-level pipeline
            choices (today uniform across levels, copied from cfg).
        strategy: the level's local-sort algorithm ("bitonic" | "radix"
            | "merge") — a PER-LEVEL plan field (DESIGN.md §8); the
            executor dispatches ``ops.sort_tiles`` on it.
        radix_bits / merge_run: strategy knobs carried alongside
            (consulted only by the matching strategy).
        compact_block: lanes W of the blocks step 9's gather compaction
            moves whole (DESIGN.md §4): ``min(COMPACT_BLOCK, tile)``,
            which divides ``lp`` (a multiple of the power-of-two tile)
            and ``cap`` (a multiple of 128).  0 on the scatter path and
            for direct nodes.
        sample_plan: step-4 recursion on the (rows, m*s) sample array.
        bucket_plan: step-9 recursion on the (rows*s_round, cap)
            bucket rows.
    """

    kind: str
    rows: int
    length: int
    lp: int
    block_rows: int | None
    tile: int = 0
    s: int = 0
    m: int = 0
    s_round: int = 0
    cap: int = 0
    part_block_rows: int | None = None
    fuse_sampling: bool = False
    fuse_ranking: bool = False
    relocation: str = "gather"
    strategy: str = "bitonic"
    radix_bits: int = 4
    merge_run: int = 512
    compact_block: int = 0
    sample_plan: "LevelPlan | None" = None
    bucket_plan: "LevelPlan | None" = None

    # -- cost-relevant derived geometry (properties, not serialized;
    #    core/cost_model.py reads these instead of re-deriving) --------

    @property
    def elements(self) -> int:
        """Padded elements entering this level (rows * lp)."""
        return self.rows * self.lp

    @property
    def tiles(self) -> int:
        """Tile count of the level's local sort (bucket: rows * m)."""
        return self.rows * self.m if self.kind == "bucket" else self.rows

    @property
    def sample_elements(self) -> int:
        """Step-3 sample array size this level emits (0 for direct)."""
        return self.rows * self.m * self.s if self.kind == "bucket" else 0

    @property
    def bucket_elements(self) -> int:
        """Dense bucket-array size after relocation (0 for direct)."""
        if self.kind != "bucket":
            return 0
        return self.rows * self.s_round * self.cap

    def max_elements(self) -> int:
        """Largest array this node or its children hold (rows * lp, or
        the dense bucket array) — the bound on every flat int32 index
        the executor computes."""
        if self.kind != "bucket":
            return self.elements
        return max(self.elements, self.bucket_elements,
                   self.sample_plan.max_elements(),
                   self.bucket_plan.max_elements())

    def moved_elements(self) -> int:
        """Elements per array that relocation and compaction write in
        this node and its children: the dense bucket array
        (rows * s_round * cap) and the compacted rows (rows * lp) of
        every bucket round, sample rounds included; 0 for direct."""
        if self.kind != "bucket":
            return 0
        return (self.bucket_elements + self.elements
                + self.sample_plan.moved_elements()
                + self.bucket_plan.moved_elements())

    def pad_span(self) -> int:
        """Pad payloads this node draws above the ``pad_base`` it is
        entered with: its own column padding, the relocation pads of a
        bucket round (``cap`` per row), and its children's spans — the
        same walk as the executor's ``pad_base`` threading."""
        span = self.lp - self.length
        if self.kind == "bucket":
            span += (self.sample_plan.pad_span() + self.cap
                     + self.bucket_plan.pad_span())
        return span


@dataclasses.dataclass(frozen=True)
class SortPlan:
    """The full static schedule of one sort signature.

    Frozen and hashable: used as a jit static argument, so two calls
    carrying equal plans share one compiled executable.

    Attributes:
        rows: entry row count (1 for the 1-D API, B for batched).
        length: entry row length L.
        dtype_name: canonical key dtype name (``jnp.dtype(...).name``).
        num_words: uint32 key words per element (codec).
        descending: order baked into the key codec.
        impl: resolved implementation ("pallas" | "xla").
        interpret: resolved Pallas interpret mode.
        backend: jax.default_backend() at build time (cache key part).
        rows_padded: rows after batch row-padding (== rows unless the
            batched pallas path pads to a cfg.row_pad multiple).
        cfg_fingerprint: stable hash of the generating config (every
            field except ``plan`` — see :func:`config_fingerprint`).
        root: the level tree the executor walks.
    """

    rows: int
    length: int
    dtype_name: str
    num_words: int
    descending: bool
    impl: str
    interpret: bool
    backend: str
    rows_padded: int
    cfg_fingerprint: str
    root: LevelPlan

    @property
    def bytes_per_element(self) -> int:
        """HBM bytes one element occupies on the hot path: the key
        words plus the int32 payload word (cost-model input)."""
        return 4 * (self.num_words + 1)

    @property
    def num_levels(self) -> int:
        """Bucket rounds on the main (bucket_plan) spine."""
        n, node = 0, self.root
        while node is not None and node.kind == "bucket":
            n += 1
            node = node.bucket_plan
        return n

    @functools.cached_property
    def moved_elements(self) -> int:
        """Elements per array that relocation and compaction write in
        one call (:meth:`LevelPlan.moved_elements` of the root),
        computed once per plan object: the entry counts it every call."""
        return self.root.moved_elements()

    def signature(self) -> tuple:
        """The cache identity: (shape, dtype, backend, cfg-fingerprint)."""
        return (
            self.rows,
            self.length,
            self.dtype_name,
            self.descending,
            self.impl,
            self.interpret,
            self.backend,
            self.cfg_fingerprint,
        )

    def describe(self) -> str:
        """Human-readable one-plan summary (levels and geometry)."""
        lines = [
            f"SortPlan(rows={self.rows}->{self.rows_padded}, "
            f"length={self.length}, dtype={self.dtype_name}"
            f"{' desc' if self.descending else ''}, impl={self.impl}, "
            f"levels={self.num_levels})"
        ]
        node, depth = self.root, 0
        while node is not None:
            if node.kind == "direct":
                lines.append(
                    f"  L{depth}: direct rows={node.rows} lp={node.lp} "
                    f"block_rows={node.block_rows} strategy={node.strategy}"
                )
                break
            lines.append(
                f"  L{depth}: bucket rows={node.rows} lp={node.lp} "
                f"tile={node.tile} s={node.s} m={node.m} "
                f"s_round={node.s_round} cap={node.cap} "
                f"block_rows={node.block_rows} reloc={node.relocation} "
                f"compact_block={node.compact_block} strategy={node.strategy}"
            )
            node = node.bucket_plan
            depth += 1
        return "\n".join(lines)


def config_fingerprint(cfg: SortConfig) -> str:
    """Stable hash of every SortConfig field except ``plan`` and ``check``.

    The ``plan`` field selects HOW a plan is obtained (default /
    autotune / file); it must not perturb the identity of the plans the
    cache is keyed by, or a cached plan could never match the config
    that requests it.  ``check`` is a call-time verification knob
    (``core/guard.py``) that never changes the schedule: excluding it
    keeps checked and unchecked runs on the same cache entries (and
    keeps fingerprints stable across the field's introduction).
    """
    d = dataclasses.asdict(cfg)
    d.pop("plan", None)
    d.pop("check", None)
    blob = json.dumps(d, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def resolve_impl(cfg: SortConfig) -> tuple[str, bool]:
    """(impl, interpret) with the cfg Nones resolved."""
    from repro.kernels import ops  # local import: ops imports core.key_codec

    impl = cfg.impl or ops.default_impl()
    interpret = (
        ops.default_interpret() if cfg.interpret is None else cfg.interpret
    )
    return impl, interpret


def _resolve_backend(cfg: SortConfig) -> tuple[str, bool, str]:
    """(impl, interpret, backend) with the cfg Nones resolved.

    Raises:
        ValueError: naming ``cfg.strategy`` when it has no native TPU
            kernel and the plan would run Pallas without interpret mode.
    """
    from repro.kernels import ops

    impl, interpret = resolve_impl(cfg)
    ops.check_strategy(cfg.strategy, impl, interpret)
    return impl, interpret, jax.default_backend()


def _sort_block_rows(
    impl: str, tiles: int, t: int, cfg_block_rows: int | None, nw: int
) -> int | None:
    from repro.kernels import bitonic

    if impl != "pallas":
        return None
    return bitonic.effective_block_rows(tiles, t, cfg_block_rows, num_words=nw)


def _build_node(
    rows: int, length: int, cfg: SortConfig, impl: str, nw: int, depth: int
) -> LevelPlan:
    if depth > _MAX_DEPTH:
        raise ValueError(
            "sort-plan recursion exceeded depth "
            f"{_MAX_DEPTH} at (rows={rows}, length={length}); degenerate "
            "config (s == tile with length > direct_max never shrinks "
            "the sample array)"
        )
    if length <= cfg.direct_max:
        lp = next_pow2(length)
        return LevelPlan(
            kind="direct",
            rows=rows,
            length=length,
            lp=lp,
            block_rows=_sort_block_rows(impl, rows, lp, cfg.block_rows, nw),
            strategy=cfg.strategy,
            radix_bits=cfg.radix_bits,
            merge_run=cfg.merge_run,
        )

    t, sper = cfg.tile, cfg.s
    lp = round_up(length, t)
    m = lp // t
    # Step 5: s_round - 1 equidistant global splitters (s_round buckets).
    s_round = min(max(next_pow2(-(-2 * lp // t)), 2), sper)
    # The paper's guaranteed capacity (DESIGN.md §2), lane-aligned.
    cap = round_up(lp // s_round + lp // sper, 128)
    compact_block = min(COMPACT_BLOCK, t) if cfg.relocation == "gather" else 0
    part_block_rows = None
    if impl == "pallas" and cfg.fuse_ranking:
        from repro.kernels import splitter

        part_block_rows = splitter.partition_block_rows(
            rows * m, t, s_round - 1, num_words=nw
        )
    return LevelPlan(
        kind="bucket",
        rows=rows,
        length=length,
        lp=lp,
        block_rows=_sort_block_rows(impl, rows * m, t, cfg.block_rows, nw),
        tile=t,
        s=sper,
        m=m,
        s_round=s_round,
        cap=cap,
        part_block_rows=part_block_rows,
        fuse_sampling=cfg.fuse_sampling,
        fuse_ranking=cfg.fuse_ranking,
        relocation=cfg.relocation,
        strategy=cfg.strategy,
        radix_bits=cfg.radix_bits,
        merge_run=cfg.merge_run,
        compact_block=compact_block,
        sample_plan=_build_node(rows, m * sper, cfg, impl, nw, depth + 1),
        bucket_plan=_build_node(
            rows * s_round, cap, cfg, impl, nw, depth + 1
        ),
    )


@functools.lru_cache(maxsize=512)
def _assemble_plan(
    rows: int,
    length: int,
    dtype_name: str,
    nw: int,
    descending: bool,
    cfg: SortConfig,
    pad_rows: bool,
    impl: str,
    interpret: bool,
    backend: str,
) -> SortPlan:
    """Memoized plan assembly: the cache key includes the RESOLVED
    backend triple, so a changed env/backend can never serve a stale
    plan, while repeated calls return the SAME object (fast jit static
    lookups)."""
    rows_padded = rows
    if pad_rows and impl == "pallas" and cfg.row_pad > 1 and rows > 0:
        rows_padded = round_up(rows, cfg.row_pad)
    return SortPlan(
        rows=rows,
        length=length,
        dtype_name=dtype_name,
        num_words=nw,
        descending=descending,
        impl=impl,
        interpret=interpret,
        backend=backend,
        rows_padded=rows_padded,
        cfg_fingerprint=config_fingerprint(cfg),
        root=_build_node(max(rows_padded, 1), length, cfg, impl, nw, 0),
    )


def build_plan(
    length: int,
    dtype,
    cfg: SortConfig,
    *,
    rows: int = 1,
    pad_rows: bool = False,
) -> SortPlan:
    """Compute the full static schedule for one sort signature.

    Pure and deterministic: equal inputs produce equal (byte-identical
    once serialized) plans.  Called once per signature (memoized); the
    executor in ``core/bucket_sort.py`` only walks the result.

    Args:
        length: row length L (the 1-D array length, or the row width of
            the batched/segmented packed array).
        dtype: key dtype (any ``core/key_codec`` dtype).
        cfg: pipeline knobs; ``cfg.descending`` is baked into the plan
            identity, ``cfg.plan`` is NOT (it selects how plans are
            obtained, see :func:`config_fingerprint`).
        rows: entry row count (1 for the 1-D API, B for batched).
        pad_rows: apply the batched-path row padding to a multiple of
            ``cfg.row_pad`` (DESIGN.md §5) — the batched/segmented
            entry points pass True, the 1-D path False.
    Returns:
        A frozen :class:`SortPlan`.

    Example:
        >>> from repro.core.plan import build_plan
        >>> from repro.core.sort_config import SortConfig
        >>> p = build_plan(100_000, "int32", SortConfig(impl="xla"))
        >>> (p.length, p.root.kind, p.num_levels >= 1)
        (100000, 'bucket', True)
    """
    import jax.numpy as jnp

    codec = codec_for(dtype, cfg.descending)
    impl, interpret, backend = _resolve_backend(cfg)
    return _assemble_plan(
        rows, length, jnp.dtype(dtype).name, codec.num_words,
        cfg.descending, cfg, pad_rows, impl, interpret, backend,
    )


def build_words_plan(
    length: int,
    num_words: int,
    cfg: SortConfig,
    *,
    rows: int = 1,
    pad_rows: bool = False,
) -> SortPlan:
    """Plan for callers already holding CANONICAL uint32 key words
    (``distributed_sort.sorted_shard``, the recursion shims): the
    canonical domain is always ascending, so there is no dtype/codec —
    only the word count matters for geometry."""
    impl, interpret, backend = _resolve_backend(cfg)
    return _assemble_plan(
        rows, length, f"uint32x{num_words}", num_words, False, cfg,
        pad_rows, impl, interpret, backend,
    )


# ----------------------------------------------------------------------
# Partial-sort (top-k) plan: the one-bucket-round schedule
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopkPlan:
    """Static schedule of the partial sort (one bucket round, steps 1-7
    + candidate pack + candidate sort — ``core/partial_sort.py``).

    Attributes:
        rows: batch rows (1 for the 1-D entry).
        length: scores per row (n / vocab).
        k: requested top-k.
        lp: length padded to a tile multiple.
        m: tiles per row.
        tile / s: tile width and samples per tile.
        cap: the bucket-capacity bound round_up(2*lp/s, 128) the
            threshold argument relies on.
        ccap: static candidate-buffer width round_up(min(k+cap, lp), 128).
        block_rows: resolved tile-sort block size (None on xla).
        raw_block_rows: the unresolved cfg knob, carried as the UPPER
            BOUND for the small sample/candidate sorts (whose padded
            widths the kernels clamp against).
        direct_max: lengths up to this take the direct single-tile path.
        strategy / radix_bits / merge_run: the local-sort strategy for
            the tile/candidate sorts, copied from the cfg (DESIGN.md
            §8; the candidate packs preserve the payload invariant the
            non-bitonic strategies rely on).
        impl / interpret / backend: resolved as in :class:`SortPlan`.
    """

    rows: int
    length: int
    k: int
    lp: int
    m: int
    tile: int
    s: int
    cap: int
    ccap: int
    block_rows: int | None
    raw_block_rows: int | None
    direct_max: int
    impl: str
    interpret: bool
    backend: str
    strategy: str = "bitonic"
    radix_bits: int = 4
    merge_run: int = 512

    @property
    def elements(self) -> int:
        """Padded elements entering the bucket round (rows * lp)."""
        return max(self.rows, 1) * self.lp

    @property
    def candidate_elements(self) -> int:
        """Candidate-buffer elements of the final pack (rows * ccap)."""
        return max(self.rows, 1) * self.ccap


@functools.lru_cache(maxsize=512)
def _assemble_topk_plan(
    length: int, k: int, nw: int, cfg: SortConfig, rows: int,
    impl: str, interpret: bool, backend: str,
) -> TopkPlan:
    """Memoized topk-plan assembly; like :func:`_assemble_plan`, the
    RESOLVED backend triple is part of the cache key so a changed
    env/backend can never serve a stale plan."""
    t, sper = cfg.tile, cfg.s
    lp = round_up(length, t)
    m = lp // t
    cap = round_up(2 * lp // sper, 128)
    ccap = round_up(min(k + cap, lp), 128)
    return TopkPlan(
        rows=rows,
        length=length,
        k=k,
        lp=lp,
        m=m,
        tile=t,
        s=sper,
        cap=cap,
        ccap=ccap,
        block_rows=_sort_block_rows(
            impl, max(rows, 1) * m, t, cfg.block_rows, nw
        ),
        raw_block_rows=cfg.block_rows,
        direct_max=cfg.direct_max,
        impl=impl,
        interpret=interpret,
        backend=backend,
        strategy=cfg.strategy,
        radix_bits=cfg.radix_bits,
        merge_run=cfg.merge_run,
    )


def build_topk_plan(
    length: int, k: int, dtype, cfg: SortConfig, *, rows: int = 1
) -> TopkPlan:
    """Static schedule for :func:`repro.core.partial_sort.topk`.

    Same builder conventions as :func:`build_plan` (pure,
    deterministic, backend-resolved, memoized).  Lengths <=
    cfg.direct_max take the direct path and never consult the bucket
    fields.
    """
    codec = codec_for(dtype, descending=True)
    impl, interpret, backend = _resolve_backend(cfg)
    return _assemble_topk_plan(
        length, k, codec.num_words, cfg, rows, impl, interpret, backend
    )


# ----------------------------------------------------------------------
# ShardPlan: the distributed (multi-device) schedule as an IR node
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardGeometry:
    """Static capacity arithmetic of the distributed deal-round sort
    (all trace-time ints; the one source of truth for the bound —
    ``DistSortSpec`` and :func:`build_shard_plan` both read it).

    Derivation (DESIGN.md §9): regular sampling bounds every global
    bucket at ``b_t <= n_pad * (1 + 1/oversample)``; the deal round
    spreads each source's contribution to bucket t evenly over the D
    devices (±1), so the per-device-pair chunk is bounded by the STATIC
    ``c_pair = ceil(b_t / d) + d`` (lane-aligned to ``pair_align``) and
    the exchange is one fixed-shape ``all_to_all``.

    Attributes:
        n_local: local shard length (pre-padding).
        d: devices along the sort axis.
        oversample: regular-sampling oversample factor c.
        pair_align: lane-alignment multiple of the c_pair capacity
            (the exchange-tiling knob the autotuner searches).
        s_loc: local samples per shard (= oversample * d).
        n_pad: shard length padded so the deal (multiple of d) and the
            equidistant sampling (multiple of s_loc) are both exact.
        b_t: max global bucket size, n_pad * (1 + 1/oversample).
        c_pair: static per-pair all_to_all capacity.
        out_cap: static per-shard output capacity >= any bucket total.
    """

    n_local: int
    d: int
    oversample: int
    pair_align: int
    s_loc: int
    n_pad: int
    b_t: int
    c_pair: int
    out_cap: int


def shard_geometry(
    n_local: int, d: int, oversample: int = 8, pair_align: int = 8
) -> ShardGeometry:
    """Compute the static distributed-sort geometry (validated).

    Raises:
        ValueError: naming the offending argument, matching the
            ``SortConfig.__post_init__`` convention — ``oversample``
            must be a power of two >= 1 (so ``s_loc = oversample * d``
            stays power-of-two-compatible with the power-of-two device
            meshes the deal targets), ``pair_align`` a power of two
            >= 8, ``n_local`` >= 1.

    Example:
        >>> from repro.core.plan import shard_geometry
        >>> g = shard_geometry(n_local=1000, d=4, oversample=8)
        >>> (g.s_loc, g.n_pad, g.b_t, g.c_pair >= g.b_t // 4 + 4)
        (32, 1024, 1152, True)
    """
    if not (isinstance(n_local, int) and n_local >= 1):
        raise ValueError(
            f"shard_geometry n_local must be an int >= 1, got {n_local!r}"
        )
    if not (isinstance(d, int) and d >= 2):
        raise ValueError(
            f"shard_geometry d must be an int >= 2 (devices along the "
            f"sort axis), got {d!r}"
        )
    if not (
        isinstance(oversample, int)
        and oversample >= 1
        and oversample & (oversample - 1) == 0
    ):
        raise ValueError(
            "oversample must be a power of two >= 1 (keeps s_loc = "
            f"oversample * d power-of-two-compatible), got {oversample!r}"
        )
    if not (
        isinstance(pair_align, int)
        and pair_align >= 8
        and pair_align & (pair_align - 1) == 0
    ):
        raise ValueError(
            f"pair_align must be a power of two >= 8, got {pair_align!r}"
        )
    s_loc = oversample * d
    n_pad = round_up(n_local, s_loc)
    b_t = n_pad + n_pad // oversample
    c_pair = round_up(-(-b_t // d) + d, pair_align)
    out_cap = min(round_up(b_t, 8), d * c_pair)
    return ShardGeometry(
        n_local=n_local,
        d=d,
        oversample=oversample,
        pair_align=pair_align,
        s_loc=s_loc,
        n_pad=n_pad,
        b_t=b_t,
        c_pair=c_pair,
        out_cap=out_cap,
    )


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """The full static schedule of one DISTRIBUTED sort signature.

    Frozen and hashable — the jit static argument of the distributed
    executor (``core/distributed_sort._sharded_argsort``): equal
    ``(shape, mesh, dtype, plan)`` signatures share one compiled
    executable, exactly as :class:`SortPlan` does for the single-device
    path (trace-count discipline tested in ``tests/test_distributed``).

    Attributes:
        axis: mesh axis name tuple the sort spans (1 or 2 axes).
        d: devices along the sort axis (product over ``axis``).
        n_local / n_pad: shard length before/after deal+sampling padding.
        oversample: regular-sampling oversample factor c.
        pair_align: lane alignment of the per-pair exchange capacity
            (the exchange-tiling knob; part of ``c_pair``).
        s_loc: local samples per shard (oversample * d).
        b_t: max global bucket size, n_pad * (1 + 1/oversample).
        c_pair: STATIC per-pair all_to_all capacity (DESIGN.md §9).
        out_cap: static per-shard output capacity (>= any bucket total).
        dtype_name / num_words / descending: key codec identity.
        impl / interpret / backend: resolved as in :class:`SortPlan`.
        cfg_fingerprint: stable hash of the generating config.
        run_plan: phase-1 local sort of the (1, n_pad) shard.
        dealt_plan: phase-3 local sort of the dealt (1, n_pad) run.
        sample_plan: replicated sort of the (1, d*s_loc) gathered
            samples.
        bucket_plan: phase-7 local sort of the received (1, d*c_pair)
            buckets.  Each is a full :class:`SortPlan` and inherits the
            per-level strategy dispatch (DESIGN.md §8), so shards can
            e.g. radix-sort their local runs.
    """

    axis: tuple[str, ...]
    d: int
    n_local: int
    n_pad: int
    oversample: int
    pair_align: int
    s_loc: int
    b_t: int
    c_pair: int
    out_cap: int
    dtype_name: str
    num_words: int
    descending: bool
    impl: str
    interpret: bool
    backend: str
    cfg_fingerprint: str
    run_plan: SortPlan
    dealt_plan: SortPlan
    sample_plan: SortPlan
    bucket_plan: SortPlan

    @property
    def n_glob(self) -> int:
        """Global padded element count (n_pad * d)."""
        return self.n_pad * self.d

    @property
    def bytes_per_element(self) -> int:
        """HBM/interconnect bytes per element (key words + payload)."""
        return 4 * (self.num_words + 1)

    @property
    def exchange_elements(self) -> int:
        """Per-device bucket-exchange volume, c_pair-padded (d * c_pair
        elements sent and received in the fixed-shape all_to_all)."""
        return self.d * self.c_pair

    @functools.cached_property
    def moved_elements(self) -> int:
        """Elements per array that relocation and compaction write on
        one device in one call: the sum of the four phase plans'
        :attr:`SortPlan.moved_elements` (every device runs the same
        four plans)."""
        return sum(p.moved_elements for p in (
            self.run_plan, self.dealt_plan, self.sample_plan,
            self.bucket_plan))

    @property
    def collective_elements(self) -> int:
        """Total per-device interconnect elements across the schedule:
        the deal all_to_all (n_pad) + the sample gather (d * s_loc) +
        the bucket exchange (d * c_pair) — cost-model input."""
        return self.n_pad + self.d * self.s_loc + self.exchange_elements

    def signature(self) -> tuple:
        """The cache identity: mesh signature (axis names + D), shard
        shape, dtype+order, oversample/pair_align, resolved backend
        triple, and the requesting config's fingerprint."""
        return (
            "x".join(self.axis),
            self.d,
            self.n_local,
            self.dtype_name,
            self.descending,
            self.oversample,
            self.pair_align,
            self.impl,
            self.interpret,
            self.backend,
            self.cfg_fingerprint,
        )

    def describe(self) -> str:
        """Human-readable summary of the distributed schedule."""
        lines = [
            f"ShardPlan(axis={self.axis}, d={self.d}, "
            f"n_local={self.n_local}->{self.n_pad}, "
            f"dtype={self.dtype_name}"
            f"{' desc' if self.descending else ''}, "
            f"oversample={self.oversample}, c_pair={self.c_pair}, "
            f"out_cap={self.out_cap}, impl={self.impl})"
        ]
        for name in ("run_plan", "dealt_plan", "sample_plan", "bucket_plan"):
            sub: SortPlan = getattr(self, name)
            lines.append(
                f"  {name}: length={sub.length} levels={sub.num_levels} "
                f"strategy={sub.root.strategy}"
            )
        return "\n".join(lines)


@functools.lru_cache(maxsize=256)
def _assemble_shard_plan(
    axis: tuple[str, ...],
    d: int,
    n_local: int,
    dtype_name: str,
    nw: int,
    descending: bool,
    cfg: SortConfig,
    oversample: int,
    pair_align: int,
    impl: str,
    interpret: bool,
    backend: str,
) -> ShardPlan:
    """Memoized shard-plan assembly (resolved backend triple in the
    key, as in :func:`_assemble_plan`): repeated calls return the SAME
    object, so the distributed executor's jit static-arg lookups are
    fast and equal signatures share one executable."""
    g = shard_geometry(n_local, d, oversample, pair_align)
    sub = functools.partial(build_words_plan, num_words=nw, cfg=cfg)
    return ShardPlan(
        axis=axis,
        d=d,
        n_local=n_local,
        n_pad=g.n_pad,
        oversample=oversample,
        pair_align=pair_align,
        s_loc=g.s_loc,
        b_t=g.b_t,
        c_pair=g.c_pair,
        out_cap=g.out_cap,
        dtype_name=dtype_name,
        num_words=nw,
        descending=descending,
        impl=impl,
        interpret=interpret,
        backend=backend,
        cfg_fingerprint=config_fingerprint(cfg),
        run_plan=sub(g.n_pad),
        dealt_plan=sub(g.n_pad),
        sample_plan=sub(d * g.s_loc),
        bucket_plan=sub(d * g.c_pair),
    )


def build_shard_plan(
    axis,
    d: int,
    n_local: int,
    dtype,
    cfg: SortConfig,
    *,
    oversample: int = 8,
    pair_align: int = 8,
) -> ShardPlan:
    """Compute the full static distributed schedule for one signature.

    Pure and deterministic, like :func:`build_plan`: the same
    ``(axis, d, n_local, dtype, cfg, oversample, pair_align)`` produces
    an equal (and identical-object, memoized) plan.  The executor in
    ``core/distributed_sort.py`` derives nothing from it.

    Args:
        axis: mesh axis name (str) or tuple of names; normalized to a
            tuple in the plan.
        d: devices along the sort axis (>= 2).
        n_local: per-shard element count (n_global // d).
        dtype: key dtype (any ``core/key_codec`` dtype; 64-bit needs
            x64 mode).
        cfg: pipeline knobs for the per-phase local sorts
            (``descending`` honored; ``plan`` is NOT consulted here —
            plan selection happens in ``make_sharded_sort``).
        oversample: regular-sampling oversample factor c (power of two
            >= 1; bounds every global bucket at n_pad*(1 + 1/c)).
        pair_align: lane-alignment multiple of the per-pair exchange
            capacity (power of two >= 8).
    Returns:
        A frozen, hashable :class:`ShardPlan`.
    Raises:
        ValueError: naming the offending argument (``oversample``,
            ``pair_align``, ``d``, ``n_local``) — validation happens at
            plan-build time, not as a shape error mid-trace.

    Example:
        >>> from repro.core.plan import build_shard_plan
        >>> from repro.core.sort_config import SortConfig
        >>> p = build_shard_plan("data", 4, 2048, "int32",
        ...                      SortConfig(impl="xla"), oversample=8)
        >>> (p.axis, p.n_pad, p.c_pair % 8, p.out_cap >= p.b_t)
        (('data',), 2048, 0, True)
    """
    import jax.numpy as jnp

    axt = (axis,) if isinstance(axis, str) else tuple(axis)
    codec = codec_for(dtype, cfg.descending)
    impl, interpret, backend = _resolve_backend(cfg)
    return _assemble_shard_plan(
        axt, d, n_local, jnp.dtype(dtype).name, codec.num_words,
        cfg.descending, cfg, oversample, pair_align, impl, interpret,
        backend,
    )


# ----------------------------------------------------------------------
# Serialization: byte-stable dict/JSON round-trip for the plan cache
# ----------------------------------------------------------------------

# v2: LevelPlan grew the per-level strategy fields (strategy /
# radix_bits / merge_run).  v3: LevelPlan grew ``compact_block``.
# Older records fail plan_from_dict with a ValueError, which the
# autotune store treats as a clean cache miss (re-tune and overwrite)
# — never a silently misread plan.
_SCHEMA = "sort_plan/v3"


def _node_to_dict(node: LevelPlan | None):
    if node is None:
        return None
    d = dataclasses.asdict(node)
    d["sample_plan"] = _node_to_dict(node.sample_plan)
    d["bucket_plan"] = _node_to_dict(node.bucket_plan)
    return d


def _node_from_dict(d) -> LevelPlan | None:
    if d is None:
        return None
    d = dict(d)
    d["sample_plan"] = _node_from_dict(d.get("sample_plan"))
    d["bucket_plan"] = _node_from_dict(d.get("bucket_plan"))
    return LevelPlan(**d)


def plan_to_dict(plan: SortPlan) -> dict:
    """JSON-serializable representation; inverse of :func:`plan_from_dict`.

    ``plan_from_dict(plan_to_dict(p)) == p`` exactly (tested), which is
    what lets the persistent cache assert a reloaded plan is identical
    to the one it saved.
    """
    d = dataclasses.asdict(plan)
    d["root"] = _node_to_dict(plan.root)
    d["schema"] = _SCHEMA
    return d


def plan_from_dict(d: dict) -> SortPlan:
    """Reconstruct a :class:`SortPlan` saved by :func:`plan_to_dict`.

    Raises:
        ValueError: on a missing/mismatched schema tag.
    """
    d = dict(d)
    schema = d.pop("schema", None)
    if schema != _SCHEMA:
        raise ValueError(f"not a {_SCHEMA} record (schema={schema!r})")
    d["root"] = _node_from_dict(d["root"])
    return SortPlan(**d)


def plan_json(plan: SortPlan) -> str:
    """Canonical JSON encoding (sorted keys) — byte-identical for equal
    plans; the determinism property tests compare these strings."""
    return json.dumps(plan_to_dict(plan), sort_keys=True)


# v1: the initial distributed-schedule record.  The four per-phase
# sub-plans are embedded as full sort_plan records, so a sort-plan
# schema bump invalidates stored shard plans too (plan_from_dict raises
# and the autotune store treats the record as a clean miss).
_SHARD_SCHEMA = "shard_plan/v1"
_SHARD_SUBPLANS = ("run_plan", "dealt_plan", "sample_plan", "bucket_plan")


def shard_plan_to_dict(plan: ShardPlan) -> dict:
    """JSON-serializable representation; inverse of
    :func:`shard_plan_from_dict` (exact round-trip, tested)."""
    d = dataclasses.asdict(plan)
    d["axis"] = list(plan.axis)
    for name in _SHARD_SUBPLANS:
        d[name] = plan_to_dict(getattr(plan, name))
    d["schema"] = _SHARD_SCHEMA
    return d


def shard_plan_from_dict(d: dict) -> ShardPlan:
    """Reconstruct a :class:`ShardPlan` saved by
    :func:`shard_plan_to_dict`.

    Raises:
        ValueError: on a missing/mismatched schema tag (also raised by
            the embedded per-phase ``plan_from_dict`` calls for stale
            sub-plan schemas).
    """
    d = dict(d)
    schema = d.pop("schema", None)
    if schema != _SHARD_SCHEMA:
        raise ValueError(f"not a {_SHARD_SCHEMA} record (schema={schema!r})")
    d["axis"] = tuple(d["axis"])
    for name in _SHARD_SUBPLANS:
        d[name] = plan_from_dict(d[name])
    return ShardPlan(**d)


def shard_plan_json(plan: ShardPlan) -> str:
    """Canonical JSON encoding of a shard plan (sorted keys)."""
    return json.dumps(shard_plan_to_dict(plan), sort_keys=True)
