"""Analytic per-plan-node cost model: rank schedules WITHOUT running them.

The paper's core property — deterministic regular sampling makes every
bucket capacity a *static guarantee* — has a corollary for tuning: the
cost of a plan is a function of its **geometry**, not of the data.
Bytes moved per pass, compare-exchange counts, exchange volume — all
are closed-form in the plan fields.  This module generalizes DESIGN.md
§6's two-word data-movement model into a full walk over the plan IR
(:func:`estimate` on ``SortPlan`` / ``TopkPlan`` / ``ShardPlan``), so
the autotuner (``core/autotune.py``) can score the WHOLE candidate
space analytically and measure only the top few (the
AttentionEngine/roller policy shape; the multiway-mergesort analysis
arXiv 1702.07961 shows such a data-movement model ranks GPU sort
variants well).

The unit is **HBM byte-equivalents**: one unit = the cost of moving one
byte between HBM and VMEM.  Compute is folded in at ``OP_BYTE_EQUIV``
bytes per compare-unit (a balance-point constant, not a measurement);
interconnect traffic at ``COLLECTIVE_BYTE_WEIGHT`` bytes per byte.
Scores therefore rank plans; they are not wall-time predictions.  The
model's *rank* quality against measured times is what the tests pin
(Spearman over a fixed candidate slice, ``tests/test_cost_model.py``)
and what ``BENCH_sort.json`` records per candidate.

Every term is geometry: the local sort is the data-oblivious bitonic
network, so no term depends on the data — the paper's determinism,
kept.

``COST_MODEL_VERSION`` is persisted with every autotuned store record;
a version bump makes old records a clean cache miss (re-tune, never
misread — mirrors the plan-schema-bump behavior).
"""

from __future__ import annotations

import dataclasses

from repro.core.plan import LevelPlan, ShardPlan, SortPlan, TopkPlan
from repro.core.sort_config import next_pow2

# Bump on ANY change to the constants or formulas below: persisted
# autotune records carry this tag and a mismatch at load is a clean
# re-tune (core/autotune.plan_for / shard_plan_for).
COST_MODEL_VERSION = "cost_model/v3"

# --- model constants (DESIGN.md §10 derives each; calibrated once
# against a measured 12-candidate slice at n=2^18, see the Spearman
# test in tests/test_cost_model.py) ------------------------------------
# One compare-unit (a w-word compare-exchange lane op) costed in HBM
# byte-equivalents: the VPU/HBM balance point of the §6 model.
OP_BYTE_EQUIV = 0.25
# Interconnect bytes are slower than HBM bytes (ICI/NVLink vs HBM BW).
COLLECTIVE_BYTE_WEIGHT = 4.0
# A scatter write costs ~this many gather-write equivalents (DESIGN.md
# §4: serialized RMW vs dense destination-indexed reads).
SCATTER_WRITE_FACTOR = 5.0
# Splitter ranking compares every element against all s_round-1
# splitters, one at a time in the partition kernel: per-element units
# per bucket.
RANK_UNITS_PER_BUCKET = 2.0
# Gather relocation finds each slot's source by expanding per-chunk
# bases over the slots (bucket_sort._chunk_values): one cumsum add and
# one offset add per slot, whatever the chunk count.
CHUNK_EXPAND_UNITS = 2.0
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
LANE = 128
SUBLANE = 8


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """The estimator's output, one number per cost channel.

    Attributes:
        hbm_bytes: HBM<->VMEM bytes moved across every pass of the plan.
        op_units: compare-unit count (compare-exchanges, splitter
            ranking, slot-source expansion) across every level.
        collective_bytes: per-device interconnect bytes (shard plans:
            deal + sample gather + c_pair-padded bucket exchange; 0 for
            single-device plans).
        vmem_peak_bytes: largest per-core VMEM working set of any level.
        align_penalty: multiplicative lane/sublane/VMEM-overflow
            penalty (>= 1.0).
        total: the scalar score the autotuner ranks by —
            ``(hbm + OP_BYTE_EQUIV*ops + COLLECTIVE_BYTE_WEIGHT*coll) *
            align_penalty``, in HBM byte-equivalents.
    """

    hbm_bytes: float
    op_units: float
    collective_bytes: float
    vmem_peak_bytes: int
    align_penalty: float
    total: float

    def as_dict(self) -> dict:
        """Plain-dict view (BENCH_sort.json rows record this)."""
        return dataclasses.asdict(self)


def _log2(x: int) -> int:
    return max(next_pow2(x).bit_length() - 1, 0)


def _stages(width: int) -> int:
    """Compare-exchange stages of the full bitonic network on
    ``next_pow2(width)`` elements: L(L+1)/2."""
    lg = _log2(width)
    return lg * (lg + 1) // 2


def local_sort_op_units(width: int, num_words: int) -> float:
    """Per-ELEMENT compare-unit cost of one bitonic sort of ``width``:
    ``stages(T) * (w+1)`` — data-oblivious (DESIGN.md §10)."""
    return _stages(width) * (num_words + 1)  # key words + payload tiebreak


def _node_vmem(node: LevelPlan, bpe: int) -> int:
    """Per-core VMEM working set of the node's tile sort: block_rows
    tiles of (words + payload), double-buffered.  0 on the xla path
    (no VMEM tiling to model)."""
    if node.block_rows is None:
        return 0
    width = node.tile if node.kind == "bucket" else next_pow2(node.lp)
    return 2 * node.block_rows * width * bpe


def _align_factor(node: LevelPlan) -> float:
    """Lane/sublane alignment penalty of one level (multiplicative)."""
    f = 1.0
    width = node.tile if node.kind == "bucket" else node.lp
    if next_pow2(width) % LANE != 0:
        f *= 1.25  # sub-lane tiles waste vector lanes
    if node.block_rows is not None and node.block_rows < SUBLANE:
        f *= 1.0 + 0.25 * (SUBLANE - node.block_rows) / SUBLANE
    return f


def _estimate_node(
    node: LevelPlan | None, nw: int
) -> tuple[float, float, int, float]:
    """(hbm_bytes, op_units, vmem_peak, align_penalty) of a level tree."""
    if node is None:
        return 0.0, 0.0, 0, 1.0
    bpe = 4 * (nw + 1)
    if node.kind == "direct":
        e = node.rows * node.lp
        hbm = 2.0 * e * bpe  # one read + one write
        ops = e * local_sort_op_units(node.lp, nw)
        return hbm, ops, _node_vmem(node, bpe), _align_factor(node)

    e = node.elements          # rows * lp entering the round
    eb = node.bucket_elements  # rows * s_round * cap after relocation
    # Step 2 local tile sort: one read + one write per element.
    hbm = 2.0 * e * bpe
    ops = e * local_sort_op_units(node.tile, nw)
    # Step 3 sampling is the tile sort's epilogue: no extra pass.
    # Steps 6-7 splitter partition: one read of the tiles.  Ranking
    # compares every element against all s_round-1 splitters, one at a
    # time in the partition kernel — LINEAR in the bucket count, which
    # is what prices the s knob.
    hbm += e * bpe
    ops += e * node.s_round * RANK_UNITS_PER_BUCKET * (nw + 1)
    # Step 8 relocation into the dense bucket array, then compaction.
    if node.relocation == "scatter":
        hbm += (e * SCATTER_WRITE_FACTOR + eb) * bpe
    else:
        hbm += (e + eb) * bpe
        ops += eb * CHUNK_EXPAND_UNITS  # source of every slot
    hbm += (eb + e) * bpe  # compaction gather back to dense rows
    vmem = _node_vmem(node, bpe)
    align = _align_factor(node)

    for child in (node.sample_plan, node.bucket_plan):
        ch, co, cv, ca = _estimate_node(child, nw)
        hbm += ch
        ops += co
        vmem = max(vmem, cv)
        align = max(align, ca)
    return hbm, ops, vmem, align


def _finish(
    hbm: float, ops: float, coll: float, vmem: int, align: float
) -> CostBreakdown:
    if vmem > VMEM_BUDGET_BYTES:
        align *= vmem / VMEM_BUDGET_BYTES  # spill: re-tile overhead
    total = (hbm + OP_BYTE_EQUIV * ops + COLLECTIVE_BYTE_WEIGHT * coll)
    return CostBreakdown(
        hbm_bytes=hbm,
        op_units=ops,
        collective_bytes=coll,
        vmem_peak_bytes=vmem,
        align_penalty=align,
        total=total * align,
    )


def _estimate_sort(plan: SortPlan) -> CostBreakdown:
    hbm, ops, vmem, align = _estimate_node(plan.root, plan.num_words)
    return _finish(hbm, ops, 0.0, vmem, align)


def _estimate_topk(plan: TopkPlan) -> CostBreakdown:
    nw, bpe = _topk_words(plan), 4 * (_topk_words(plan) + 1)
    if plan.length <= plan.direct_max:
        e = max(plan.rows, 1) * next_pow2(plan.length)
        ops = e * local_sort_op_units(plan.length, nw)
        return _finish(2.0 * e * bpe, ops, 0.0, 0, 1.0)
    e = plan.elements
    ec = plan.candidate_elements
    # tile sort + threshold pass + candidate pack + candidate sort
    hbm = 2.0 * e * bpe + e * bpe + (e + ec) * bpe + 2.0 * ec * bpe
    ops = e * local_sort_op_units(plan.tile, nw)
    ops += ec * local_sort_op_units(plan.ccap, nw)
    vmem = 0
    if plan.block_rows is not None:
        vmem = 2 * plan.block_rows * plan.tile * bpe
    return _finish(hbm, ops, 0.0, vmem, 1.0)


def _topk_words(plan: TopkPlan) -> int:
    # TopkPlan predates num_words as a field; one word is the common
    # case (topk encodes through the descending codec of the dtype).
    return getattr(plan, "num_words", 1)


def _estimate_shard(plan: ShardPlan) -> CostBreakdown:
    bpe = 4 * (plan.num_words + 1)
    hbm = ops = 0.0
    vmem, align = 0, 1.0
    for name in ("run_plan", "dealt_plan", "sample_plan", "bucket_plan"):
        sub: SortPlan = getattr(plan, name)
        b = _estimate_sort(sub)
        hbm += b.hbm_bytes
        ops += b.op_units
        vmem = max(vmem, b.vmem_peak_bytes)
        align = max(align, b.align_penalty)
    # Per-device interconnect volume: the deal all_to_all (n_pad), the
    # sample gather (d*s_loc), and the c_pair-PADDED bucket exchange —
    # padding waste (d*c_pair vs b_t) is charged at full price, which
    # is what lets the tuner trade pair_align against message size.
    coll = float(plan.collective_elements) * bpe
    if plan.c_pair % LANE != 0:
        align = max(align, 1.02)  # unaligned exchange messages
    return _finish(hbm, ops, coll, vmem, align)


def estimate(plan) -> CostBreakdown:
    """Analytic cost of a plan node — the autotuner's ranking score.

    Deterministic and pure: equal plans give equal breakdowns; cost is
    positive and monotone in n for fixed config geometry
    (property-tested in ``tests/test_cost_model.py``).

    Args:
        plan: a :class:`~repro.core.plan.SortPlan`,
            :class:`~repro.core.plan.TopkPlan` or
            :class:`~repro.core.plan.ShardPlan`.
    Returns:
        A :class:`CostBreakdown`; rank candidates by ``.total``.
    Raises:
        TypeError: for an unknown plan type.

    Example:
        >>> from repro.core.cost_model import estimate
        >>> from repro.core.plan import build_plan
        >>> from repro.core.sort_config import SortConfig
        >>> cfg = SortConfig(tile=256, s=16, direct_max=512, impl="xla")
        >>> small = estimate(build_plan(10_000, "int32", cfg))
        >>> big = estimate(build_plan(80_000, "int32", cfg))
        >>> (small.total > 0, big.total > small.total)
        (True, True)
    """
    if isinstance(plan, SortPlan):
        return _estimate_sort(plan)
    if isinstance(plan, TopkPlan):
        return _estimate_topk(plan)
    if isinstance(plan, ShardPlan):
        return _estimate_shard(plan)
    raise TypeError(
        f"estimate() takes a SortPlan, TopkPlan or ShardPlan, got "
        f"{type(plan).__name__}"
    )
