"""Configuration for the deterministic sample sort (GPU BUCKET SORT on TPU)."""

from __future__ import annotations

import dataclasses


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (trace-time int; next_pow2(0) == 1).

    Args:
        x: non-negative python int.
    Returns:
        The next power of two, as a python int.
    """
    p = 1
    while p < x:
        p *= 2
    return p


def round_up(x: int, mult: int) -> int:
    """Round x up to the nearest multiple of mult (trace-time ints).

    Args:
        x: non-negative python int.
        mult: positive python int.
    Returns:
        Smallest multiple of ``mult`` >= x, as a python int.
    """
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class SortConfig:
    """Knobs of Algorithm 1, adapted to TPU (layout: DESIGN.md §3-§4).

    tile: VMEM tile size T (paper: n/m = 2K items per SM shared memory).
        Power of two; multiple of 128 for lane alignment on real TPU.
    s: samples per tile == max buckets per round (paper: s = 64, Fig. 3).
    direct_max: arrays up to this length are bitonic-sorted directly in a
        single tile instead of going through a bucket round.  16384 by
        default: on a v5e a direct sort costs ~7.6 ps an element a
        network pass (4096 x 8192, 91 passes, in 23.2 ms), while a bucket
        round's relocation gathers cost 8.6 ns an element a word from
        VMEM and ~22 ns from HBM, so one direct sort of a row of up to
        16384 beats any bucket round over it.  This makes the 4-chip
        sort's 9344-long level-1 buckets (``c_pair``'s slack puts them
        just past 8192) go direct instead of through a third level.  Not
        higher: the unrolled network's code grows with T * log2(T)^2.
    impl: "pallas" (kernels) | "xla" (pure-jnp reference path) | None=auto.
    interpret: Pallas interpret mode (None = auto: True off-TPU).
    block_rows: tiles sorted per grid program in the row-blocked bitonic
        kernel.  None = auto-pick the largest power-of-two divisor of the
        tile count that fills the VMEM budget; an explicit value must be
        a power of two and acts as an UPPER BOUND — recursion levels
        whose tile count it does not divide clamp down to the largest
        power-of-two divisor (bitonic.effective_block_rows).
    relocation: "gather" (default) computes the SOURCE index of every
        destination slot and relocates/compacts with `take` — no
        scatters anywhere on the hot path (DESIGN.md §4).  "scatter" is
        the legacy destination-scatter formulation, kept as a reference
        for tests and benchmarks.
    descending: sort every key sequence in DESCENDING order.  A pure
        codec-level switch (DESIGN.md §6): keys are encoded with the
        order-reversing complement codec and the pipeline runs
        unchanged, so descending costs nothing and stays stable (equal
        keys keep their input order, matching
        ``jnp.sort(x, descending=True)``).  Ignored by ``topk`` (top-k
        is descending by definition) and by the ``*_with_stats`` bound
        introspection (bounds are order-agnostic).
    plan: how the static schedule (``core/plan.SortPlan``) is obtained
        (DESIGN.md §7):
          * ``"default"``  — built directly from this config;
          * ``"autotune"`` — the measured-best plan from
            ``core/autotune`` (persistent on-disk cache keyed by
            (shape, dtype, backend, cfg-fingerprint); the first miss
            runs the tuning search and records the winner);
          * any other string — a path to a plan file saved by
            ``autotune.save_plan``; its signature must match the call.
    row_pad: batch-aware block_rows auto-pick (DESIGN.md §5).  The
        batched entry points (``sort_batched``, ``segment_sort``) pad
        the row count up to a multiple of this power of two before
        entering the row-blocked kernels, so ``auto_block_rows`` always
        finds a divisor >= row_pad and every compare-exchange runs as a
        dense (>= 8-sublane) vector op even for odd batch sizes.  Only
        applied on the pallas path (it is pure overhead for the xla
        reference path); 1 disables.  Pad rows are all-pad (MAXU keys),
        obey the same capacity bound, and are sliced off on exit.
    check: runtime invariant checking (``core/guard.py``, DESIGN.md
        §11).  ``"off"`` (default) runs unguarded.  ``"bounds"``
        verifies the paper's deterministic capacity invariant on every
        bucket round of every call — no bucket fill exceeds the static
        ``cap`` (so relocation dropped nothing and ``within < cap``)
        and per-row fills conserve the padded row length.  ``"full"``
        adds output post-conditions: permutation checksums (payloads
        and key words, input vs output) and canonical-word sortedness.
        Violations raise ``guard.SortRuntimeError`` naming the plan
        node and invariant.  A call-time knob: it is EXCLUDED from the
        config fingerprint (``plan.config_fingerprint``), so checked
        and unchecked runs share plan-cache entries.
    """

    tile: int = 4096
    s: int = 64
    direct_max: int = 16384
    impl: str | None = None
    interpret: bool | None = None
    block_rows: int | None = None
    relocation: str = "gather"
    descending: bool = False
    row_pad: int = 8
    plan: str = "default"
    check: str = "off"

    def __post_init__(self):
        # Field-by-field validation with errors that NAME the offending
        # field — a bad knob must fail here, at construction, not as a
        # shape error deep inside a kernel spec.
        def _pow2(name, v, lo):
            if not (isinstance(v, int) and v >= lo and v & (v - 1) == 0):
                raise ValueError(
                    f"SortConfig.{name} must be a power of two >= {lo}, "
                    f"got {v!r}"
                )

        _pow2("tile", self.tile, 2)
        _pow2("s", self.s, 2)
        if self.s > self.tile:
            raise ValueError(
                f"SortConfig.s ({self.s}) must not exceed SortConfig.tile "
                f"({self.tile}): s samples are drawn per tile"
            )
        if self.tile % self.s != 0:
            raise ValueError(
                f"SortConfig.tile ({self.tile}) must be a multiple of "
                f"SortConfig.s ({self.s})"
            )
        if self.direct_max < self.tile:
            raise ValueError(
                f"SortConfig.direct_max ({self.direct_max}) must be >= "
                f"SortConfig.tile ({self.tile})"
            )
        if self.impl not in (None, "pallas", "xla"):
            raise ValueError(
                f'SortConfig.impl must be None, "pallas" or "xla", '
                f"got {self.impl!r}"
            )
        if self.block_rows is not None:
            _pow2("block_rows", self.block_rows, 1)
        if self.relocation not in ("gather", "scatter"):
            raise ValueError(
                f'SortConfig.relocation must be "gather" or "scatter", '
                f"got {self.relocation!r}"
            )
        _pow2("row_pad", self.row_pad, 1)
        if not (isinstance(self.plan, str) and self.plan):
            raise ValueError(
                'SortConfig.plan must be "default", "autotune", or a '
                f"plan-file path, got {self.plan!r}"
            )
        if self.check not in ("off", "bounds", "full"):
            raise ValueError(
                'SortConfig.check must be "off", "bounds" or "full", '
                f"got {self.check!r}"
            )


# Paper default: s = 64 (Fig. 3 sweep), 2K-item tiles on 16KB shared memory.
# TPU default: larger VMEM => larger tiles.
PAPER_CONFIG = SortConfig(tile=2048, s=64, direct_max=4096)
DEFAULT_CONFIG = SortConfig()
