"""Guarded execution: runtime invariant checks + degradation chains.

The paper's headline claim — deterministic regular sampling makes every
bucket capacity a *static guarantee* (``cap = round_up(lp/s_round +
lp/s, 128)``, DESIGN.md §2) — was previously verified only in tests.
This module makes it a production check (``SortConfig.check``) and
gives every fallible site in the engine an explicit recovery story
(DESIGN.md §11):

* ``check='bounds'`` re-verifies the capacity invariant on the actual
  bucket fills of every round: no bucket exceeds its deterministic
  capacity (so no relocated element was dropped and every ``within``
  offset is ``< cap``), and each row's fills sum to the padded row
  length (conservation).
* ``check='full'`` adds output post-conditions: a permutation checksum
  (per-row sum/xor of payloads and key words, input vs output — no
  element dropped or duplicated) and lexicographic sortedness of the
  canonical key words.

Violations raise :class:`SortRuntimeError` naming the plan node and the
invariant — never a silently corrupt result.

The degradation side: :func:`with_retries` (bounded exponential
backoff for transiently-fallible sites), and a bounded in-memory
:func:`degradation_log` fed by :func:`record_degradation` every time a
chain falls back to a slower-but-correct path, mirrored as a
:class:`DegradationWarning` so operators see it without polling.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings

import numpy as np

from repro.core.faults import FaultInjected

__all__ = [
    "CHECK_MODES",
    "SortRuntimeError",
    "RECOVERABLE",
    "DegradationWarning",
    "DegradationEvent",
    "record_degradation",
    "degradation_log",
    "clear_degradation_log",
    "with_retries",
    "validate_check",
    "bucket_spine",
    "plan_site",
    "check_bounds",
    "check_full",
    "check_topk",
]

#: Valid values of ``SortConfig.check``.
CHECK_MODES = ("off", "bounds", "full")


class SortRuntimeError(RuntimeError):
    """A runtime invariant of the sort engine was violated.

    Attributes:
        site: where — a plan-node path (e.g.
            ``"SortPlan(rows=1, length=65536, ...)/level0:bucket(...)"``)
            or a named subsystem site (e.g. ``"autotune.measure"``).
        invariant: which guarantee failed, as a short expression
            (e.g. ``"bucket_fill <= cap"``).
        detail: the measured numbers behind the violation.
    """

    def __init__(self, site: str, invariant: str, detail: str = ""):
        self.site = site
        self.invariant = invariant
        self.detail = detail
        msg = f"sort invariant violated at {site}: {invariant}"
        if detail:
            msg += f" — {detail}"
        super().__init__(msg)


#: The failures a degradation chain recovers from: an injected fault and
#: a violated runtime invariant.  Anything else (a Mosaic refusal, a
#: lowering error, an out-of-budget size) propagates, so a run never
#: leaves the kernels it was planned on in silence.
RECOVERABLE = (FaultInjected, SortRuntimeError)


class DegradationWarning(UserWarning):
    """A degradation chain fell back to a slower-but-correct path."""


@dataclasses.dataclass(frozen=True)
class DegradationEvent:
    """One recorded fallback/retry step of a degradation chain."""

    site: str
    action: str      # "retry" | "fallback"
    frm: str         # what failed
    to: str          # what the chain moved to
    error: str       # repr of the triggering exception


_LOG_MAX = 256
_log_lock = threading.Lock()
_log: list[DegradationEvent] = []


def record_degradation(site: str, action: str, frm: str, to: str,
                       error: BaseException | str) -> DegradationEvent:
    """Append an event to the bounded degradation log + warn once visibly."""
    err = error if isinstance(error, str) else f"{type(error).__name__}: {error}"
    ev = DegradationEvent(site=site, action=action, frm=frm, to=to, error=err)
    with _log_lock:
        if len(_log) >= _LOG_MAX:
            del _log[0]
        _log.append(ev)
    warnings.warn(
        f"degraded at {site}: {frm} -> {to} ({action}) after {err}",
        DegradationWarning,
        stacklevel=3,
    )
    return ev


def degradation_log() -> tuple[DegradationEvent, ...]:
    """Snapshot of recorded degradation events (most recent last)."""
    with _log_lock:
        return tuple(_log)


def clear_degradation_log() -> None:
    with _log_lock:
        _log.clear()


def with_retries(fn, *, site: str, attempts: int = 3, base_delay: float = 0.05,
                 max_delay: float = 2.0, retry_on=(Exception,),
                 sleep=time.sleep):
    """Call ``fn()`` with bounded retry + exponential backoff.

    Retries up to ``attempts`` total calls on ``retry_on`` exceptions,
    sleeping ``base_delay * 2**k`` (capped at ``max_delay``) between
    them and recording each retry in the degradation log.  The final
    failure re-raises the original exception — callers decide the next
    chain step (fallback, denylist, structured error).
    """
    if attempts < 1:
        raise ValueError(f"attempts must be >= 1, got {attempts}")
    delay = base_delay
    for attempt in range(attempts):
        try:
            return fn()
        except retry_on as e:
            if attempt == attempts - 1:
                raise
            record_degradation(
                site, "retry", f"attempt {attempt + 1}", f"attempt {attempt + 2}", e
            )
            sleep(min(delay, max_delay))
            delay *= 2


def validate_check(check: str) -> None:
    """Raise ValueError unless ``check`` is a valid checked-mode name."""
    if check not in CHECK_MODES:
        raise ValueError(
            f"check must be one of {CHECK_MODES}, got {check!r}")


# ----------------------------------------------------------------------
# Invariant checks (host-side post-conditions on concrete outputs)
# ----------------------------------------------------------------------


def plan_site(plan) -> str:
    """Stable human-readable identity of a plan for error sites."""
    return (f"SortPlan(rows={plan.rows}, length={plan.length}, "
            f"dtype={plan.dtype_name}, impl={plan.impl})")


def bucket_spine(plan) -> list:
    """The chain of bucket nodes the executor collects stats for, in
    stats order: the root's ``bucket_plan`` descent (sample recursions
    run with stats disabled — see ``_run_node``)."""
    nodes = []
    node = plan.root
    while node is not None and node.kind == "bucket":
        nodes.append(node)
        node = node.bucket_plan
    return nodes


def _node_site(plan, level: int, node) -> str:
    return (f"{plan_site(plan)}/level{level}:bucket(rows={node.rows}, "
            f"lp={node.lp}, s_round={node.s_round}, cap={node.cap})")


def check_bounds(plan, stats) -> None:
    """``check='bounds'``: verify the paper's capacity invariant on the
    measured bucket fills of every round.

    Per bucket round (stats entry, matched to the plan's bucket spine):

    * executor/plan capacity agreement (``capacity == node.cap``);
    * ``max bucket fill <= cap`` — the deterministic regular-sampling
      bound; a violation means relocation dropped elements and every
      in-bucket offset ``within`` is no longer ``< cap``;
    * per-row fills sum to the padded row length — conservation: every
      element (including pads) landed in exactly one bucket.

    Raises :class:`SortRuntimeError` naming the plan node + invariant.
    """
    spine = bucket_spine(plan)
    if len(stats) != len(spine):
        raise SortRuntimeError(
            plan_site(plan), "len(stats) == len(bucket_spine)",
            f"executor reported {len(stats)} bucket rounds, plan has "
            f"{len(spine)}")
    for level, (node, st) in enumerate(zip(spine, stats)):
        site = _node_site(plan, level, node)
        cap = int(st["capacity"])
        if cap != node.cap:
            raise SortRuntimeError(
                site, "capacity == plan.cap",
                f"executor ran with capacity {cap}, plan says {node.cap}")
        totals = np.asarray(st["totals"])
        max_fill = int(totals.max()) if totals.size else 0
        if max_fill > cap:
            raise SortRuntimeError(
                site, "bucket_fill <= cap",
                f"max bucket fill {max_fill} exceeds the deterministic "
                f"capacity {cap} (lp={int(st['level_len'])}, "
                f"s_round={int(st['s_round'])}): relocation dropped "
                f"elements / within >= cap")
        lp = int(st["level_len"])
        row_sums = totals.sum(axis=1)
        if totals.size and not (row_sums == lp).all():
            bad = int((row_sums != lp).sum())
            raise SortRuntimeError(
                site, "sum(bucket_fills) == lp",
                f"{bad} row(s) have bucket fills summing to "
                f"{int(row_sums.min())}..{int(row_sums.max())}, expected "
                f"{lp}: elements lost or duplicated in relocation")


def _row_checksums(kw, vals):
    """Per-row (sum, xor) over payloads + per-word sums — order-invariant
    fingerprints for the permutation check."""
    v = np.asarray(vals).astype(np.int64)
    sums = v.sum(axis=1)
    xors = np.bitwise_xor.reduce(v, axis=1)
    wsums = tuple(np.asarray(w).astype(np.uint64).sum(axis=1) for w in kw)
    return sums, xors, wsums


def check_full(plan, in_kw, in_vals, out_kw, out_vals) -> None:
    """``check='full'``: output post-conditions, after :func:`check_bounds`.

    * permutation checksum — per-row sum and xor of the int32 payloads
      and per-row sums of each key word match between input and output
      (order-invariant: catches dropped, duplicated, or corrupted
      elements that conserve bucket counts);
    * sortedness — adjacent canonical key words are lexicographically
      non-decreasing in every row.
    """
    site = f"{plan_site(plan)}/output"
    in_s, in_x, in_w = _row_checksums(in_kw, in_vals)
    out_s, out_x, out_w = _row_checksums(out_kw, out_vals)
    if not (np.array_equal(in_s, out_s) and np.array_equal(in_x, out_x)):
        bad = int(((in_s != out_s) | (in_x != out_x)).sum())
        raise SortRuntimeError(
            site, "payload permutation checksum",
            f"{bad} row(s): output payloads are not a permutation of the "
            f"input payloads (elements dropped or duplicated)")
    for wi, (a, b) in enumerate(zip(in_w, out_w)):
        if not np.array_equal(a, b):
            raise SortRuntimeError(
                site, "key-word permutation checksum",
                f"word {wi}: {int((a != b).sum())} row(s) changed key "
                f"content through the sort")
    ws = [np.asarray(w) for w in out_kw]
    if ws[0].shape[1] > 1:
        gt = np.zeros((ws[0].shape[0], ws[0].shape[1] - 1), dtype=bool)
        eq = np.ones_like(gt)
        for w in ws:
            a, b = w[:, :-1], w[:, 1:]
            gt |= eq & (a > b)
            eq &= a == b
        if gt.any():
            raise SortRuntimeError(
                site, "output sortedness",
                f"{int(gt.sum())} adjacent inversion(s) in the canonical "
                f"key words")


def check_topk(x, vals, idx, k: int, check: str, codec) -> None:
    """Checked-mode post-conditions for top-k (``core/partial_sort``).

    ``'bounds'``: indices lie in the candidate range.  ``'full'`` adds:
    per-row index uniqueness, bitwise ``vals == x[idx]`` agreement, and
    descending sortedness of ``vals`` under the dtype's total order
    (via the descending key codec).
    """
    xs = np.asarray(x)
    if xs.ndim == 1:
        xs = xs[None, :]
    v = np.asarray(vals).reshape(-1, k)
    ix = np.asarray(idx).reshape(-1, k)
    site = f"topk(rows={xs.shape[0]}, n={xs.shape[1]}, k={k})"
    n = xs.shape[1]
    if ((ix < 0) | (ix >= n)).any():
        raise SortRuntimeError(
            site, "0 <= idx < n",
            f"indices outside [0, {n}): "
            f"min={int(ix.min())}, max={int(ix.max())}")
    if check != "full":
        return
    srt = np.sort(ix, axis=1)
    if (srt[:, 1:] == srt[:, :-1]).any():
        raise SortRuntimeError(
            site, "idx unique per row", "duplicate indices returned")
    gathered = np.take_along_axis(xs, ix, axis=1)
    # bitwise agreement (NaN-safe): compare raw bytes, not values
    if gathered.view(np.uint8).tobytes() != v.view(np.uint8).tobytes():
        raise SortRuntimeError(
            site, "vals == x[idx] (bitwise)",
            "returned values disagree with the gathered indices")
    import jax.numpy as jnp  # deferred: keep guard importable early

    words = [np.asarray(w) for w in codec.encode(jnp.asarray(v))]
    if k > 1:
        gt = np.zeros((v.shape[0], k - 1), dtype=bool)
        eq = np.ones_like(gt)
        for w in words:
            a, b = w[:, :-1], w[:, 1:]
            gt |= eq & (a > b)
            eq &= a == b
        if gt.any():
            raise SortRuntimeError(
                site, "vals descending",
                f"{int(gt.sum())} adjacent inversion(s) in top-k values")
