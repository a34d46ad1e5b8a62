"""Plan autotuner: analytically pruned, measured search over the
sort-plan space, with a persistent on-disk plan cache.

The planner (``core/plan.py``) makes the schedule explicit data; this
module picks the BEST schedule for a signature.  The knobs that
dominate throughput (``tile``, ``s``, ``block_rows``, the relocation
mode) must be tuned per architecture and input size (Leischner et al.;
Casanova et al.), and the deterministic pipeline makes every candidate
a pure config swap.

Search protocol (DESIGN.md §10): every candidate in the space is
scored by the analytic cost model (``core/cost_model.estimate``), and
only the ``measure_budget`` cheapest-predicted candidates are timed on
real executions — the base config (candidate 0) is always among them,
so the winner is never slower than the default schedule.
``measure_budget=None`` restores the exhaustive measured search.
Predicted and measured cost for EVERY candidate are recorded on
:class:`AutotuneResult` so model error is observable (the autotune
benchmark suite writes it into ``BENCH_sort.json``).

Cross-shape transfer: on a store miss at a new signature,
:func:`plan_for` seeds the measured set from the cached winner at the
NEAREST signature (same dtype/order/backend, nearest log2 n, then
log2 rows) and caps the budget at 2 measurements (base + transferred
winner) — warm workloads converge without a fresh search.

Cache semantics (DESIGN.md §7): plans are cached under
``(shape, dtype, backend, cfg-fingerprint)`` — the signature of the
*requesting* config (fingerprint over every field except ``plan``).  A
hit deserializes to a plan EQUAL to the one saved (dataclass equality,
tested), so the jit static-argument cache also hits: repeated
same-signature ``sort()`` calls after a plan-cache hit compile zero new
executables.

The cache lives at ``$REPRO_SORT_PLAN_CACHE`` (default
``~/.cache/repro_sort/plans.json``); writes are atomic
(tmp + ``os.replace``).  ``SortConfig(plan="autotune")`` routes every
public entry point through :func:`plan_for`; benchmarks record
best-found plans and their speedups via ``benchmarks/run.py --suite
autotune``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings

import jax
import numpy as np

from repro.core import cost_model, faults, guard
from repro.core.plan import (
    ShardPlan,
    SortPlan,
    build_plan,
    build_shard_plan,
    plan_from_dict,
    plan_to_dict,
    shard_plan_from_dict,
    shard_plan_to_dict,
)
from repro.core.sort_config import SortConfig, next_pow2

_CACHE_ENV = "REPRO_SORT_PLAN_CACHE"
_STORE_SCHEMA = "sort_plan_cache/v1"

# Process-local memo so a warm signature never re-reads the disk store.
_MEMO: dict[str, SortPlan] = {}
# Memo for explicit plan FILES (SortConfig(plan=<path>)), keyed by
# (path, mtime_ns) so the hot serving path pays one stat() per call
# instead of open+parse+tree-rebuild, while an updated file still
# reloads.
_FILE_MEMO: dict[tuple, SortPlan] = {}


def cache_path() -> str:
    """Resolved plan-cache location (env override, else XDG-ish default)."""
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro_sort", "plans.json"
    )


def cache_key(plan: SortPlan) -> str:
    """The persistent-cache key: every component of the plan signature —
    (rows, length) shape, dtype+order, resolved impl/interpret/backend,
    and the requesting config's fingerprint."""
    return "|".join(str(x) for x in plan.signature())


def _fresh_store() -> dict:
    return {"schema": _STORE_SCHEMA, "plans": {}, "denylist": {}}


def _quarantine_store(path: str, err: Exception) -> None:
    """Corrupt store recovery (DESIGN.md §11): atomically rename the
    unparseable file to ``<path>.corrupt-<pid>`` — NEVER overwrite it
    in place (the evidence survives, and the next save rebuilds a clean
    store) — and warn once."""
    qpath = f"{path}.corrupt-{os.getpid()}"
    try:
        os.replace(path, qpath)
    except OSError:
        qpath = "<rename failed; left in place>"
    warnings.warn(
        f"plan cache {path} is corrupt ({type(err).__name__}: {err}); "
        f"quarantined to {qpath} and rebuilding a clean store",
        guard.DegradationWarning,
        stacklevel=3,
    )


def _load_store(path: str) -> dict:
    """Read the JSON plan store; degrade to an empty store on any
    failure (degradation chain: a broken cache must never break a
    sort).  Corrupt JSON is quarantined (atomic rename) so the bytes
    survive for inspection; unreadable files (I/O errors, injected
    ``cache.load`` faults) warn and fall back without quarantine."""
    try:
        faults.check("cache.load")
        with open(path) as f:
            store = json.load(f)
    except FileNotFoundError:
        return _fresh_store()
    except json.JSONDecodeError as e:
        _quarantine_store(path, e)
        return _fresh_store()
    except (faults.FaultInjected, OSError) as e:
        warnings.warn(
            f"plan cache {path} unreadable ({type(e).__name__}: {e}); "
            f"continuing with an empty store",
            guard.DegradationWarning,
            stacklevel=2,
        )
        return _fresh_store()
    if store.get("schema") != _STORE_SCHEMA:
        return _fresh_store()
    store.setdefault("denylist", {})
    return store


def _save_store(path: str, store: dict) -> None:
    faults.check("cache.save")
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(store, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def _persist_store(path: str, store: dict) -> None:
    """Best-effort store persist for the tune-and-cache paths: a failed
    save (I/O error, injected ``cache.save`` fault) degrades to
    memo-only caching — the tuned plan is still returned and memoized,
    only the cross-process record is lost (recorded + warned)."""
    try:
        _save_store(path, store)
    except (faults.FaultInjected, OSError) as e:
        guard.record_degradation(
            "cache.save", "fallback", f"persist to {path}",
            "process-memo only (store not written)", e)


def save_plan(plan: SortPlan, path: str, *, meta: dict | None = None) -> None:
    """Write one plan to ``path`` as a standalone plan file (the format
    ``SortConfig(plan=<path>)`` and :func:`load_plan` read)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    payload = plan_to_dict(plan)
    if meta:
        payload["meta"] = meta
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def load_plan(
    path: str,
    *,
    length: int | None = None,
    dtype=None,
    cfg: SortConfig | None = None,
    rows: int = 1,
    pad_rows: bool = False,
) -> SortPlan:
    """Read a plan file saved by :func:`save_plan`.

    When a call signature is supplied (``length``/``dtype``/``rows``,
    as ``resolve_plan`` does for ``SortConfig(plan=<path>)``), the
    file's plan must match it — shape, dtype and order are load-bearing
    (ValueError otherwise).  The plan's tunables (tile, s, ...) override
    the requesting cfg's: that is the point of carrying a tuned plan.
    """
    import jax.numpy as jnp

    fkey = (path, os.stat(path).st_mtime_ns)
    plan = _FILE_MEMO.get(fkey)
    if plan is None:
        with open(path) as f:
            d = json.load(f)
        d.pop("meta", None)
        plan = plan_from_dict(d)
        _FILE_MEMO[fkey] = plan
    if length is not None:
        want = (rows, length, jnp.dtype(dtype).name,
                cfg.descending if cfg else plan.descending)
        got = (plan.rows, plan.length, plan.dtype_name, plan.descending)
        if want != got:
            raise ValueError(
                f"plan file {path} was built for (rows, length, dtype, "
                f"descending)={got}, call needs {want}"
            )
    return plan


# ----------------------------------------------------------------------
# Candidate space
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the search space (a full SortConfig swap)."""

    cfg: SortConfig
    label: str


def candidate_space(
    cfg: SortConfig, length: int, *, max_trials: int = 16
) -> list[Candidate]:
    """Deterministic, ordered candidate list around ``cfg``.

    The BASE config is always candidate 0, so the measured winner is by
    construction at least as fast as the default plan.  The space
    crosses tile × s × block_rows × relocation, nearest neighbours
    first, deduplicated, truncated to ``max_trials``.
    """
    tiles = [cfg.tile, cfg.tile * 2, max(cfg.tile // 2, 128), cfg.tile * 4]
    svals = [cfg.s, cfg.s * 2, max(cfg.s // 2, 2), cfg.s * 4]
    brs = [cfg.block_rows, 8, 32] if cfg.block_rows is None else [
        cfg.block_rows, None, 8
    ]
    relocs = ["gather", "scatter"]
    if cfg.relocation != "gather":
        relocs.reverse()

    seen: set[SortConfig] = set()
    out: list[Candidate] = []

    def _add(**kw):
        if len(out) >= max_trials:
            return
        t = kw.get("tile", cfg.tile)
        s = kw.get("s", cfg.s)
        if s > t or t % s != 0 or t > max(next_pow2(length), 128):
            return
        # Only grow direct_max when a LARGER tile needs it to stay a
        # valid config — candidate 0 (no overrides) must be the
        # requesting config itself, bit for bit, or default_us/speedup
        # would measure the wrong schedule.
        if t > cfg.direct_max:
            kw.setdefault("direct_max", 2 * t)
        kw.setdefault("plan", "default")
        try:
            cand = dataclasses.replace(cfg, **kw)
        except ValueError:
            return
        if cand in seen:
            return
        seen.add(cand)
        bits = ",".join(f"{k}={v}" for k, v in sorted(kw.items())
                        if k not in ("direct_max", "plan"))
        out.append(Candidate(cfg=cand, label=bits or "base"))

    _add()  # the base config: candidate 0, the speedup reference
    for t in tiles:
        _add(tile=t)
    for s in svals:
        _add(s=s)
    for t in tiles[:2]:
        for s in svals[:2]:
            _add(tile=t, s=s)
    for br in brs:
        _add(block_rows=br)
    for rl in relocs:
        _add(relocation=rl)
    return out


# ----------------------------------------------------------------------
# Measurement
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrialResult:
    label: str
    us_per_call: float


@dataclasses.dataclass(frozen=True)
class CandidateScore:
    """Predicted (and, when measured, observed) cost of one candidate.

    One of these exists for EVERY candidate in the search space, not
    just the measured ones — model error (predicted rank vs measured
    rank) is observable from a single :class:`AutotuneResult`.

    Attributes:
        index: position in the candidate space (0 = base config).
        label: the candidate's config-delta label.
        predicted: analytic cost (HBM byte-equivalents,
            ``cost_model.estimate(...).total``).
        us_per_call: median measured micros, or None if the candidate
            was pruned by the measure budget (or failed to run).
    """

    index: int
    label: str
    predicted: float
    us_per_call: float | None = None


@dataclasses.dataclass(frozen=True)
class AutotuneResult:
    """Outcome of one tuning run.

    Attributes:
        best_plan: the measured-fastest candidate's plan.
        best_us / default_us: median wall micros of the winner and of
            candidate 0 (the requesting config) — ``speedup`` is their
            ratio, >= 1.0 up to timer noise since the default is in the
            space.
        trials: every MEASURED candidate, in candidate order (the base
            config is always measured, so ``trials[0]`` is "base").
        candidates: predicted vs measured for every candidate in the
            space, candidate order (measured ones carry
            ``us_per_call``).
        measure_budget: the budget the run used (None = exhaustive).
        cost_model_version: ``cost_model.COST_MODEL_VERSION`` at tune
            time (persisted; a bump invalidates cached records).
        failed: (label, error) for every candidate that exhausted the
            measurement retry chain this run — ``plan_for`` persists
            these into the store's per-signature denylist.
        skipped: labels excluded up front by the caller's denylist.
    """

    best_plan: SortPlan
    best_label: str
    best_us: float
    default_us: float
    trials: tuple[TrialResult, ...]
    candidates: tuple[CandidateScore, ...] = ()
    measure_budget: int | None = None
    cost_model_version: str = cost_model.COST_MODEL_VERSION
    failed: tuple[tuple[str, str], ...] = ()
    skipped: tuple[str, ...] = ()

    @property
    def speedup(self) -> float:
        return self.default_us / self.best_us if self.best_us else 1.0


def _validate_budget(measure_budget) -> None:
    if measure_budget is None:
        return
    if not isinstance(measure_budget, int) or isinstance(
        measure_budget, bool
    ) or measure_budget < 1:
        raise ValueError(
            f"measure_budget must be an int >= 1 (candidates to time) or "
            f"None for the exhaustive measured search, got "
            f"{measure_budget!r}"
        )


def _select_measured(
    predicted: list[float],
    measure_budget: int | None,
    mandatory: list[int],
) -> list[int]:
    """Indices to time: the mandatory set (base config, transfer
    seeds), then cheapest-predicted-first up to the budget.  Ties on
    predicted cost break deterministically toward the lower candidate
    index, so equal-cost reruns measure the same set."""
    if measure_budget is None:
        return list(range(len(predicted)))
    chosen = list(dict.fromkeys(mandatory))
    ranked = sorted(range(len(predicted)), key=lambda i: (predicted[i], i))
    for i in ranked:
        if len(chosen) >= measure_budget:
            break
        if i not in chosen:
            chosen.append(i)
    return sorted(chosen)


def _measure(fn, x, *, repeats: int, warmup: int = 1) -> float:
    faults.check("autotune.measure")
    for _ in range(warmup):
        jax.block_until_ready(fn(x))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


# Retry policy for candidate measurement (DESIGN.md §11): transient
# launch/measurement failures get _MEASURE_ATTEMPTS total tries with
# exponential backoff from _MEASURE_BASE_DELAY seconds; a candidate
# that exhausts them is reported on ``AutotuneResult.failed`` and
# (via plan_for/shard_plan_for) lands in the store's per-signature
# denylist so later tuning runs skip it outright.
_MEASURE_ATTEMPTS = 3
_MEASURE_BASE_DELAY = 0.02


def _measure_candidate(fn, x, label: str, *, repeats: int,
                       warmup: int = 1) -> tuple[float | None, str | None]:
    """One candidate's guarded measurement: bounded retry with
    exponential backoff, then (None, error-string) — the caller
    denylists, never silently swallows."""
    try:
        us = guard.with_retries(
            lambda: _measure(fn, x, repeats=repeats, warmup=warmup),
            site=f"autotune.measure[{label}]",
            attempts=_MEASURE_ATTEMPTS,
            base_delay=_MEASURE_BASE_DELAY,
        )
        return us, None
    except Exception as e:  # terminal after retries: report, denylist
        warnings.warn(
            f"autotune candidate {label!r} failed to measure after "
            f"{_MEASURE_ATTEMPTS} attempts ({type(e).__name__}: {e}); "
            f"excluded from this run and denylisted for the signature",
            guard.DegradationWarning,
            stacklevel=2,
        )
        return None, f"{type(e).__name__}: {e}"


def _sample_input(length: int, dtype, rows: int, seed: int):
    """Deterministic representative data for measurement (seeded uniform
    keys of the target dtype), shared by the single-device and
    distributed tuners."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    npdt = np.dtype(jnp.dtype(dtype).name)
    shape = (length,) if rows == 1 else (rows, length)
    if npdt.kind == "f":
        x = rng.standard_normal(shape).astype(npdt)
    elif npdt.kind == "b":
        x = rng.integers(0, 2, shape).astype(npdt)
    elif npdt.kind == "u":
        x = rng.integers(0, np.iinfo(npdt).max, shape, dtype=np.uint64).astype(npdt)
    else:
        info = np.iinfo(npdt)
        x = rng.integers(info.min, info.max, shape, dtype=np.int64).astype(npdt)
    return jnp.asarray(x)


def autotune(
    length: int,
    dtype,
    cfg: SortConfig,
    *,
    rows: int = 1,
    pad_rows: bool = False,
    max_trials: int = 16,
    repeats: int = 3,
    warmup: int = 1,
    seed: int = 0,
    measure_budget: int | None = 5,
    seed_cfgs: tuple[SortConfig, ...] = (),
    denylist: frozenset[str] = frozenset(),
) -> AutotuneResult:
    """Budgeted search: score every candidate's plan with the analytic
    cost model, time only the ``measure_budget`` cheapest-predicted
    candidates (base config always included) on representative data,
    return the measured winner.

    Args:
        measure_budget: candidates to actually time (None =
            exhaustive).  ValueError if not a positive int or None.
        seed_cfgs: extra configs appended to the candidate space and
            FORCED into the measured set (the cross-shape transfer
            path of :func:`plan_for` passes the nearest cached
            winner's config here).
        denylist: candidate labels never to measure (persisted failures
            from earlier runs at this signature — see :func:`plan_for`).

    Data is deterministic (seeded uniform keys of the target dtype), so
    back-to-back runs rank candidates consistently up to timer noise;
    ties on predicted cost break toward the lower candidate index.
    Candidates whose measurement exhausts the retry chain are reported
    on ``result.failed`` (and excluded from the winner), never silently
    swallowed.  Raises :class:`guard.SortRuntimeError` when NO candidate
    measures successfully.
    """
    from repro.core import bucket_sort

    _validate_budget(measure_budget)
    xj = _sample_input(length, dtype, rows, seed)

    cands = candidate_space(cfg, length, max_trials=max_trials)
    mandatory = [0]
    seen_cfgs = {c.cfg for c in cands}
    for sc in seed_cfgs:
        sc = dataclasses.replace(sc, plan="default")
        if sc in seen_cfgs:
            mandatory.append(
                next(i for i, c in enumerate(cands) if c.cfg == sc)
            )
            continue
        seen_cfgs.add(sc)
        cands.append(Candidate(cfg=sc, label="transfer"))
        mandatory.append(len(cands) - 1)

    plans: list[SortPlan] = []
    predicted: list[float] = []
    for cand in cands:
        plan = build_plan(
            length, dtype, cand.cfg, rows=rows, pad_rows=pad_rows
        )
        plans.append(plan)
        try:
            predicted.append(cost_model.estimate(plan).total)
        except Exception as e:  # score as worst — never silently
            warnings.warn(
                f"cost model failed for candidate {cand.label!r} "
                f"({type(e).__name__}: {e}); scoring as +inf",
                guard.DegradationWarning, stacklevel=2)
            predicted.append(float("inf"))

    measured = set(_select_measured(predicted, measure_budget, mandatory))
    skipped = tuple(
        c.label for i, c in enumerate(cands)
        if i in measured and c.label in denylist
    )
    measured -= {i for i, c in enumerate(cands) if c.label in denylist}
    trials: list[TrialResult] = []
    scores: list[CandidateScore] = []
    failed: list[tuple[str, str]] = []
    best_plan, best_label = None, ""
    best_us, default_us = float("inf"), float("inf")
    for i, cand in enumerate(cands):
        us = None
        if i in measured:
            us, err = _measure_candidate(
                lambda a, p=plans[i]: bucket_sort.sort_planned(a, p),
                xj, cand.label, repeats=repeats, warmup=warmup,
            )
            if err is not None:
                failed.append((cand.label, err))
        scores.append(CandidateScore(
            index=i, label=cand.label, predicted=predicted[i],
            us_per_call=us,
        ))
        if us is None:
            continue
        trials.append(TrialResult(label=cand.label, us_per_call=us))
        if i == 0:
            default_us = us
        if us < best_us:
            best_plan, best_label, best_us = plans[i], cand.label, us
    if best_plan is None:
        raise guard.SortRuntimeError(
            "autotune.measure", "at least one candidate measured",
            f"all {len(measured)} measured candidate(s) failed "
            f"({len(skipped)} denylisted) for length={length} rows={rows}")
    return AutotuneResult(
        best_plan=best_plan,
        best_label=best_label,
        best_us=best_us,
        default_us=default_us,
        trials=tuple(trials),
        candidates=tuple(scores),
        measure_budget=measure_budget,
        failed=tuple(failed),
        skipped=skipped,
    )


# ----------------------------------------------------------------------
# The cfg.plan == "autotune" entry: cache-or-tune (with cross-shape
# transfer seeding on a miss)
# ----------------------------------------------------------------------


def _record_is_current(rec: dict | None) -> bool:
    """A persisted record is usable only if it was tuned under the
    CURRENT cost-model version — a version bump means the analytic
    pruning that picked the winner is no longer trusted, so the record
    is a clean miss that re-tunes (mirrors the shard_plan/v1
    schema-bump behavior)."""
    return (
        rec is not None
        and rec.get("cost_model") == cost_model.COST_MODEL_VERSION
    )


def _cfg_from_winner_plan(plan: SortPlan, cfg: SortConfig):
    """Reconstruct a tunable config from a cached winner plan's root
    level, applied over the requesting ``cfg`` (the transfer seed).
    None when the winner's geometry can't express a valid config."""
    node = plan.root
    kw: dict = dict(
        plan="default",
        block_rows=node.block_rows,
    )
    if node.kind == "bucket":
        kw.update(
            tile=node.tile,
            s=node.s,
            relocation=node.relocation,
        )
        if node.tile > cfg.direct_max:
            kw["direct_max"] = 2 * node.tile
    try:
        return dataclasses.replace(cfg, **kw)
    except ValueError:
        return None


def _nearest_plan_record(
    store: dict, base: SortPlan, key: str
) -> tuple[SortPlan, str] | None:
    """The cached winner at the signature NEAREST to ``base``: same
    dtype/order/backend triple required, then prefer the same config
    fingerprint, then the closest log2 length, then log2 rows (ties
    break on the store key, so the choice is deterministic)."""
    want = (base.dtype_name, str(base.descending), base.impl,
            str(base.interpret), base.backend)
    best = None
    for k, rec in store["plans"].items():
        if k == key or k.startswith("shard|"):
            continue
        if not _record_is_current(rec):
            continue
        parts = k.split("|")
        if len(parts) != 8 or tuple(parts[2:7]) != want:
            continue
        try:
            rows_k, length_k = int(parts[0]), int(parts[1])
            plan = plan_from_dict(rec["plan"])
        except (ValueError, TypeError, KeyError):
            continue
        dist = (
            0 if parts[7] == base.cfg_fingerprint else 1,
            abs(np.log2(max(length_k, 1)) - np.log2(max(base.length, 1))),
            abs(np.log2(max(rows_k, 1)) - np.log2(max(base.rows, 1))),
            k,
        )
        if best is None or dist < best[0]:
            best = (dist, plan, k)
    return (best[1], best[2]) if best else None


def plan_for(
    length: int,
    dtype,
    cfg: SortConfig,
    *,
    rows: int = 1,
    pad_rows: bool = False,
    path: str | None = None,
    max_trials: int = 16,
    repeats: int = 3,
    measure_budget: int | None = 5,
    transfer: bool = True,
) -> SortPlan:
    """Cached-or-tuned plan for a signature (the ``plan="autotune"``
    path).

    Lookup order: process memo -> on-disk store -> run
    :func:`autotune` and persist the winner.  The reloaded plan is
    EQUAL to the saved one, so jit's static-argument cache hits too —
    a plan-cache hit performs zero retraces (tested).

    Persisted records carry the cost-model version; a record tuned
    under a stale version is a clean miss that re-tunes.  On a miss
    with ``transfer=True`` (default), the measured set is seeded from
    the cached winner at the nearest signature and the budget drops to
    ≤2 measurements (base + transferred winner).
    """
    base = build_plan(length, dtype, cfg, rows=rows, pad_rows=pad_rows)
    key = cache_key(base)
    if key in _MEMO:
        return _MEMO[key]
    path = path or cache_path()
    store = _load_store(path)
    rec = store["plans"].get(key)
    if rec is not None and _record_is_current(rec):
        try:
            plan = plan_from_dict(rec["plan"])
        except (ValueError, TypeError):
            # A record from an older plan schema (e.g. sort_plan/v3):
            # treat as a clean miss — re-tune below and overwrite,
            # never misread a stale plan.
            pass
        else:
            _MEMO[key] = plan
            return plan

    seed_cfgs: tuple[SortConfig, ...] = ()
    budget = measure_budget
    transfer_from = None
    if transfer and measure_budget is not None:
        near = _nearest_plan_record(store, base, key)
        if near is not None:
            seed_cfg = _cfg_from_winner_plan(near[0], cfg)
            if seed_cfg is not None:
                seed_cfgs = (seed_cfg,)
                budget = min(measure_budget, 2)
                transfer_from = near[1]

    deny = store.get("denylist", {}).get(key, {})
    result = autotune(
        length, dtype, cfg, rows=rows, pad_rows=pad_rows,
        max_trials=max_trials, repeats=repeats,
        measure_budget=budget, seed_cfgs=seed_cfgs,
        denylist=frozenset(deny),
    )
    if result.failed:
        store.setdefault("denylist", {}).setdefault(key, {}).update(
            dict(result.failed))
    store["plans"][key] = dict(
        plan=plan_to_dict(result.best_plan),
        best_us=round(result.best_us, 1),
        default_us=round(result.default_us, 1),
        speedup=round(result.speedup, 3),
        cost_model=result.cost_model_version,
        measure_budget=result.measure_budget,
        measured=sum(
            1 for c in result.candidates if c.us_per_call is not None
        ),
        candidates=len(result.candidates),
        **({"transfer_from": transfer_from} if transfer_from else {}),
    )
    _persist_store(path, store)
    _MEMO[key] = result.best_plan
    return result.best_plan


# ----------------------------------------------------------------------
# Distributed candidate axis: oversample x exchange tiling, persisted
# in the same JSON store keyed by mesh signature
# ----------------------------------------------------------------------

# Process-local memo for tuned shard plans (same role as _MEMO).
_SHARD_MEMO: dict[str, ShardPlan] = {}


def shard_cache_key(plan: ShardPlan) -> str:
    """The persistent-cache key of a distributed plan: the ``shard|``
    namespace plus every component of :meth:`ShardPlan.signature` —
    mesh signature (axis names + D), shard shape, dtype+order, the
    requested oversample/pair_align, the resolved backend triple, and
    the requesting config's fingerprint.  Lives in the same JSON store
    as the single-device keys (disjoint namespaces)."""
    return "shard|" + "|".join(str(x) for x in plan.signature())


@dataclasses.dataclass(frozen=True)
class ShardCandidate:
    """One point of the distributed search space."""

    cfg: SortConfig
    oversample: int
    pair_align: int
    label: str


def shard_candidate_space(
    cfg: SortConfig,
    *,
    oversample: int = 8,
    pair_align: int = 8,
    max_trials: int = 8,
) -> list[ShardCandidate]:
    """Deterministic, ordered distributed candidate list.

    The BASE (requested cfg/oversample/pair_align) is candidate 0, so
    the measured winner is never slower than the default schedule.  The
    axes, nearest first: the oversample factor c (trades sample volume
    against the 1/c slack in ``c_pair``) and the exchange tiling
    ``pair_align`` (lane alignment of the per-pair all_to_all
    capacity).
    """
    seen: set[tuple] = set()
    out: list[ShardCandidate] = []

    cand_cfg = dataclasses.replace(cfg, plan="default")

    def _add(label: str, *, osamp=None, palign=None):
        if len(out) >= max_trials:
            return
        o = oversample if osamp is None else osamp
        pa = pair_align if palign is None else palign
        if o < 1 or o & (o - 1) or pa < 8 or pa & (pa - 1):
            return
        key = (cand_cfg, o, pa)
        if key in seen:
            return
        seen.add(key)
        out.append(ShardCandidate(
            cfg=cand_cfg, oversample=o, pair_align=pa, label=label
        ))

    _add("base")
    for o in (oversample * 2, max(oversample // 2, 1), oversample * 4):
        _add(f"oversample={o}", osamp=o)
    for pa in (128, 256):
        _add(f"pair_align={pa}", palign=pa)
    return out


def autotune_shard(
    mesh,
    axis,
    n_global: int,
    dtype,
    cfg: SortConfig,
    *,
    oversample: int = 8,
    pair_align: int = 8,
    max_trials: int = 8,
    repeats: int = 2,
    warmup: int = 1,
    seed: int = 0,
    measure_budget: int | None = 5,
    seed_candidates: tuple[ShardCandidate, ...] = (),
    denylist: frozenset[str] = frozenset(),
) -> AutotuneResult:
    """Budgeted search over the distributed schedule space: score each
    candidate's :class:`ShardPlan` analytically (including the
    ``c_pair``-padded collective volume), time only the
    ``measure_budget`` cheapest-predicted candidates (base always
    included) on the real jit'd distributed executor over ``mesh``,
    return the measured winner.

    Needs a mesh whose ``axis`` spans >= 2 devices (forced-host meshes
    in tests/benchmarks); data is deterministic so back-to-back runs
    rank candidates consistently up to timer noise.
    """
    from repro.core import distributed_sort

    _validate_budget(measure_budget)
    axt = (axis,) if isinstance(axis, str) else tuple(axis)
    d = 1
    for a in axt:
        d *= mesh.shape[a]
    xj = _sample_input(n_global, dtype, 1, seed)

    space = shard_candidate_space(
        cfg, oversample=oversample, pair_align=pair_align,
        max_trials=max_trials,
    )
    mandatory = [0]
    seen = {(c.cfg, c.oversample, c.pair_align) for c in space}
    for sc in seed_candidates:
        k = (sc.cfg, sc.oversample, sc.pair_align)
        if k in seen:
            mandatory.append(next(
                i for i, c in enumerate(space)
                if (c.cfg, c.oversample, c.pair_align) == k
            ))
            continue
        seen.add(k)
        space.append(sc)
        mandatory.append(len(space) - 1)

    plans: list[ShardPlan] = []
    predicted: list[float] = []
    for cand in space:
        plan = build_shard_plan(
            axt, d, n_global // d, dtype, cand.cfg,
            oversample=cand.oversample, pair_align=cand.pair_align,
        )
        plans.append(plan)
        try:
            predicted.append(cost_model.estimate(plan).total)
        except Exception as e:  # score as worst — never silently
            warnings.warn(
                f"cost model failed for distributed candidate "
                f"{cand.label!r} ({type(e).__name__}: {e}); scoring as +inf",
                guard.DegradationWarning, stacklevel=2)
            predicted.append(float("inf"))

    measured = set(_select_measured(predicted, measure_budget, mandatory))
    skipped = tuple(
        c.label for i, c in enumerate(space)
        if i in measured and c.label in denylist
    )
    measured -= {i for i, c in enumerate(space) if c.label in denylist}
    trials: list[TrialResult] = []
    scores: list[CandidateScore] = []
    failed: list[tuple[str, str]] = []
    best_plan, best_label = None, ""
    best_us, default_us = float("inf"), float("inf")
    for i, cand in enumerate(space):
        us = None
        if i in measured:
            us, err = _measure_candidate(
                lambda a, p=plans[i]: distributed_sort._sharded_argsort(
                    a, mesh, p
                ),
                xj, cand.label, repeats=repeats, warmup=warmup,
            )
            if err is not None:
                failed.append((cand.label, err))
        scores.append(CandidateScore(
            index=i, label=cand.label, predicted=predicted[i],
            us_per_call=us,
        ))
        if us is None:
            continue
        trials.append(TrialResult(label=cand.label, us_per_call=us))
        if i == 0:
            default_us = us
        if us < best_us:
            best_plan, best_label, best_us = plans[i], cand.label, us
    if best_plan is None:
        raise guard.SortRuntimeError(
            "autotune.measure", "at least one candidate measured",
            f"all {len(measured)} measured distributed candidate(s) "
            f"failed ({len(skipped)} denylisted) for n_global={n_global} "
            f"D={d}")
    return AutotuneResult(
        best_plan=best_plan,
        best_label=best_label,
        best_us=best_us,
        default_us=default_us,
        trials=tuple(trials),
        candidates=tuple(scores),
        measure_budget=measure_budget,
        failed=tuple(failed),
        skipped=skipped,
    )


def _nearest_shard_record(
    store: dict, base: ShardPlan, key: str
) -> tuple[ShardPlan, str] | None:
    """The cached distributed winner at the mesh signature NEAREST to
    ``base``: same dtype/order/backend triple required, then prefer
    the same config fingerprint, then the closest log2 shard length,
    then log2 D (deterministic key tie-break)."""
    want = (base.dtype_name, str(base.descending), base.impl,
            str(base.interpret), base.backend)
    best = None
    for k, rec in store["plans"].items():
        if k == key or not k.startswith("shard|"):
            continue
        if not _record_is_current(rec):
            continue
        parts = k.split("|")[1:]
        if len(parts) != 11 or (
            tuple(parts[3:5]) + tuple(parts[7:10])
        ) != want:
            continue
        try:
            d_k, n_local_k = int(parts[1]), int(parts[2])
            plan = shard_plan_from_dict(rec["plan"])
        except (ValueError, TypeError, KeyError):
            continue
        dist = (
            0 if parts[10] == base.cfg_fingerprint else 1,
            abs(np.log2(max(n_local_k, 1))
                - np.log2(max(base.n_local, 1))),
            abs(np.log2(max(d_k, 1)) - np.log2(max(base.d, 1))),
            k,
        )
        if best is None or dist < best[0]:
            best = (dist, plan, k)
    return (best[1], best[2]) if best else None


def _shard_seed_from_record(plan: ShardPlan, cfg: SortConfig):
    """Transfer seed for the distributed search: the cached winner's
    oversample/pair_align, applied over the requesting ``cfg``."""
    return ShardCandidate(
        cfg=dataclasses.replace(cfg, plan="default"),
        oversample=plan.oversample, pair_align=plan.pair_align,
        label="transfer",
    )


def shard_plan_for(
    mesh,
    axis,
    n_global: int,
    dtype,
    cfg: SortConfig,
    *,
    oversample: int = 8,
    pair_align: int = 8,
    path: str | None = None,
    max_trials: int = 8,
    repeats: int = 2,
    measure_budget: int | None = 5,
    transfer: bool = True,
) -> ShardPlan:
    """Cached-or-tuned distributed plan (the ``plan="autotune"`` path of
    ``make_sharded_sort``).

    Lookup order mirrors :func:`plan_for`: process memo -> on-disk
    store (keyed by :func:`shard_cache_key`, i.e. by mesh signature) ->
    run :func:`autotune_shard` and persist the winner.  A reloaded plan
    is EQUAL to the saved one, so the distributed jit entry's static-arg
    cache hits too — a shard-plan-cache hit performs zero retraces
    (tested on forced-host meshes).

    Records carry the cost-model version (stale version = clean miss),
    and a miss with ``transfer=True`` seeds from the nearest cached
    mesh signature with the budget capped at 2 measurements, exactly
    as :func:`plan_for` does for the local path.
    """
    axt = (axis,) if isinstance(axis, str) else tuple(axis)
    d = 1
    for a in axt:
        d *= mesh.shape[a]
    base = build_shard_plan(
        axt, d, n_global // d, dtype, cfg,
        oversample=oversample, pair_align=pair_align,
    )
    key = shard_cache_key(base)
    if key in _SHARD_MEMO:
        return _SHARD_MEMO[key]
    path = path or cache_path()
    store = _load_store(path)
    rec = store["plans"].get(key)
    if rec is not None and _record_is_current(rec):
        try:
            plan = shard_plan_from_dict(rec["plan"])
        except (ValueError, TypeError):
            pass  # stale schema: clean miss, re-tune and overwrite
        else:
            _SHARD_MEMO[key] = plan
            return plan

    seeds: tuple[ShardCandidate, ...] = ()
    budget = measure_budget
    transfer_from = None
    if transfer and measure_budget is not None:
        near = _nearest_shard_record(store, base, key)
        if near is not None:
            seeds = (_shard_seed_from_record(near[0], cfg),)
            budget = min(measure_budget, 2)
            transfer_from = near[1]

    deny = store.get("denylist", {}).get(key, {})
    result = autotune_shard(
        mesh, axt, n_global, dtype, cfg,
        oversample=oversample, pair_align=pair_align,
        max_trials=max_trials, repeats=repeats,
        measure_budget=budget, seed_candidates=seeds,
        denylist=frozenset(deny),
    )
    if result.failed:
        store.setdefault("denylist", {}).setdefault(key, {}).update(
            dict(result.failed))
    store["plans"][key] = dict(
        plan=shard_plan_to_dict(result.best_plan),
        best_us=round(result.best_us, 1),
        default_us=round(result.default_us, 1),
        speedup=round(result.speedup, 3),
        cost_model=result.cost_model_version,
        measure_budget=result.measure_budget,
        measured=sum(
            1 for c in result.candidates if c.us_per_call is not None
        ),
        candidates=len(result.candidates),
        **({"transfer_from": transfer_from} if transfer_from else {}),
    )
    _persist_store(path, store)
    _SHARD_MEMO[key] = result.best_plan
    return result.best_plan


def save_shard_plan(
    plan: ShardPlan, path: str, *, meta: dict | None = None
) -> None:
    """Write one distributed plan to ``path`` as a standalone file (the
    format ``SortConfig(plan=<path>)`` reads through
    ``make_sharded_sort`` and :func:`load_shard_plan`)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    payload = shard_plan_to_dict(plan)
    if meta:
        payload["meta"] = meta
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def load_shard_plan(
    path: str,
    *,
    axis=None,
    d: int | None = None,
    n_local: int | None = None,
    dtype=None,
    cfg: SortConfig | None = None,
) -> ShardPlan:
    """Read a distributed plan file saved by :func:`save_shard_plan`.

    When a call signature is supplied (as ``make_sharded_sort`` does
    for ``SortConfig(plan=<path>)``), the file's plan must match it —
    mesh axis/D, shard length, dtype and order are load-bearing
    (ValueError otherwise).
    """
    import jax.numpy as jnp

    fkey = (path, os.stat(path).st_mtime_ns)
    plan = _FILE_MEMO.get(fkey)
    if not isinstance(plan, ShardPlan):
        with open(path) as f:
            rec = json.load(f)
        rec.pop("meta", None)
        plan = shard_plan_from_dict(rec)
        _FILE_MEMO[fkey] = plan
    if d is not None:
        axt = (axis,) if isinstance(axis, str) else tuple(axis)
        want = (axt, d, n_local, jnp.dtype(dtype).name,
                cfg.descending if cfg else plan.descending)
        got = (plan.axis, plan.d, plan.n_local, plan.dtype_name,
               plan.descending)
        if want != got:
            raise ValueError(
                f"shard plan file {path} was built for (axis, d, n_local, "
                f"dtype, descending)={got}, call needs {want}"
            )
    return plan


def clear_memo() -> None:
    """Drop the process-local memos (tests use this to force the disk
    path)."""
    _MEMO.clear()
    _SHARD_MEMO.clear()
    _FILE_MEMO.clear()
