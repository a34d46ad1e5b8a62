"""Partial deterministic sample sort: top-k of a large array (beyond-paper).

Serving needs top-k / top-p over vocab-sized logits (50k-164k).  A full
sort wastes work; instead run ONE bucket round of Algorithm 1 (steps
1-7) to locate a splitter threshold θ whose global rank >= k, gather the
< k + B candidates below θ (B = the paper's guaranteed bucket capacity —
that static bound is exactly what makes the candidate buffer static),
and fully sort only the candidates.

Work: O(n) tile sort + O((k+B) log(k+B))  vs  O(n log n) full sort.

Everything here operates on "smallest-k of canonical key words"; the
public entries encode with a ``descending=True`` key codec
(``core/key_codec``), under which ascending canonical order ==
descending score order and ties break toward the smaller index,
matching jax.lax.top_k.  All codec dtypes are supported (64-bit scores
use two-word keys and need x64 mode); ``cfg.descending`` is ignored —
top-k is descending by definition.

``topk_batched`` runs the same partial round on every row of a
serving-shaped (B, vocab) batch in ONE launch (DESIGN.md §5): tiles of
all rows sort together, splitters/thresholds are per row, and the
candidate pack is a gather whose source indices are expanded from the
per-row tile candidate-count prefix sums, like the step-8 relocation.

Scheduling follows the planner/executor split (DESIGN.md §7): the
one-round geometry (lp, m, cap, ccap, kernel block sizes, resolved
backend) is computed once by ``core/plan.build_topk_plan`` and the
jit'd bodies below consume the frozen ``TopkPlan`` as their static
argument instead of re-deriving it per trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import guard
from repro.core.bucket_sort import _chunk_values
from repro.core.key_codec import codec_for
from repro.core.plan import TopkPlan, build_topk_plan
from repro.core.sort_config import DEFAULT_CONFIG, SortConfig, next_pow2
from repro.kernels import ops

_MAXU = jnp.uint32(0xFFFFFFFF)
_IMAX = jnp.int32(2**31 - 1)


def _pad_pow2(kw, v2):
    """Pad (r, L) words/payloads to the next power of two with
    (all-ones, IMAX) pairs (sort last; never candidates)."""
    r, length = kw[0].shape
    lp = next_pow2(length)
    if lp == length:
        return kw, v2
    pk = jnp.full((r, lp - length), _MAXU, jnp.uint32)
    pv = jnp.full((r, lp - length), _IMAX, jnp.int32)
    return (
        tuple(jnp.concatenate([w, pk], 1) for w in kw),
        jnp.concatenate([v2, pv], 1),
    )


def _sort_small(kw, v1, tplan: TopkPlan):
    """Bitonic sort of a single row (pads with (all-ones, IMAX) go last)."""
    n = kw[0].shape[0]
    skw, sv = ops.sort_tiles(
        *_pad_pow2(tuple(w[None] for w in kw), v1[None]),
        impl=tplan.impl, interpret=tplan.interpret,
    )
    return tuple(w[0, :n] for w in skw), sv[0, :n]


@functools.partial(jax.jit, static_argnames=("tplan",))
def _smallest_k(kw, tplan: TopkPlan):
    """Ascending smallest-k of canonical key words; payload = original
    index.  kw: tuple of (n,) uint32 word arrays (msw first); every
    static quantity (lp, m, cap, ccap, kernel geometry) is read off the
    :class:`repro.core.plan.TopkPlan`."""
    (n,) = kw[0].shape
    k = tplan.k
    t, s = tplan.tile, tplan.s
    lp = tplan.lp
    vals = jnp.arange(n, dtype=jnp.int32)
    if lp > n:  # pad with MAX pairs: never candidates for smallest-k
        kw = tuple(
            jnp.concatenate([w, jnp.full((lp - n,), _MAXU, jnp.uint32)])
            for w in kw
        )
        vals = jnp.concatenate([vals, jnp.full((lp - n,), _IMAX, jnp.int32)])
    m = tplan.m

    # steps 1-2: tile sort
    tkw, tv = ops.sort_tiles(
        tuple(w.reshape(m, t) for w in kw), vals.reshape(m, t),
        impl=tplan.impl, interpret=tplan.interpret,
        block_rows=tplan.block_rows,
    )

    # steps 3-5: samples -> sorted samples -> s-1 splitters
    samp_idx = (jnp.arange(1, s + 1, dtype=jnp.int32) * (t // s)) - 1
    skw, sv = _sort_small(
        tuple(w[:, samp_idx].reshape(m * s) for w in tkw),
        tv[:, samp_idx].reshape(m * s), tplan,
    )
    sp_idx = (jnp.arange(1, s, dtype=jnp.int32) * (m * s)) // s
    spkw = tuple(jnp.broadcast_to(w[sp_idx], (m, s - 1)) for w in skw)
    spv = jnp.broadcast_to(sv[sp_idx], (m, s - 1))

    # step 6: ranks
    ranks = ops.splitter_ranks(
        tkw, tv, spkw, spv, impl=tplan.impl, interpret=tplan.interpret
    )  # (m, s-1)
    glob_ranks = ranks.sum(axis=0, dtype=jnp.int32)  # (s-1,)

    # θ = smallest splitter with global rank >= k; candidates = elements < θ.
    # Bucket bound: candidate count < k + cap.  If no splitter qualifies,
    # the last bucket alone exceeds lp - k, hence cap > lp - k and the
    # static capacity (plan-carried) already covers taking ALL elements.
    ccap = tplan.ccap
    qualifies = glob_ranks >= k  # monotone
    any_q = jnp.any(qualifies)
    theta = jnp.argmax(qualifies).astype(jnp.int32)  # first True (or 0)
    tile_rank = jnp.where(
        any_q,
        jnp.take_along_axis(
            ranks, jnp.broadcast_to(theta[None, None], (m, 1)), axis=1
        )[:, 0],
        jnp.full((m,), t, jnp.int32),
    )  # (m,) elements of tile i below θ (or all)

    # candidate gather: global candidate slot = (#cands in earlier tiles) + pos
    tile_excl = jnp.cumsum(tile_rank, dtype=jnp.int32) - tile_rank
    pos = jax.lax.broadcasted_iota(jnp.int32, (m, t), 1)
    is_cand = pos < tile_rank[:, None]
    within = tile_excl[:, None] + pos
    dest = jnp.where(is_cand & (within < ccap), within, ccap).reshape(-1)
    ckw = tuple(
        jnp.full((ccap + 1,), _MAXU, jnp.uint32)
        .at[dest].set(w.reshape(-1), mode="drop")[:ccap]
        for w in tkw
    )
    cv = jnp.full((ccap + 1,), _IMAX, jnp.int32)
    cv = cv.at[dest].set(tv.reshape(-1), mode="drop")[:ccap]

    fkw, fv = _sort_small(ckw, cv, tplan)
    return tuple(w[:k] for w in fkw), fv[:k]


def _fallback_topk_plan(n, k, dtype, tplan: TopkPlan, rows: int = 1):
    """Default-config xla stand-in plan for the degradation chain
    (DESIGN.md §11), or None when indistinguishable from ``tplan``."""
    alt = build_topk_plan(
        n, k, dtype, SortConfig(impl="xla", interpret=False), rows=rows
    )
    return None if alt == tplan else alt


def _topk_site(tplan: TopkPlan) -> str:
    return (f"TopkPlan(rows={tplan.rows}, n={tplan.length}, "
            f"k={tplan.k}, impl={tplan.impl})")


def _reference_topk(x, k, codec, check):
    """Last rung of the chain: jax.lax.top_k (no plan machinery)."""
    v, i = jax.lax.top_k(x, k)
    i = i.astype(jnp.int32)
    if check != "off":
        guard.check_topk(x, v, i, k, check, codec)
    return v, i


def topk(x: jax.Array, k: int, cfg: SortConfig = DEFAULT_CONFIG):
    """Top-k (descending) values + original indices of 1-D x.

    Args:
        x: 1-D scores in any codec dtype (int/uint/float 8..64-bit,
            bool; 64-bit needs x64 mode — see ``core/key_codec``).
        k: 1 <= k <= len(x).
        cfg: pipeline knobs (``cfg.descending`` is ignored: top-k is
            descending by definition; ``cfg.check`` enables runtime
            invariants and the degradation chain of DESIGN.md §11).
    Returns:
        (values (k,) in x.dtype, indices (k,) int32); ties break toward
        the smaller index (matches jax.lax.top_k).

    Example:
        >>> import jax.numpy as jnp
        >>> from repro.core import partial_sort
        >>> v, i = partial_sort.topk(jnp.asarray([1.0, 9.0, 4.0, 9.0]), 2)
        >>> v, i
        (Array([9., 9.], dtype=float32), Array([1, 3], dtype=int32))
    """
    n = x.shape[0]
    assert 1 <= k <= n
    guard.validate_check(cfg.check)
    codec = codec_for(x.dtype, descending=True)
    kw = codec.encode(x)  # ascending canonical == descending score

    def run(tplan):
        if n <= tplan.direct_max:
            fkw, fv = _sort_small(kw, jnp.arange(n, dtype=jnp.int32), tplan)
            fkw, fv = tuple(w[:k] for w in fkw), fv[:k]
        else:
            fkw, fv = _smallest_k(kw, tplan)
        v, i = codec.decode(fkw), fv
        if cfg.check != "off":
            guard.check_topk(x, v, i, k, cfg.check, codec)
        return v, i

    tplan = build_topk_plan(n, k, x.dtype, cfg)
    try:
        return run(tplan)
    except guard.RECOVERABLE as e1:
        alt = _fallback_topk_plan(n, k, x.dtype, tplan)
        if alt is not None:
            guard.record_degradation(
                _topk_site(tplan), "fallback",
                f"impl={tplan.impl} topk plan", "default xla stand-in plan",
                e1)
            try:
                return run(alt)
            except guard.RECOVERABLE as e2:
                e1 = e2
        guard.record_degradation(
            _topk_site(tplan), "fallback",
            "partial-sort top-k", "jax.lax.top_k reference", e1)
        return _reference_topk(x, k, codec, cfg.check)


# ----------------------------------------------------------------------
# Batched partial sort: top-k of every row of (B, vocab) in one launch
# ----------------------------------------------------------------------


def _sort_small_rows(kw, v2, tplan: TopkPlan):
    """Bitonic sort of each row of (r, L) (pads with (all-ones, IMAX) last)."""
    n = kw[0].shape[1]
    skw, sv = ops.sort_tiles(
        *_pad_pow2(kw, v2), impl=tplan.impl, interpret=tplan.interpret,
        block_rows=tplan.raw_block_rows,
    )
    return tuple(w[:, :n] for w in skw), sv[:, :n]


@functools.partial(jax.jit, static_argnames=("tplan",))
def _smallest_k_rows(kw, tplan: TopkPlan):
    """Per-row ascending smallest-k of (B, n) canonical key words;
    payload = original column index.  One bucket round for the whole
    batch (geometry plan-carried); θ and the candidate set are per row."""
    b, n = kw[0].shape
    k = tplan.k
    t, s = tplan.tile, tplan.s
    lp = tplan.lp
    vals = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (b, n))
    if lp > n:  # pad with MAX pairs: never candidates for smallest-k
        kw = tuple(
            jnp.concatenate(
                [w, jnp.full((b, lp - n), _MAXU, jnp.uint32)], axis=1
            )
            for w in kw
        )
        vals = jnp.concatenate(
            [vals, jnp.full((b, lp - n), _IMAX, jnp.int32)], axis=1
        )
    m = tplan.m

    # steps 1-2: tile sort, all rows' tiles in one launch
    tkw, tv = ops.sort_tiles(
        tuple(w.reshape(b * m, t) for w in kw), vals.reshape(b * m, t),
        impl=tplan.impl, interpret=tplan.interpret,
        block_rows=tplan.block_rows,
    )

    # steps 3-5: per-row samples -> sorted sample rows -> s-1 splitters
    samp_idx = (jnp.arange(1, s + 1, dtype=jnp.int32) * (t // s)) - 1
    sskw, ssv = _sort_small_rows(
        tuple(w[:, samp_idx].reshape(b, m * s) for w in tkw),
        tv[:, samp_idx].reshape(b, m * s),
        tplan,
    )
    sp_idx = (jnp.arange(1, s, dtype=jnp.int32) * (m * s)) // s
    spkw_t = tuple(jnp.repeat(w[:, sp_idx], m, axis=0) for w in sskw)
    spv_t = jnp.repeat(ssv[:, sp_idx], m, axis=0)  # (b*m, s-1)

    # step 6: ranks, reduced per row
    ranks = ops.splitter_ranks(
        tkw, tv, spkw_t, spv_t, impl=tplan.impl, interpret=tplan.interpret
    ).reshape(b, m, s - 1)
    glob_ranks = ranks.sum(axis=1, dtype=jnp.int32)  # (b, s-1)

    # Per-row θ: smallest splitter with global rank >= k (see _smallest_k
    # for why ccap always covers the candidate count).
    ccap = tplan.ccap
    qualifies = glob_ranks >= k  # (b, s-1), monotone per row
    any_q = jnp.any(qualifies, axis=1)  # (b,)
    theta = jnp.argmax(qualifies, axis=1).astype(jnp.int32)  # (b,)
    tile_rank = jnp.where(
        any_q[:, None],
        jnp.take_along_axis(ranks, theta[:, None, None], axis=2)[:, :, 0],
        jnp.full((b, m), t, jnp.int32),
    )  # (b, m) elements of each tile below the row's θ (or all)

    # Scatter-free candidate pack: slot p of row q reads the tile whose
    # candidate-count prefix interval covers p, at its first tile_rank
    # positions (the candidates are a sorted tile's prefix).
    tile_excl = jnp.cumsum(tile_rank, axis=1, dtype=jnp.int32) - tile_rank
    total = tile_rank.sum(axis=1, dtype=jnp.int32)  # (b,)
    tile = jax.lax.broadcasted_iota(jnp.int32, (b, m), 1)
    row_base = jax.lax.broadcasted_iota(jnp.int32, (b, m), 0) * m
    base = (row_base + tile) * t - tile_excl
    p = jax.lax.broadcasted_iota(jnp.int32, (b, ccap), 1)
    src = p + _chunk_values(tile_excl, base, ccap)
    valid = p < total[:, None]
    src = jnp.where(valid, src, 0).reshape(-1)
    ckw = tuple(
        jnp.where(valid, jnp.take(w.reshape(-1), src).reshape(b, ccap), _MAXU)
        for w in tkw
    )
    cv = jnp.where(valid, jnp.take(tv.reshape(-1), src).reshape(b, ccap),
                   _IMAX)

    fkw, fv = _sort_small_rows(ckw, cv, tplan)
    return tuple(w[:, :k] for w in fkw), fv[:, :k]


def topk_batched(x: jax.Array, k: int, cfg: SortConfig = DEFAULT_CONFIG):
    """Top-k (descending) values + column indices of every row of (B, C).

    Equivalent to ``jax.lax.top_k(x, k)`` (ties toward the smaller
    index) but via the partial deterministic sample sort, one launch for
    the whole batch — the serving shape: (batch, vocab) logits.

    Args:
        x: (B, C) scores in any codec dtype (see :func:`topk`).
        k: 1 <= k <= C.
        cfg: pipeline knobs (``descending`` ignored, see :func:`topk`;
            ``cfg.check`` enables runtime invariants + degradation).
    Returns:
        (values (B, k) in x.dtype, indices (B, k) int32).
    """
    assert x.ndim == 2, x.shape
    b, n = x.shape
    assert 1 <= k <= n
    if b == 0:
        return (jnp.zeros((0, k), x.dtype), jnp.zeros((0, k), jnp.int32))
    guard.validate_check(cfg.check)
    codec = codec_for(x.dtype, descending=True)
    kw = codec.encode(x)  # ascending canonical == descending score

    def run(tplan):
        if n <= tplan.direct_max:
            vals = jnp.broadcast_to(
                jnp.arange(n, dtype=jnp.int32)[None, :], (b, n)
            )
            fkw, fv = _sort_small_rows(kw, vals, tplan)
            fkw, fv = tuple(w[:, :k] for w in fkw), fv[:, :k]
        else:
            fkw, fv = _smallest_k_rows(kw, tplan)
        v, i = codec.decode(fkw), fv
        if cfg.check != "off":
            guard.check_topk(x, v, i, k, cfg.check, codec)
        return v, i

    tplan = build_topk_plan(n, k, x.dtype, cfg, rows=b)
    try:
        return run(tplan)
    except guard.RECOVERABLE as e1:
        alt = _fallback_topk_plan(n, k, x.dtype, tplan, rows=b)
        if alt is not None:
            guard.record_degradation(
                _topk_site(tplan), "fallback",
                f"impl={tplan.impl} topk plan", "default xla stand-in plan",
                e1)
            try:
                return run(alt)
            except guard.RECOVERABLE as e2:
                e1 = e2
        guard.record_degradation(
            _topk_site(tplan), "fallback",
            "partial-sort top-k", "jax.lax.top_k reference", e1)
        return _reference_topk(x, k, codec, cfg.check)
