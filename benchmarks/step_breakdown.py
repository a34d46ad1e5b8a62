"""Fig. 5 analogue: per-step timing of Algorithm 1 (C3), plus the
baseline-vs-fused deltas of the row-blocked pipeline (DESIGN.md §3-§4).

The paper observes: local sort (step 2) + sublist sort (step 9)
dominate; deterministic-sampling overhead (steps 3-7) is small; the
relocation (step 8) is cheap because it is one coalesced pass.

On top of the per-step rows this emits A/B rows for the two hot spots
this port optimizes:
  * step 2 local sort — the row-blocked Pallas kernel (interpret mode
    off the TPU, native on it);
  * steps 8/9 relocation + compaction — legacy scatter formulation vs
    the scatter-free gather formulation, on the xla path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.core import bucket_sort as bs
from repro.core.sort_config import SortConfig
from repro.kernels import ops

CFG = SortConfig(tile=4096, s=64, direct_max=8192, impl="xla")


def run(n=1048576, repeats=3, pallas_compare=True):
    rng = np.random.default_rng(2)
    x = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    u = ops.to_sortable(jnp.asarray(x))
    # All geometry comes off the RESOLVED plan (the planner/executor
    # split, DESIGN.md §7) — the benchmark no longer re-derives it.
    full_plan = bs.resolve_plan(n, jnp.int32, CFG)
    root = full_plan.root
    assert root.kind == "bucket", "step breakdown needs a bucket round"
    t, sper = root.tile, root.s
    lp, m = root.lp, root.m
    s_round, cap = root.s_round, root.cap
    r = 1

    # --- Per-step rows (Fig. 5), on the default fused path. -------------
    @jax.jit
    def local_sort(u):
        v = jnp.arange(lp, dtype=jnp.int32)
        return ops.sort_tiles_sample(
            u.reshape(m, t), v.reshape(m, t), num_samples=sper, impl="xla"
        )

    tk, tv, sampk, sampv = jax.block_until_ready(local_sort(u))

    @jax.jit
    def sample_sort(sampk, sampv):
        # internal canonical entries take tuples of key words (one word
        # for int32 keys); unwrap on the way out
        skw, ssv, _ = bs._sort_rows(
            (sampk.reshape(1, m * sper),), sampv.reshape(1, m * sper),
            CFG, 2 * lp, None,
        )
        return skw[0], ssv

    ssk, ssv = jax.block_until_ready(sample_sort(sampk, sampv))

    def splitters(ssk, ssv):
        sp_idx = (jnp.arange(1, s_round, dtype=jnp.int32) * (m * sper)) // s_round
        spk = jnp.repeat(ssk[:, sp_idx], m, axis=0)
        spv = jnp.repeat(ssv[:, sp_idx], m, axis=0)
        return spk, spv

    @jax.jit
    def ranks_fn(tk, tv, ssk, ssv):
        spk, spv = splitters(ssk, ssv)
        return ops.splitter_partition(tk, tv, spk, spv, impl="xla")

    ranks, counts2 = jax.block_until_ready(ranks_fn(tk, tv, ssk, ssv))

    @jax.jit
    def full(u):
        return bs._sort_canonical((u,), full_plan)

    rows = []
    t_local = timeit(local_sort, u, repeats=repeats)
    t_samp = timeit(sample_sort, sampk, sampv, repeats=repeats)
    t_rank = timeit(ranks_fn, tk, tv, ssk, ssv, repeats=repeats)
    t_full = timeit(full, u, repeats=repeats)
    rest = max(t_full - t_local - t_samp - t_rank, 0.0)
    for name, tt in [
        ("step2-3_local_sort_fused_sampling", t_local),
        ("step4-5_sample_sort", t_samp),
        ("step6-7_splitter_partition", t_rank),
        ("steps8-9_relocate_and_bucket_sort", rest),
        ("total", t_full),
    ]:
        frac = tt / t_full if t_full else 0
        rows.append(dict(name=f"step_breakdown/{name}", us_per_call=tt * 1e6,
                         derived=f"{100*frac:.1f}% of total (n={n})"))
    overhead = (t_samp + t_rank) / t_full
    rows.append(dict(
        name="step_breakdown/sampling_overhead_fraction", us_per_call=0.0,
        derived=f"{100*overhead:.1f}% (paper C3: small)"))

    # --- Per-strategy local sort (hybrid dispatch, DESIGN.md §8). -------
    v_st = jnp.arange(lp, dtype=jnp.int32).reshape(m, t)
    uk_st = u.reshape(m, t) if lp == n else jnp.pad(u, (0, lp - n)).reshape(m, t)

    @functools.partial(jax.jit, static_argnames=("st",))
    def strat_sort(uk, v, st):
        return ops.sort_tiles(uk, v, impl="xla", strategy=st)

    st_us: dict[str, float] = {}
    for st in ("bitonic", "radix", "merge"):
        st_us[st] = timeit(lambda a, b, s=st: strat_sort(a, b, s),
                           uk_st, v_st, repeats=repeats)
        rows.append(dict(
            name=f"step_breakdown/step2_local_sort_{st}",
            us_per_call=st_us[st] * 1e6,
            derived=f"strategy={st} (xla), "
                    f"{st_us['bitonic'] / max(st_us[st], 1e-12):.2f}x "
                    f"vs bitonic"))

    # --- A/B: scatter vs gather relocation + compaction (steps 8/9). ----
    starts = jnp.concatenate([jnp.zeros((r * m, 1), jnp.int32), ranks], axis=1)
    counts = counts2.reshape(r, m, s_round)
    tile_off = jnp.cumsum(counts, axis=1) - counts
    totals = counts.sum(axis=1)

    @jax.jit
    def reloc_scatter(tk, tv, ranks, starts, tile_off):
        bkw, bv = bs._relocate_scatter(
            (tk,), tv, ranks, starts, tile_off, r, m, s_round, t, cap, 2 * lp)
        return bkw[0], bv

    @jax.jit
    def reloc_gather(tk, tv, starts, tile_off, totals):
        bkw, bv = bs._relocate_gather(
            (tk,), tv, starts, tile_off, totals, r, m, s_round, t, cap, 2 * lp)
        return bkw[0], bv

    bk, bv = jax.block_until_ready(reloc_gather(tk, tv, starts, tile_off, totals))

    @jax.jit
    def compact_scatter(bk, bv, totals):
        okw, ov = bs._compact_scatter((bk,), bv, totals, r, s_round, cap, lp)
        return okw[0], ov

    @jax.jit
    def compact_gather(bk, bv, totals):
        okw, ov = bs._compact_gather((bk,), bv, totals, r, s_round, cap, lp)
        return okw[0], ov

    t_rel_sc = timeit(reloc_scatter, tk, tv, ranks, starts, tile_off,
                      repeats=repeats)
    t_rel_ga = timeit(reloc_gather, tk, tv, starts, tile_off, totals,
                      repeats=repeats)
    # NB: compaction here runs on the *uncompacted* bucket array (the real
    # pipeline compacts after the recursive sort) — identical shapes/cost.
    t_cmp_sc = timeit(compact_scatter, bk, bv, totals, repeats=repeats)
    t_cmp_ga = timeit(compact_gather, bk, bv, totals, repeats=repeats)
    rows.append(dict(
        name="step_breakdown/step8_relocation_scatter",
        us_per_call=t_rel_sc * 1e6, derived="legacy 1-D scatter (xla)"))
    rows.append(dict(
        name="step_breakdown/step8_relocation_gather",
        us_per_call=t_rel_ga * 1e6,
        derived=f"scatter-free; {t_rel_sc / max(t_rel_ga, 1e-12):.2f}x vs scatter"))
    rows.append(dict(
        name="step_breakdown/step9_compaction_scatter",
        us_per_call=t_cmp_sc * 1e6, derived="legacy 1-D scatter (xla)"))
    rows.append(dict(
        name="step_breakdown/step9_compaction_gather",
        us_per_call=t_cmp_ga * 1e6,
        derived=f"scatter-free; {t_cmp_sc / max(t_cmp_ga, 1e-12):.2f}x vs scatter"))

    # --- Row-blocked Pallas local sort (interpret mode off the TPU). ----
    if pallas_compare:
        v = jnp.arange(lp, dtype=jnp.int32).reshape(m, t)
        uk = u.reshape(m, t) if lp == n else jnp.pad(u, (0, lp - n)).reshape(m, t)
        pal_sort = jax.jit(
            lambda uk, v: ops.sort_tiles(uk, v, impl="pallas"))
        t_pal_blk = timeit(pal_sort, uk, v, repeats=repeats)
        mode = "interpret" if ops.default_interpret() else "native"
        rows.append(dict(
            name="step_breakdown/step2_local_sort_pallas_blocked",
            us_per_call=t_pal_blk * 1e6,
            derived=f"auto block_rows, {m} tiles ({mode})"))

    # --- Acceptance row: relocation + compaction, scatter vs gather. ----
    base = t_local + t_rel_sc + t_cmp_sc
    new = t_local + t_rel_ga + t_cmp_ga
    rows.append(dict(
        name="step_breakdown/local_sort_plus_relocation_baseline",
        us_per_call=base * 1e6,
        derived="xla sort + scatter relocation/compaction"))
    rows.append(dict(
        name="step_breakdown/local_sort_plus_relocation_fused",
        us_per_call=new * 1e6,
        derived=f"xla sort + gather relocation/compaction; "
                f"{base / max(new, 1e-12):.2f}x speedup (n={n})"))
    return rows
