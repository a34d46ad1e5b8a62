"""Unit tests for the single-device GPU BUCKET SORT (Algorithm 1)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket_sort
from repro.core.sort_config import PAPER_CONFIG, SortConfig

CFG = SortConfig(tile=256, s=16, direct_max=512, impl="xla")


@pytest.mark.parametrize("n", [1, 2, 100, 511, 512, 513, 4096, 50_000])
@pytest.mark.parametrize(
    "dist", ["uniform", "dup", "equal", "sorted", "reverse", "zipf"]
)
def test_sort_all_distributions(rng, n, dist):
    if dist == "uniform":
        x = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    elif dist == "dup":
        x = rng.integers(0, 7, n).astype(np.int32)
    elif dist == "equal":
        x = np.full(n, 42, np.int32)
    elif dist == "sorted":
        x = np.sort(rng.integers(0, 1000, n).astype(np.int32))
    elif dist == "reverse":
        x = np.sort(rng.integers(0, 1000, n).astype(np.int32))[::-1].copy()
    else:
        x = (rng.zipf(1.3, n) % 100000).astype(np.int32)
    out = np.asarray(bucket_sort.sort(jnp.asarray(x), CFG))
    np.testing.assert_array_equal(out, np.sort(x))


def test_sort_kv_permutes_values(rng):
    x = rng.integers(0, 100, 5000).astype(np.int32)
    vals = rng.normal(size=(5000, 3)).astype(np.float32)
    sk, sv = bucket_sort.sort_kv(jnp.asarray(x), jnp.asarray(vals), CFG)
    perm = np.argsort(x, kind="stable")
    np.testing.assert_array_equal(np.asarray(sk), x[perm])
    np.testing.assert_array_equal(np.asarray(sv), vals[perm])


def test_argsort_matches_numpy_stable(rng):
    x = rng.integers(0, 50, 20_000).astype(np.int32)
    perm = np.asarray(bucket_sort.argsort(jnp.asarray(x), CFG))
    np.testing.assert_array_equal(perm, np.argsort(x, kind="stable"))


def test_paper_config_sorts(rng):
    """PAPER_CONFIG mirrors the paper's geometry (2K tiles, s=64)."""
    x = rng.integers(-(2**31), 2**31 - 1, 300_000).astype(np.int32)
    out = np.asarray(bucket_sort.sort(jnp.asarray(x), PAPER_CONFIG))
    np.testing.assert_array_equal(out, np.sort(x))


def test_bfloat16_keys(rng):
    x = rng.normal(size=4000).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    out = np.asarray(bucket_sort.sort(xb, CFG).astype(jnp.float32))
    ref = np.sort(np.asarray(xb.astype(jnp.float32)))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("dist", ["uniform", "dup", "equal"])
def test_gather_relocation_matches_scatter_reference(rng, dist):
    """The scatter-free relocation/compaction (DESIGN.md §4) must produce
    the IDENTICAL permutation as the legacy scatter formulation, and the
    fused sampling/ranking epilogues must not change it either."""
    n = 5000
    if dist == "uniform":
        x = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    elif dist == "dup":
        x = rng.integers(0, 7, n).astype(np.int32)
    else:
        x = np.full(n, 42, np.int32)
    base = dataclasses.replace(
        CFG, relocation="scatter", fuse_sampling=False, fuse_ranking=False
    )
    want = np.asarray(bucket_sort.argsort(jnp.asarray(x), base))
    for cfg in [
        CFG,  # gather + fused (the default hot path)
        dataclasses.replace(CFG, relocation="scatter"),
        dataclasses.replace(CFG, fuse_sampling=False),
        dataclasses.replace(CFG, fuse_ranking=False),
    ]:
        got = np.asarray(bucket_sort.argsort(jnp.asarray(x), cfg))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("block_rows", [1, 4])
def test_explicit_block_rows_sorts(rng, block_rows):
    cfg = dataclasses.replace(CFG, block_rows=block_rows)
    x = rng.integers(0, 100_000, 20_000).astype(np.int32)
    out = np.asarray(bucket_sort.sort(jnp.asarray(x), cfg))
    np.testing.assert_array_equal(out, np.sort(x))


def test_deterministic_across_runs(rng):
    """The paper's determinism claim: identical input => identical output
    AND identical permutation (no RNG anywhere in the pipeline)."""
    x = jnp.asarray(rng.integers(0, 10, 10_000).astype(np.int32))
    p1 = np.asarray(bucket_sort.argsort(x, CFG))
    p2 = np.asarray(bucket_sort.argsort(x, CFG))
    np.testing.assert_array_equal(p1, p2)


def test_sort_with_stats_direct_path_returns_empty_stats(rng):
    """Inputs within direct_max run zero bucket rounds: stats must be a
    well-defined EMPTY list (not an error), sort/perm still correct."""
    x = rng.integers(0, 100, CFG.direct_max).astype(np.int32)
    srt, perm, stats = bucket_sort.sort_with_stats(jnp.asarray(x), CFG)
    assert stats == []
    np.testing.assert_array_equal(np.asarray(srt), np.sort(x))
    np.testing.assert_array_equal(np.asarray(perm), np.argsort(x, kind="stable"))
    # trivial inputs too
    for n in (0, 1):
        srt, perm, stats = bucket_sort.sort_with_stats(
            jnp.asarray(x[:n]), CFG
        )
        assert stats == [] and srt.shape == (n,) and perm.shape == (n,)
    # and the batched variant
    xb = rng.integers(0, 100, (3, CFG.direct_max // 2)).astype(np.int32)
    srt, perm, stats = bucket_sort.sort_batched_with_stats(jnp.asarray(xb), CFG)
    assert stats == []
    np.testing.assert_array_equal(np.asarray(srt), np.sort(xb, axis=1))


def test_batched_stats_bucket_bound_adversarial_rows(rng):
    """The capacity bound holds PER ROW: an all-duplicates row next to a
    uniform row (plus sorted/reverse/zipf rows) must keep every round's
    max bucket fill <= capacity, for every bucket of every row."""
    n = 4 * CFG.direct_max
    rows = np.stack([
        np.full(n, 42, np.int32),  # all-dup: worst case for splitters
        rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32),  # uniform
        np.sort(rng.integers(0, 1000, n).astype(np.int32)),  # presorted
        np.sort(rng.integers(0, 1000, n).astype(np.int32))[::-1],  # reverse
        (rng.zipf(1.3, n) % 100000).astype(np.int32),  # heavy skew
    ])
    srt, perm, stats = bucket_sort.sort_batched_with_stats(
        jnp.asarray(rows), CFG
    )
    assert len(stats) >= 1
    for stt in stats:
        totals = np.asarray(stt["totals"])  # (rows_at_level, s_round)
        assert totals.min() >= 0
        assert totals.max() <= stt["capacity"], (totals.max(), stt["capacity"])
        assert int(np.asarray(stt["max_within"])) < stt["capacity"]
    np.testing.assert_array_equal(np.asarray(srt), np.sort(rows, axis=1))
    np.testing.assert_array_equal(
        np.asarray(perm), np.argsort(rows, axis=1, kind="stable")
    )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_int32_budget_overflow_raises_before_tracing(impl):
    """At n = 2**28 the bucket arrays outgrow int32 flat indices: the
    sort names the payload budget instead of tracing (or degrading)."""
    import jax

    cfg = SortConfig(impl=impl, interpret=impl == "pallas")
    x = jax.ShapeDtypeStruct((1 << 28,), jnp.int32)
    with pytest.raises(ValueError, match="int32 payload budget"):
        jax.eval_shape(lambda a: bucket_sort.argsort(a, cfg), x)


def test_int32_budget_admits_largest_single_chip_size():
    """2**27 (the chip-smoke size) stays inside the budget."""
    n = 1 << 27
    plan = bucket_sort.resolve_plan(n, jnp.int32, SortConfig(impl="xla"))
    bucket_sort.check_pad_budget(plan, n)


# Built bucket fills for step 9's compaction: (rows, s_round, cap, lp,
# fills per row, lanes w per block).  Block edges are multiples of w;
# the plan's w is min(128, tile), so a tile below 128 gives narrower
# blocks.
COMPACT_FILLS = {
    "empty_buckets": (2, 4, 384, 512, [[0, 256, 0, 256], [128, 0, 384, 0]],
                      128),
    "shorter_than_a_block": (
        1, 8, 256, 256, [[50, 3, 77, 1, 60, 40, 20, 5]], 128),
    "several_in_one_block": (
        1, 8, 384, 384, [[10, 20, 30, 5, 7, 40, 16, 256]], 128),
    "one_bucket_holds_the_row": (
        2, 4, 512, 512, [[0, 512, 0, 0], [512, 0, 0, 0]], 128),
    "full_cap_off_an_edge": (1, 4, 256, 512, [[100, 256, 156, 0]], 128),
    "ends_on_block_edges": (1, 4, 256, 512, [[128, 256, 0, 128]], 128),
    "ends_off_block_edges": (1, 4, 256, 512, [[129, 127, 255, 1]], 128),
    "last_block_starts": (1, 4, 256, 384, [[256, 100, 27, 1]], 128),
    "tile_64_rows": (
        2, 4, 256, 320, [[0, 200, 120, 0], [5, 99, 100, 116]], 64),
    "eight_lane_blocks": (
        1, 8, 128, 256, [[3, 17, 0, 40, 8, 100, 64, 24]], 8),
    "two_lane_blocks": (2, 4, 128, 130, [[1, 64, 0, 65], [0, 0, 127, 3]], 2),
}


def _bucket_rows(rng, rows, cap):
    k = jnp.asarray(rng.integers(0, 2**32, (rows, cap), dtype=np.uint32))
    v = jnp.asarray(rng.integers(0, 2**31 - 1, (rows, cap), dtype=np.int32))
    return k, v


@pytest.mark.parametrize("case", sorted(COMPACT_FILLS))
def test_blocked_compaction_matches_gather_and_scatter(rng, case):
    """Blocked compaction (DESIGN.md §4) gives the scatter reference's
    dense rows bit for bit, and those of a NumPy gather of each
    bucket's filled prefix."""
    r, s_round, cap, lp, fills, w = COMPACT_FILLS[case]
    totals = jnp.asarray(fills, jnp.int32)
    assert (np.asarray(fills).sum(axis=1) == lp).all()
    assert lp % w == 0 and cap % w == 0
    k, v = _bucket_rows(rng, r * s_round, cap)
    args = (totals, r, s_round, cap, lp)
    got = bucket_sort._compact_blocked((k,), v, *args, w)
    for x, out in ((k, got[0][0]), (v, got[1])):
        rows = np.asarray(x).reshape(r, s_round, cap)
        want = np.stack([
            np.concatenate([rows[i, j, :f] for j, f in enumerate(fills[i])])
            for i in range(r)])
        np.testing.assert_array_equal(np.asarray(out), want)
    ref = bucket_sort._compact_scatter((k,), v, *args)
    np.testing.assert_array_equal(np.asarray(got[0][0]),
                                  np.asarray(ref[0][0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


@pytest.mark.parametrize("tile", [16, 64])
def test_small_tile_plans_compact_by_tile_wide_blocks(rng, tile):
    """A tile below 128 plans blocks of ``tile`` lanes, which divide
    every bucket node's padded length and capacity; the sort stays
    stable through them."""
    from repro.core.plan import build_plan

    cfg = SortConfig(tile=tile, s=8, direct_max=128, impl="xla")
    n = 1000
    node, blocks = build_plan(n, jnp.int32, cfg).root, []
    while node.kind == "bucket":
        blocks.append(node.compact_block)
        assert node.lp % tile == 0 and node.cap % tile == 0
        node = node.bucket_plan
    assert blocks and set(blocks) == {tile}
    x = rng.integers(0, 9, n).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(bucket_sort.argsort(jnp.asarray(x), cfg)),
        np.argsort(x, kind="stable"))


def _hbj_duplicates(n):
    """Helman-Bader-JaJa deterministic duplicates: the first n/2 keys
    are log2 n, the next n/4 are log2(n/2), and so on."""
    out, start, size = np.empty(n, np.int32), 0, n // 2
    while start < n:
        size = max(size, 1)
        out[start:start + size] = int(np.log2(n - start))
        start += size
        size //= 2
    return out


@pytest.mark.parametrize("dist", ["equal", "hbj_duplicates", "presorted"])
def test_blocked_two_level_argsort_is_stable(rng, dist):
    """Two bucket levels, both compacted by blocks, on the inputs that
    make the most empty and the most lopsided buckets."""
    from repro.core.plan import build_plan

    cfg = SortConfig(tile=256, s=16, direct_max=256, impl="xla")
    n = 5000
    plan = build_plan(n, jnp.int32, cfg)
    assert plan.num_levels == 2
    assert plan.root.compact_block == plan.root.bucket_plan.compact_block == 128
    if dist == "equal":
        x = np.full(n, 7, np.int32)
    elif dist == "hbj_duplicates":
        x = _hbj_duplicates(n)
    else:
        x = np.sort(rng.integers(-1000, 1000, n).astype(np.int32))
    perm = np.asarray(bucket_sort.argsort(jnp.asarray(x), cfg))
    np.testing.assert_array_equal(perm, np.argsort(x, kind="stable"))
