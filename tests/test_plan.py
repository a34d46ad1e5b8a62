"""Planner/executor split: plan IR purity, planner-path conformance,
compile-count discipline, the persistent plan cache, and the
SortConfig construction-time validation.

The conformance slice here is the CI plan-cache smoke leg's 16-cell
matrix (dtype x impl x size x relocation through ``sort_planned``);
the full 806-cell harness in test_conformance*.py exercises the same
plan-driven executor through the public entry points.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune as autotune_mod
from repro.core import bucket_sort, partial_sort
from repro.core.plan import (
    build_plan,
    build_topk_plan,
    build_words_plan,
    config_fingerprint,
    plan_from_dict,
    plan_json,
    plan_to_dict,
)
from repro.core.sort_config import SortConfig

_XLA = SortConfig(tile=256, s=16, direct_max=512, impl="xla")
_PAL = SortConfig(tile=128, s=8, direct_max=256, impl="pallas", interpret=True)


# ----------------------------------------------------------------------
# SortConfig construction-time validation (ValueError naming the field)
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw,field",
    [
        (dict(tile=3000), "tile"),
        (dict(tile=0), "tile"),
        (dict(s=48, tile=4096), "s"),
        (dict(s=8192, tile=4096), "SortConfig.s"),
        (dict(block_rows=12), "block_rows"),
        (dict(row_pad=6), "row_pad"),
        (dict(direct_max=1024, tile=4096), "direct_max"),
        (dict(impl="cuda"), "impl"),
        (dict(relocation="teleport"), "relocation"),
        (dict(plan=""), "plan"),
    ],
)
def test_config_validation_names_field(kw, field):
    with pytest.raises(ValueError, match=field):
        SortConfig(**kw)


def test_config_valid_knobs_accepted():
    SortConfig(tile=1024, s=64, direct_max=2048, block_rows=16, row_pad=4,
               plan="autotune")


# ----------------------------------------------------------------------
# build_plan: pure, deterministic, structurally sound
# ----------------------------------------------------------------------


@pytest.mark.parametrize("length", [100, 513, 5000, 100_000])
@pytest.mark.parametrize("dtype", ["int32", "float64"])
def test_build_plan_deterministic(length, dtype):
    a = build_plan(length, dtype, _XLA)
    b = build_plan(length, dtype, _XLA)
    assert a == b and hash(a) == hash(b)
    # byte-identical canonical serialization
    assert plan_json(a) == plan_json(b)


def test_build_plan_property_deterministic_and_bounded():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(
        length=st.integers(min_value=1, max_value=300_000),
        rows=st.integers(min_value=1, max_value=64),
        tile_log=st.integers(min_value=7, max_value=12),
        s_log=st.integers(min_value=1, max_value=6),
    )
    def prop(length, rows, tile_log, s_log):
        tile = 2 ** tile_log
        s = min(2 ** s_log, tile)
        cfg = SortConfig(tile=tile, s=s, direct_max=2 * tile, impl="xla")
        if s == 2 and length > cfg.direct_max:
            # cap = L/2 + L/2 = L: buckets never shrink, and the planner
            # names the degenerate config instead of recursing forever.
            with pytest.raises(ValueError, match="degenerate config"):
                build_plan(length, "int32", cfg, rows=rows)
            return
        a = build_plan(length, "int32", cfg, rows=rows)
        b = build_plan(length, "int32", cfg, rows=rows)
        assert plan_json(a) == plan_json(b)
        # structural invariants: every node's geometry is self-consistent
        node = a.root
        while node is not None:
            assert node.rows >= 1 and node.lp >= node.length
            if node.kind == "direct":
                assert node.lp & (node.lp - 1) == 0
                break
            assert node.lp == node.m * node.tile
            assert 2 <= node.s_round <= node.s
            # the paper's capacity bound, lane-aligned
            assert node.cap >= node.lp // node.s_round + node.lp // node.s
            assert node.sample_plan.length == node.m * node.s
            assert node.bucket_plan.rows == node.rows * node.s_round
            assert node.bucket_plan.length == node.cap
            node = node.bucket_plan

    prop()


def test_plan_fingerprint_ignores_plan_field():
    a = config_fingerprint(_XLA)
    b = config_fingerprint(dataclasses.replace(_XLA, plan="autotune"))
    c = config_fingerprint(dataclasses.replace(_XLA, s=32))
    assert a == b
    assert a != c


def test_words_plan_matches_dtype_plan_geometry():
    p32 = build_plan(5000, "int32", _XLA)
    pw = build_words_plan(5000, 1, _XLA)
    assert pw.root == p32.root  # same geometry, codec-free identity
    p64 = build_plan(5000, "int64", _XLA)
    assert build_words_plan(5000, 2, _XLA).root == p64.root


def test_plan_dict_roundtrip_identical():
    for cfg in (_XLA, _PAL, dataclasses.replace(_XLA, descending=True)):
        p = build_plan(40_000, "float32", cfg, rows=4, pad_rows=True)
        d = plan_to_dict(p)
        # the dict is JSON-clean
        rt = plan_from_dict(json.loads(json.dumps(d)))
        assert rt == p and hash(rt) == hash(p)


def test_plan_from_dict_rejects_bad_schema():
    d = plan_to_dict(build_plan(100, "int32", _XLA))
    d["schema"] = "bogus/v9"
    with pytest.raises(ValueError, match="schema"):
        plan_from_dict(d)


def test_degenerate_config_raises_clear_error():
    # s == tile never shrinks the sample array: the builder must say so
    # instead of recursing forever.
    cfg = SortConfig(tile=128, s=128, direct_max=128, impl="xla")
    with pytest.raises(ValueError, match="depth"):
        build_plan(1000, "int32", cfg)


# ----------------------------------------------------------------------
# Planner-path conformance: the CI smoke slice (16 cells)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("reloc", ["gather", "scatter"])
@pytest.mark.parametrize("n", [255, 1500])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("cfg0", [_XLA, _PAL], ids=["xla", "pallas"])
def test_planner_conformance(cfg0, dtype, n, reloc, rng):
    cfg = dataclasses.replace(cfg0, relocation=reloc)
    if dtype == "int32":
        x = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    else:
        x = rng.standard_normal(n).astype(np.float32)
        x[:4] = [np.nan, np.inf, -np.inf, 0.0]
    plan = build_plan(n, dtype, cfg)
    got = bucket_sort.sort_planned(jnp.asarray(x), plan)
    want = jnp.sort(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sort_planned_validates_signature():
    plan = build_plan(100, "int32", _XLA)
    with pytest.raises(ValueError, match="match"):
        bucket_sort.sort_planned(jnp.zeros(101, jnp.int32), plan)
    with pytest.raises(ValueError, match="match"):
        bucket_sort.sort_planned(jnp.zeros(100, jnp.float32), plan)


def test_sort_planned_batched_and_descending(rng):
    xs = rng.integers(0, 50, (5, 700)).astype(np.int32)
    cfg = dataclasses.replace(_XLA, descending=True)
    plan = build_plan(700, "int32", cfg, rows=5, pad_rows=True)
    got = bucket_sort.sort_planned(jnp.asarray(xs), plan)
    np.testing.assert_array_equal(
        np.asarray(got), -np.sort(-xs, axis=1, kind="stable")
    )


def test_topk_plan_matches_lax_topk(rng):
    x = rng.standard_normal(9000).astype(np.float32)
    tplan = build_topk_plan(9000, 7, jnp.float32, _XLA)
    assert tplan.lp % tplan.tile == 0 and tplan.ccap >= 7
    v, i = partial_sort.topk(jnp.asarray(x), 7, _XLA)
    lv, li = jax.lax.top_k(jnp.asarray(x), 7)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(li))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(lv))


# ----------------------------------------------------------------------
# The default direct-versus-bucket threshold (SortConfig.direct_max)
# ----------------------------------------------------------------------

_DEFAULT_PAL = SortConfig(impl="pallas")


def _spine(node):
    """(kind, rows, length, lp) of each node on the bucket_plan spine."""
    out = []
    while node is not None:
        out.append((node.kind, node.rows, node.length, node.lp))
        node = node.bucket_plan if node.kind == "bucket" else None
    return out


@pytest.mark.parametrize("n,levels,ratio,spine", [
    (1 << 17, 1, 3.0, [("bucket", 1, 1 << 17, 1 << 17),
                       ("direct", 64, 4096, 4096)]),
    (1 << 23, 2, 9.046875, [("bucket", 1, 1 << 23, 1 << 23),
                            ("bucket", 64, 1 << 18, 1 << 18),
                            ("direct", 4096, 8192, 8192)]),
])
def test_default_plans_keep_their_shape(n, levels, ratio, spine):
    """The benchmark's one-chip sizes have every node on the same side
    of the 8192 and the 16384 threshold: raising ``direct_max`` leaves
    their plans as they were, node for node."""
    plan = build_plan(n, jnp.int32, _DEFAULT_PAL)
    assert plan.num_levels == levels
    assert plan.moved_elements == ratio * n
    assert _spine(plan.root) == spine
    old = build_plan(n, jnp.int32,
                     dataclasses.replace(_DEFAULT_PAL, direct_max=8192))
    assert plan.root == old.root


@pytest.mark.parametrize("n,kind", [
    (8193, "direct"), (16384, "direct"), (16385, "bucket")])
def test_default_direct_threshold(n, kind):
    root = build_plan(n, jnp.int32, _DEFAULT_PAL).root
    assert SortConfig().direct_max == 16384
    assert root.kind == kind
    if kind == "direct":
        assert root.lp == 16384


@pytest.mark.parametrize("keys", ["uniform", "all_equal"])
def test_default_direct_sort_past_8192_is_stable(keys, rng):
    """A row of 12000 keys, past the old 8192 threshold, now takes the
    default config's one direct 16384-lane kernel (interpret mode)."""
    n = 12000
    assert build_plan(n, jnp.int32, _DEFAULT_PAL).root.kind == "direct"
    x = (rng.integers(-(2**31), 2**31, n, dtype=np.int64)
         if keys == "uniform" else np.full(n, 7)).astype(np.int32)
    got = bucket_sort.argsort(jnp.asarray(x), _DEFAULT_PAL)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.argsort(x, kind="stable"))


# ----------------------------------------------------------------------
# Compile-count discipline: same signature traces once; plan-cache hits
# trace zero times
# ----------------------------------------------------------------------


def test_same_signature_traces_once(rng):
    cfg = dataclasses.replace(_XLA, tile=128, s=8, direct_max=256)
    x = jnp.asarray(rng.integers(0, 10_000, 1777).astype(np.int32))
    bucket_sort.sort(x, cfg)  # may trace (fresh signature)
    t0 = bucket_sort.trace_count()
    for _ in range(3):
        bucket_sort.sort(x, cfg)
    assert bucket_sort.trace_count() == t0, "same-signature sort retraced"


def test_same_signature_batched_traces_once(rng):
    cfg = dataclasses.replace(_XLA, tile=128, s=8, direct_max=256)
    xs = jnp.asarray(rng.integers(0, 10_000, (3, 911)).astype(np.int32))
    bucket_sort.sort_batched(xs, cfg)
    t0 = bucket_sort.trace_count()
    for _ in range(3):
        bucket_sort.sort_batched(xs, cfg)
        bucket_sort.argsort_batched(xs, cfg)  # same plan, same executable
    assert bucket_sort.trace_count() == t0, "same-signature batch retraced"


def test_plan_cache_hit_zero_retrace(tmp_path, monkeypatch, rng):
    monkeypatch.setenv("REPRO_SORT_PLAN_CACHE", str(tmp_path / "plans.json"))
    cfg = SortConfig(tile=128, s=8, direct_max=256, impl="xla",
                     plan="autotune")
    x = jnp.asarray(rng.integers(0, 10_000, 2333).astype(np.int32))
    y = bucket_sort.sort(x, cfg)  # miss: tunes, saves, compiles winner
    np.testing.assert_array_equal(np.asarray(y), np.sort(np.asarray(x)))
    assert (tmp_path / "plans.json").exists()
    # Forget the in-process memo: the next call must go to DISK, reload
    # an identical plan, and hit the jit cache — zero retraces.
    autotune_mod.clear_memo()
    t0 = bucket_sort.trace_count()
    y2 = bucket_sort.sort(x, cfg)
    assert bucket_sort.trace_count() == t0, "plan-cache hit retraced"
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))


def test_plan_cache_roundtrip_identical(tmp_path):
    """CI plan-cache smoke: build -> save -> reload -> identical plan."""
    p = build_plan(123_456, "float32", _PAL, rows=3, pad_rows=True)
    path = str(tmp_path / "plan.json")
    autotune_mod.save_plan(p, path, meta={"source": "test"})
    rt = autotune_mod.load_plan(
        path, length=123_456, dtype="float32", cfg=_PAL, rows=3
    )
    assert rt == p and hash(rt) == hash(p)
    assert plan_json(rt) == plan_json(p)


def test_load_plan_rejects_signature_mismatch(tmp_path):
    p = build_plan(1000, "int32", _XLA)
    path = str(tmp_path / "plan.json")
    autotune_mod.save_plan(p, path)
    with pytest.raises(ValueError, match="built for"):
        autotune_mod.load_plan(path, length=2000, dtype="int32", cfg=_XLA)
    with pytest.raises(ValueError, match="built for"):
        autotune_mod.load_plan(path, length=1000, dtype="float32", cfg=_XLA)


def test_cfg_plan_path_roundtrip(tmp_path, rng):
    x = rng.integers(0, 1000, 3000).astype(np.int32)
    p = build_plan(3000, "int32", _XLA)
    path = str(tmp_path / "plan.json")
    autotune_mod.save_plan(p, path)
    cfg = dataclasses.replace(_XLA, plan=path)
    got = bucket_sort.sort(jnp.asarray(x), cfg)
    np.testing.assert_array_equal(np.asarray(got), np.sort(x))


def test_autotune_winner_not_slower_than_default():
    """Acceptance: the tuned plan's measured time <= the default
    config's (the default is candidate 0 of the search space)."""
    res = autotune_mod.autotune(
        50_000, "int32", _XLA, max_trials=6, repeats=2
    )
    assert res.best_us <= res.default_us
    assert res.trials and res.trials[0].label == "base"
    assert res.speedup >= 1.0


def test_autotune_candidate_space_valid_and_deterministic():
    cands = autotune_mod.candidate_space(_XLA, 100_000, max_trials=16)
    cands2 = autotune_mod.candidate_space(_XLA, 100_000, max_trials=16)
    assert [c.label for c in cands] == [c.label for c in cands2]
    assert 2 <= len(cands) <= 16
    assert cands[0].cfg.tile == _XLA.tile and cands[0].cfg.s == _XLA.s
    for c in cands:
        assert c.cfg.s <= c.cfg.tile and c.cfg.tile % c.cfg.s == 0
