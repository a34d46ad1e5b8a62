"""ShardPlan IR tests: capacity-bound math, deal-round sampling
property, build determinism, serialization, validation messages, and
the shard-plan file round-trip.

Everything here is HOST math (``shard_geometry`` / ``build_shard_plan``
/ the numpy deal simulation) — no device mesh is needed, so these run
in the main 1-CPU pytest process.  The executor-side counterparts
(conformance, trace discipline, cache-hit zero-retrace) live in
``tests/test_distributed.py`` behind the subprocess harness.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import autotune as autotune_mod
from repro.core.distributed_sort import DistSortSpec
from repro.core.plan import (
    build_shard_plan,
    shard_geometry,
    shard_plan_from_dict,
    shard_plan_to_dict,
)
from repro.core.sort_config import SortConfig

_XLA = SortConfig(tile=256, s=16, direct_max=512, impl="xla")


# ----------------------------------------------------------------------
# Capacity-bound invariants (DESIGN.md §9) over random geometry
# ----------------------------------------------------------------------


def _assert_geometry_invariants(n_local, d, oversample, pair_align):
    g = shard_geometry(n_local, d, oversample, pair_align)
    # sampling geometry: s_loc samples spaced exactly n_pad/s_loc apart
    assert g.s_loc == oversample * d
    assert g.n_pad >= n_local and g.n_pad % g.s_loc == 0
    assert g.n_pad - n_local < g.s_loc, "n_pad padding not minimal"
    assert g.n_pad % d == 0, "deal needs n_pad divisible by d"
    # the paper's bucket bound: B_t <= n_pad * (1 + 1/c), exactly
    assert g.b_t == g.n_pad + g.n_pad // oversample
    assert g.b_t <= g.n_pad * (1 + 1 / oversample)
    # deal bound: per-pair chunk <= ceil(B_t/D) + D, lane-aligned with
    # EXACT padding (less than one alignment unit of slack)
    raw = -(-g.b_t // d) + d
    assert g.c_pair >= raw and g.c_pair % pair_align == 0
    assert g.c_pair - raw < pair_align, "c_pair padding not exact"
    # out_cap covers any achievable bucket total (<= B_t) and never
    # exceeds what the exchange can deliver (d * c_pair)
    assert g.out_cap >= g.b_t
    assert g.out_cap <= d * g.c_pair


def _random_geometry(seed):
    r = np.random.default_rng(seed)
    return (
        int(r.integers(1, 100_000)),
        int(r.integers(2, 33)),  # d need not be a power of two
        int(2 ** r.integers(0, 7)),
        int(2 ** r.integers(3, 9)),
    )


try:  # optional dev dep (pip install -e '.[test]')
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(
        st.integers(min_value=1, max_value=100_000),
        st.integers(min_value=2, max_value=32),
        st.integers(min_value=0, max_value=6),
        st.integers(min_value=3, max_value=8),
    )
    def test_shard_geometry_capacity_invariants(n_local, d, oexp, paexp):
        _assert_geometry_invariants(n_local, d, 2**oexp, 2**paexp)

except ModuleNotFoundError:  # seeded fallback keeps the invariant tested

    @pytest.mark.parametrize("seed", range(16))
    def test_shard_geometry_capacity_invariants(seed):
        _assert_geometry_invariants(*_random_geometry(seed))


def test_spec_delegates_to_shard_geometry():
    """DistSortSpec is the minimal arithmetic view — every derived
    capacity must agree with the single source of truth."""
    for seed in range(8):
        n_local, d, oversample, pair_align = _random_geometry(seed)
        spec = DistSortSpec("data", d, n_local, oversample, pair_align)
        g = shard_geometry(n_local, d, oversample, pair_align)
        assert (spec.s_loc, spec.n_pad, spec.b_t, spec.c_pair, spec.out_cap) \
            == (g.s_loc, g.n_pad, g.b_t, g.c_pair, g.out_cap)
        plan = build_shard_plan(
            "data", d, n_local, "int32", _XLA,
            oversample=oversample, pair_align=pair_align,
        )
        assert (plan.s_loc, plan.n_pad, plan.b_t, plan.c_pair, plan.out_cap) \
            == (g.s_loc, g.n_pad, g.b_t, g.c_pair, g.out_cap)


# ----------------------------------------------------------------------
# Deal round: numpy simulation of the executor's reshape/swapaxes
# transpose — every device must receive a stride-D regular sample of
# every source's sorted run (what the capacity proof relies on)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_deal_round_leaves_stride_d_regular_samples(seed):
    r = np.random.default_rng(seed)
    d = int(2 ** r.integers(1, 4))
    oversample = int(2 ** r.integers(0, 4))
    g = shard_geometry(int(r.integers(1, 5000)), d, oversample)
    runs = [np.sort(r.integers(0, 2**31, g.n_pad)) for _ in range(d)]
    for t in range(d):
        # _deal_all_to_all: x.reshape(n_pad//d, d).swapaxes(0,1) then
        # all_to_all(split=0) -> device t holds row t of every source
        received = [
            x.reshape(g.n_pad // d, d).swapaxes(0, 1)[t] for x in runs
        ]
        for j, chunk in enumerate(received):
            np.testing.assert_array_equal(chunk, runs[j][t::d])
            assert (chunk[1:] >= chunk[:-1]).all(), "sample not sorted"
            assert chunk.shape == (g.n_pad // d,)


# ----------------------------------------------------------------------
# Plan build: determinism, memoization, signatures
# ----------------------------------------------------------------------


def test_build_shard_plan_deterministic_and_memoized():
    a = build_shard_plan("data", 4, 2048, "int32", _XLA)
    b = build_shard_plan(("data",), 4, 2048, "int32", _XLA)
    assert a is b, "axis-normalized rebuild must hit the assembly memo"
    assert a == b and hash(a) == hash(b)
    # per-phase sub-plans carry the strategy dispatch of the config
    radix = build_shard_plan(
        "data", 4, 2048, "int32",
        dataclasses.replace(_XLA, strategy="radix"),
    )
    assert radix.run_plan.root.strategy == "radix"
    assert radix != a and radix.signature() != a.signature()


def test_default_mesh_bucket_phase_goes_direct_at_level_one():
    """The 4-chip benchmark cell's plan (2^23 keys a chip, default
    config): ``c_pair``'s slack makes the bucket phase's level-1
    buckets 9344 long, which the default ``direct_max`` (16384) sorts
    directly, so that phase has two bucket levels, not three."""
    plan = build_shard_plan("data", 4, 1 << 23, "int32",
                            SortConfig(impl="pallas"))
    phases = (plan.run_plan, plan.dealt_plan, plan.sample_plan,
              plan.bucket_plan)
    assert [p.num_levels for p in phases] == [2, 2, 0, 2]
    child = plan.bucket_plan.root.bucket_plan.bucket_plan
    assert child.kind == "direct"
    assert (child.rows, child.length, child.lp, child.block_rows) == (
        4096, 9344, 16384, 32)
    assert plan.moved_elements == sum(p.moved_elements for p in phases)
    assert plan.d * plan.moved_elements / (1 << 25) == 28.3681640625


def test_shard_plan_signature_separates_schedules():
    base = build_shard_plan("data", 4, 2048, "int32", _XLA)
    for other in (
        build_shard_plan("data", 4, 2048, "int32", _XLA, oversample=4),
        build_shard_plan("data", 4, 2048, "int32", _XLA, pair_align=128),
        build_shard_plan("data", 4, 2048, "uint32", _XLA),
        build_shard_plan("data", 4, 1024, "int32", _XLA),
        build_shard_plan(("data", "model"), 4, 2048, "int32", _XLA),
        build_shard_plan(
            "data", 4, 2048, "int32",
            dataclasses.replace(_XLA, descending=True),
        ),
    ):
        assert other.signature() != base.signature()
        assert autotune_mod.shard_cache_key(other) \
            != autotune_mod.shard_cache_key(base)


def test_shard_cache_key_namespace_disjoint_from_sort_plans():
    p = build_shard_plan("data", 2, 64, "int32", _XLA)
    key = autotune_mod.shard_cache_key(p)
    assert key.startswith("shard|")
    assert "data" in key and "int32" in key


def test_shard_candidate_space_base_first_covers_all_axes():
    cands = autotune_mod.shard_candidate_space(_XLA, max_trials=16)
    assert cands[0].label == "base"
    assert cands[0].oversample == 8 and cands[0].pair_align == 8
    labels = [c.label for c in cands]
    assert any(l.startswith("strategy=") for l in labels)
    assert any(l.startswith("oversample=") for l in labels)
    assert any(l.startswith("pair_align=") for l in labels)
    assert len(set(labels)) == len(labels), "candidate space has dupes"
    # deterministic, every candidate pins plan="default" (no recursion)
    assert cands == autotune_mod.shard_candidate_space(_XLA, max_trials=16)
    assert all(c.cfg.plan == "default" for c in cands)


# ----------------------------------------------------------------------
# Serialization + file round-trip
# ----------------------------------------------------------------------


def test_shard_plan_dict_roundtrip_identical():
    p = build_shard_plan(
        ("data", "model"), 8, 1000, "float32",
        SortConfig(tile=256, s=16, direct_max=512, impl="xla",
                   descending=True),
        oversample=4, pair_align=128,
    )
    q = shard_plan_from_dict(json.loads(json.dumps(shard_plan_to_dict(p))))
    assert q == p and hash(q) == hash(p)
    assert q.run_plan == p.run_plan and q.bucket_plan == p.bucket_plan


def test_shard_plan_from_dict_rejects_bad_schema():
    d = shard_plan_to_dict(build_shard_plan("data", 2, 64, "int32", _XLA))
    d["schema"] = "shard_plan/v0"
    with pytest.raises(ValueError, match="shard_plan/v1"):
        shard_plan_from_dict(d)


def test_save_load_shard_plan_roundtrip(tmp_path):
    p = build_shard_plan("data", 4, 2048, "int32", _XLA)
    path = str(tmp_path / "shard.json")
    autotune_mod.save_shard_plan(p, path, meta={"note": "unit test"})
    assert autotune_mod.load_shard_plan(path) == p
    # the checked load make_sharded_sort performs for plan=<path>
    assert autotune_mod.load_shard_plan(
        path, axis="data", d=4, n_local=2048, dtype="int32", cfg=_XLA
    ) == p


def test_load_shard_plan_rejects_signature_mismatch(tmp_path):
    path = str(tmp_path / "shard.json")
    autotune_mod.save_shard_plan(
        build_shard_plan("data", 4, 2048, "int32", _XLA), path
    )
    with pytest.raises(ValueError, match="was built for"):
        autotune_mod.load_shard_plan(
            path, axis="data", d=8, n_local=1024, dtype="int32", cfg=_XLA
        )


# ----------------------------------------------------------------------
# Validation: field-naming ValueErrors at plan-build time
# ----------------------------------------------------------------------


@pytest.mark.parametrize("kw,match", [
    (dict(n_local=0), "n_local must be an int >= 1"),
    (dict(d=1), "d must be an int >= 2"),
    (dict(oversample=3), "oversample must be a power of two"),
    (dict(oversample=0), "oversample must be a power of two"),
    (dict(pair_align=4), "pair_align must be a power of two >= 8"),
    (dict(pair_align=12), "pair_align must be a power of two >= 8"),
])
def test_shard_geometry_validation_names_field(kw, match):
    base = dict(n_local=1024, d=4, oversample=8, pair_align=8)
    with pytest.raises(ValueError, match=match):
        shard_geometry(**{**base, **kw})


def test_build_shard_plan_validates_before_tracing():
    with pytest.raises(ValueError, match="oversample must be a power of two"):
        build_shard_plan("data", 4, 1024, "int32", _XLA, oversample=6)
    with pytest.raises(ValueError, match="pair_align"):
        build_shard_plan("data", 4, 1024, "int32", _XLA, pair_align=2)
