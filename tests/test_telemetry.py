"""The sort names its own work: step scopes on the compiled ops, host
spans round the entry's phases, and counters of what a call does
(``core/telemetry.py``; PERF.md lists what reads each)."""

from __future__ import annotations

import contextlib
import pathlib
import re
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket_sort, faults, guard, telemetry
from repro.core.plan import build_plan
from repro.core.sort_config import SortConfig

# Two bucket levels at a few thousand keys: 4096 -> 16 buckets of 512
# -> 4 buckets of 256 each, sorted directly.
TWO_LEVEL = SortConfig(impl="xla", tile=256, s=16, direct_max=256)
N = 4096
STEPS = ("local_sort", "splitters", "partition", "relocate", "compact", "pad")
_OP = re.compile(r"= (\S+) (gather|scatter)\(.*op_name=\"([^\"]*)\"")


def _keys(n=N, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32))


def _innermost(op_name: str, kind: str) -> str | None:
    found = re.findall(rf"sort\.({kind})(?=/|$)", op_name)
    return found[-1] if found else None


def test_relocation_and_compaction_ops_carry_their_scopes():
    plan = build_plan(N, jnp.int32, TWO_LEVEL)
    assert plan.num_levels == 2
    text = jax.jit(lambda a: bucket_sort.sort_planned(a, plan)).lower(
        _keys()).compile().as_text()
    ops = []
    for line in text.splitlines():
        if re.search(r"= \S+ (gather|scatter)\(", line):
            m = _OP.search(line)
            assert m, f"gather or scatter without op_name: {line[:200]}"
            ops.append(m.groups())
    assert ops
    seen = set()
    for shape, _, op_name in ops:
        level = _innermost(op_name, r"level\d+")
        step = _innermost(op_name, "|".join(STEPS))
        assert level in ("level0", "level1"), op_name
        assert step is not None, op_name
        elements = np.prod([int(d) for d in
                            re.search(r"\[([\d,]*)\]", shape).group(1)
                            .split(",") if d])
        if elements >= N:  # the moves of whole arrays: keys and payloads
            assert step in ("relocate", "compact"), op_name
            seen.add((level, step))
    assert seen == {(lv, st) for lv in ("level0", "level1")
                    for st in ("relocate", "compact")}


def _host_spans(profile_dir: pathlib.Path, names) -> list[tuple]:
    from jax.profiler import ProfileData

    path = sorted(profile_dir.rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name in names)
    return sorted(spans)


def test_profile_holds_the_entry_spans_in_order(tmp_path):
    x = _keys()
    jax.block_until_ready(bucket_sort.argsort(x, TWO_LEVEL))  # compile
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(bucket_sort.argsort(x, TWO_LEVEL))
    phases = ("sort.plan", "sort.encode", "sort.launch", "sort.decode")
    spans = _host_spans(tmp_path, ("sort.argsort",) + phases)
    outer = [s for s in spans if s[2] == "sort.argsort"]
    assert len(outer) == 1
    lo, hi, _ = outer[0]
    inner = [s for s in spans if s[2] != "sort.argsort"]
    assert [s[2] for s in inner] == list(phases)
    assert all(lo <= s <= e <= hi for s, e, _ in inner)
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


@pytest.mark.parametrize("n,ratio", [(1 << 17, 3.0), (1 << 23, 9.046875)])
def test_default_plan_moved_elements(n, ratio):
    plan = build_plan(n, jnp.int32, SortConfig())
    assert plan.moved_elements == ratio * n
    assert plan.moved_elements is plan.moved_elements


def _counter_delta(fn):
    before = telemetry.counts()
    fn()
    after = telemetry.counts()
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in ("sort.keys", "sort.moved_elements")}


def test_call_counters_advance_by_the_plan():
    plan = build_plan(N, jnp.int32, TWO_LEVEL)
    delta = _counter_delta(lambda: jax.block_until_ready(
        bucket_sort.argsort(_keys(), TWO_LEVEL)))
    assert delta == {"sort.keys": N,
                     "sort.moved_elements": plan.moved_elements}
    # 4096 -> 16 x 512 buckets, 16 x 512 -> 64 x 256, and both compactions
    assert plan.moved_elements == 16 * 512 + N + 64 * 256 + 16 * 512


@pytest.mark.parametrize("n,blocked", [
    # the 2^17 sample round of the 2^23 plan is compacted by blocks too
    (1 << 17, 1 << 17), (1 << 23, (1 << 24) + (1 << 23) + (1 << 17))])
def test_default_plan_compact_blocked(n, blocked):
    """Every bucket round of a default plan compacts by 128-lane blocks:
    ``blocked`` elements per array a call, the compaction's share of
    ``sort.moved_elements``."""
    plan = build_plan(n, jnp.int32, SortConfig())
    nodes, todo = [], [plan.root]
    while todo:
        node = todo.pop()
        if node is not None and node.kind == "bucket":
            nodes.append(node)
            todo += [node.sample_plan, node.bucket_plan]
    assert {node.compact_block for node in nodes} == {128}
    assert sum(node.elements for node in nodes) == blocked


def test_keys_counter_leaves_out_segment_padding():
    offsets = [0, 100, 1000, 1003, 2500]  # rows pad to the longest, 1500
    x = _keys(offsets[-1])
    delta = _counter_delta(lambda: jax.block_until_ready(
        bucket_sort.segment_argsort(x, offsets, TWO_LEVEL)))
    assert delta["sort.keys"] == offsets[-1]


def test_counters_under_jit_count_traces_not_calls():
    x = _keys(N - 64)  # a fresh length, so the outer function traces
    f = jax.jit(lambda k: bucket_sort.argsort(k, TWO_LEVEL))
    first = _counter_delta(lambda: jax.block_until_ready(f(x)))
    again = _counter_delta(lambda: jax.block_until_ready(f(x)))
    assert first["sort.keys"] == N - 64
    assert again == {"sort.keys": 0, "sort.moved_elements": 0}


def test_degraded_retry_shows_as_a_second_launch(monkeypatch):
    opened = []

    @contextlib.contextmanager
    def recording(name):
        opened.append(name)
        yield

    monkeypatch.setattr(telemetry, "span", recording)
    x = _keys(N + 128)  # a fresh length, so the executor is traced anew
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", guard.DegradationWarning)
        with faults.inject("kernel.launch", on_hit=1, count=1):
            out = bucket_sort.sort(x, TWO_LEVEL)
    np.testing.assert_array_equal(np.asarray(out), np.sort(np.asarray(x)))
    assert opened[0] == "sort.sort"
    assert opened.count("sort.launch") == 2


def test_counters_lose_no_update_across_threads():
    name = "test.threads"
    start = telemetry.counts().get(name, 0)
    per_thread, threads = 2000, 16

    def work():
        for _ in range(per_thread):
            telemetry.count(name)

    pool = [threading.Thread(target=work) for _ in range(threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    assert telemetry.counts()[name] - start == per_thread * threads
