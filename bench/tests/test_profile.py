"""The reduction from a trace to the per-layer metrics, on a synthetic
trace built here (CPU only)."""

import pytest

from harness import profile
from harness.cell import load_module

KERNEL = ('%sort_tiles_kv.1 = (u32[64,4096]{1,0:T(8,128)}, '
          's32[64,4096]{1,0:T(8,128)S(1)}) custom-call('
          'u32[64,4096]{1,0:T(8,128)} %a, s32[64,4096]{1,0:T(8,128)S(1)} %b), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{u32[64,4096]{1,0}, s32[64,4096]{1,0}}')
GATHER = ('%fusion.7 = s32[1,262144]{1,0:T(1,128)} fusion('
          's32[262144]{0:T(1024)} %p, s32[1,262144]{1,0:T(1,128)} %q), '
          'kind=kLoop, calls=%fused_computation.7')
ALL_TO_ALL = ('%all-to-all.2 = s32[4,1024]{1,0:T(4,128)} all-to-all('
              's32[4,1024]{1,0:T(4,128)} %fusion.3), dimensions={0}')
PEAKS = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def _escape(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def _plane(pid, name, line, events):
    """events: (name, start_ns, dur_ns); one line per plane."""
    names = sorted({e[0] for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1000)} "
        f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in events)
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{_escape(n)}" }} }}\n'
        for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
            f'{meta}}}\n')


def _reading(device_events, host_events, calls=2):
    from jax.profiler import ProfileData

    text = "".join(
        _plane(i + 1, f"/device:TPU:{d}", "XLA Ops", evs)
        for i, (d, evs) in enumerate(sorted(device_events.items())))
    text += _plane(99, "/host:CPU", "python", host_events)
    trace = profile.read_trace(ProfileData.from_text_proto(text))
    spans = trace.spans["bench.call"]
    return profile.Reading(trace=trace, calls=len(spans),
                           window=(spans[0][0], spans[-1][1]),
                           layers=profile.load_layers(), peaks=PEAKS)


# Two calls in [0, 1000] and [1000, 2000] ns.  Device 0 runs a gather,
# a kernel and an all-to-all per call; device 1 only the gathers.
DEVICE = {
    0: [(GATHER, 100, 500), (KERNEL, 600, 200), (ALL_TO_ALL, 850, 50),
        (GATHER, 1100, 500), (KERNEL, 1600, 200), (ALL_TO_ALL, 1850, 50)],
    1: [(GATHER, 100, 300), (GATHER, 1100, 300)],
}
HOST = [("bench.call", 0, 1000), ("bench.dispatch", 0, 100),
        ("bench.wait", 100, 900), ("bench.call", 1000, 1000),
        ("bench.dispatch", 1000, 60), ("bench.wait", 1060, 940),
        ("other.span", 0, 10)]


@pytest.fixture(scope="module")
def reading():
    return _reading(DEVICE, HOST)


def test_union_and_gaps():
    assert profile.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert profile.union_ns([]) == 0
    assert profile.gaps([(2, 4), (3, 6)], 0, 10) == [(0, 2), (6, 10)]


def test_trace_reads_devices_ops_and_bench_spans(reading):
    assert reading.trace.devices == (0, 1)
    assert len(reading.trace.ops) == 8
    assert set(reading.trace.spans) == {"bench.call", "bench.dispatch",
                                        "bench.wait"}
    assert reading.window == (0, 2000) and reading.calls == 2


def test_layer_classification_by_pattern():
    layers = profile.load_layers()
    assert profile.classify(KERNEL, layers) == "kernels"
    assert profile.classify(ALL_TO_ALL, layers) == "mesh"
    assert profile.classify(GATHER, layers) == profile.EXECUTOR
    # an op that only reads the collective's result is not one
    reader = "%f.1 = s32[4]{0} fusion(s32[4]{0} %all-to-all.2), kind=kLoop"
    assert profile.classify(reader, layers) == profile.EXECUTOR


def test_call_bytes_counts_hbm_results_and_operands_only():
    hbm = 64 * 4096 * 4
    # results: u32 in HBM, s32 in VMEM (S(1)); operands likewise; the
    # operand_layout_constraints attribute is not an operand.
    assert profile.call_bytes(KERNEL) == 2 * hbm
    assert profile.call_bytes(KERNEL, hbm_only=False) == 4 * hbm
    assert profile.call_bytes("%p = s32[] parameter(0)") == 4
    assert profile.call_bytes("not an instruction") == 0


def test_busy_union_and_idle_share(reading):
    busy = reading.busy_ns()
    assert busy == {0: 1500, 1: 600}
    idle = load_module("metrics", "device_idle_share").read(reading)
    assert idle == pytest.approx(100 * (1 - 1500 / 2000))


def test_layer_times_per_call(reading):
    ms = 1e6
    assert load_module("metrics", "xla_ms").read(reading) == pytest.approx(
        1000 / 2 / ms)
    assert load_module("metrics", "pallas_ms").read(reading) == (
        pytest.approx(400 / 2 / ms))
    assert load_module("metrics", "collective_ms").read(reading) == (
        pytest.approx(100 / 2 / ms))
    assert load_module("metrics", "dispatch_ms").read(reading) == (
        pytest.approx((100 + 60) / 2 / ms))


def test_pallas_roofline(reading):
    least_ns = 2 * (64 * 4096 * 4) / PEAKS["hbm_bytes_per_s"] * 1e9
    want = 100 * 2 * least_ns / 400
    got = load_module("metrics", "pallas_roofline").read(reading)
    assert got == pytest.approx(want)


def test_readers_find_nothing_to_read():
    r = _reading({0: [(GATHER, 100, 500)]}, [("bench.call", 0, 1000)])
    for name in ("pallas_ms", "pallas_roofline", "collective_ms",
                 "dispatch_ms"):
        assert load_module("metrics", name).read(r) is None, name
    assert load_module("metrics", "xla_ms").read(r) == pytest.approx(5e-4)


def test_breakdown_names_ops_and_idle_gaps(reading):
    b = reading.breakdown()
    top_name, top_s = b["device_ops"][0]
    assert top_name.startswith("%fusion.7 = s32[1,262144]")
    assert top_s == pytest.approx((1000 + 600) / 2 / 1e9)
    gap_name, gap_s = b["idle_gaps"][0]
    # device 1's longest gap, [400, 1100], is mostly inside bench.wait
    assert gap_name == "bench.wait TPU:1"
    assert gap_s == pytest.approx(700 / 1e9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
