"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run finds its cell in ``BENCHMARK.json`` and the cell's files under
``bench/`` (``harness/cell.py``), makes a pool of key arrays on the
device from ``--seed``, calls the configuration's entry once to warm
it up (set-up ends there), and then calls it in a closed loop with one
caller: call, ``block_until_ready``, repeat, until ``--seconds`` have
passed.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces a short stretch of calls (``TRACE_SECONDS``, at
least ``TRACE_MIN_CALLS``) with ``jax.profiler`` and reports the cell's
per-layer metrics, read by ``bench/metrics/<name>.py``.

After the loop, a sample of the calls' results drawn from the seed is
compared with the configuration's plain reference; the run is
``correct`` when every number under ``checks`` is within its limit.
The last stdout line is one JSON object; the checks are also the last
lines on stderr.  Without a TPU, with fewer chips than the cell asks
for, or on a chip that ``bench/peaks.json`` does not list, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from harness.cell import load_cell, load_module  # noqa: E402

TRACE_SECONDS = 3.0
TRACE_MIN_CALLS = 2
SAMPLE = 8  # results compared with the reference per run


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; the run prints no result."""


class Sample:
    """A uniform sample of ``k`` of the results offered (reservoir),
    drawn from a seeded generator.

    An item is ``(which, out)``.  A kept ``out`` starts its copy to the
    host at once and is held there from the next offer on, so the
    sample keeps at most one result in device memory."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0
        self.on_device: list[int] = []

    def offer(self, item) -> None:
        import jax

        for slot in self.on_device:
            which, out = self.items[slot]
            self.items[slot] = (which, jax.tree.map(np.asarray, out))
        self.on_device = []
        self.seen += 1
        if len(self.items) < self.k:
            slot = len(self.items)
            self.items.append(None)
        else:
            slot = int(self.rng.integers(self.seen))
            if slot >= self.k:
                return
        jax.tree.map(lambda a: a.copy_to_host_async(), item[1])
        self.items[slot] = item
        self.on_device.append(slot)


class CompileCounter:
    """Counts JAX's compile and compile-cache events while armed."""

    def __init__(self):
        from jax import monitoring

        self.armed, self.count = False, 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kwargs):
        if self.armed and name.startswith(
                ("/jax/core/compile/", "/jax/compilation_cache/")):
            self.count += 1


def _phase(name: str) -> None:
    """Log when a set-up phase ended, in seconds since the process began."""
    print(f"setup {name} done at {time.perf_counter() - T_PROCESS:.3f} s",
          file=sys.stderr, flush=True)


def closed_loop(entry, inputs, seconds: float, sample: Sample,
                min_calls: int = 1) -> dict:
    """Call the entry on the pool in turn until ``seconds`` have passed
    and at least ``min_calls`` calls are done; each call ends with
    ``block_until_ready``."""
    import jax

    call_s = []
    start = time.perf_counter()
    deadline = start + seconds
    i = 0
    while True:
        which = i % len(inputs)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = entry(inputs[which])
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(out)
        t2 = time.perf_counter()
        call_s.append(t2 - t0)
        sample.offer((which, out))
        del out
        i += 1
        if t2 >= deadline and i >= min_calls:
            break
    return {"calls": i, "elapsed_s": t2 - start, "call_s": call_s}


def compare(entry, inputs, sample: Sample, reference) -> dict:
    """Compare the sampled results with the plain reference, once the
    window has closed.  Returns the positions that differ, summed over
    the results compared, and how many results differed."""
    refs: dict = {}
    mismatched = failed = 0
    for which, out in sample.items:
        got = entry.permutation(out)
        if which not in refs:
            refs[which] = reference(np.asarray(inputs[which]))
        want = refs[which]
        bad = (want.size if got.shape != want.shape
               else int(np.count_nonzero(got != want)))
        mismatched += bad
        failed += int(bad > 0)
    return {"mismatched": mismatched, "failed": failed,
            "compared": len(sample.items)}


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _traced(entry, inputs, seconds, sample, cell, peaks) -> tuple:
    """The traced stretch: (loop result, per-layer metrics, device
    busy/window seconds, breakdown)."""
    import jax

    from harness import profile

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python calls would slow the host
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            loop = closed_loop(entry, inputs, min(seconds, TRACE_SECONDS),
                               sample, TRACE_MIN_CALLS)
        finally:
            jax.profiler.stop_trace()
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        trace = profile.load_trace_file(files[-1])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    calls = trace.spans.get("bench.call", [])
    if not calls:
        raise RuntimeError("the trace holds no call")
    if not trace.devices:  # a CPU run: no device metric to read
        return loop, {}, {}, None
    reading = profile.Reading(
        trace=trace, calls=len(calls), window=(calls[0][0], calls[-1][1]),
        layers=profile.load_layers(), peaks=peaks)
    metrics = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = reading.busy_ns()
    device = {"busy_s": sum(busy.values()) / len(busy) / 1e9,
              "window_s": reading.window_ns / 1e9}
    return loop, metrics, device, reading.breakdown()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        on_chip: bool = True, wrap_entry=None,
        traffic_override: dict | None = None) -> dict:
    """One run of a cell; returns the result object.

    ``on_chip=False`` skips the look for a TPU and the native-plan
    check and takes no peaks table (for tests on the CPU);
    ``wrap_entry`` replaces the entry by ``wrap_entry(entry)`` and
    ``traffic_override`` changes the mix's parameters (tests only).

    Raises:
        NoChip: no TPU, fewer chips than the cell asks for, or a chip
            that ``bench/peaks.json`` does not list.
    """
    cell = load_cell(workload)
    traffic = {**cell.traffic, **(traffic_override or {})}

    import jax

    from repro import compile_cache
    from repro.core import guard

    _phase("imports")
    devices = jax.devices()
    _phase("backend")
    peaks = {}
    if on_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                         f"{len(devices)}")
        table = json.loads((BENCH_DIR / "peaks.json").read_text())
        if devices[0].device_kind not in table:
            raise NoChip(f"no peaks for device kind "
                         f"{devices[0].device_kind!r} in bench/peaks.json")
        peaks = table[devices[0].device_kind]
    used = devices[:cell.chips]
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    guard.clear_degradation_log()
    compiles = CompileCounter()

    from harness.keys import key_count, make_inputs

    config = cell.config
    n = key_count(config["dtype"], traffic)
    _phase("key count")
    entry = load_module("entries", config["entry"]).build(config, used, n)
    if wrap_entry is not None:
        entry = wrap_entry(entry)
    _phase("entry")
    inputs = make_inputs(seed, config["dtype"], traffic, entry.sharding)
    _phase("inputs")
    jax.block_until_ready(entry(inputs[0]))  # warm-up: compile or load
    _phase("warm-up")
    setup_s = time.perf_counter() - T_PROCESS

    sample = Sample(SAMPLE, np.random.default_rng(seed))
    traces_before = entry.trace_count()
    compiles.armed = True
    device, breakdown = {}, None
    if trace:
        loop, metrics, device, breakdown = _traced(
            entry, inputs, seconds, sample, cell, peaks)
    else:
        loop = closed_loop(entry, inputs, seconds, sample)
    compiles.armed = False
    retraces = entry.trace_count() - traces_before
    peak = _peak_bytes(used)
    if not trace:
        values = {
            "keys_per_s": n * loop["calls"] / loop["elapsed_s"],
            "call_ms_p95": float(np.percentile(loop["call_s"], 95)) * 1e3,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}

    reference = load_module("configs", config["reference"]).reference
    result = compare(entry, inputs, sample, reference)
    checks = {"mismatched_indices": result["mismatched"],
              **{k: v for k, v in entry.faults().items()
                 if on_chip or k != "non_native_plans"},
              "retraces_in_window": retraces,
              "compiles_in_window": compiles.count}
    correct = result["compared"] > 0 and all(v == 0 for v in checks.values())
    out = {
        "correct": correct,
        "attempted": loop["calls"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": peak,
                   **device},
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout (set before JAX is imported), so that later runs of a
    # cell load what the first one compiled.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
