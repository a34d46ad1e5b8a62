"""From a profiler trace to the numbers the per-layer metrics read.

A TPU trace (``*.xplane.pb``) holds one plane per chip
(``/device:TPU:<i>``) whose ``XLA Ops`` line has one event per executed
HLO instruction, named by the instruction's full text
(``%name = shape opcode(operands), attributes``), and one host plane
(``/host:CPU``) that holds the benchmark's own ``bench.*`` spans.  Both
use one clock, in nanoseconds.

The layer of an op is decided by the regular expressions of
``bench/layers.json``, tried in order; an op that none matches belongs
to the executor (XLA's own ops between kernels).
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
EXECUTOR = "executor"

_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
# One array shape of HLO text: dtype[dims] with an optional layout,
# whose ``S(n)`` names the memory space (none or S(0): HBM).
_SHAPE = re.compile(
    r"\b(" + "|".join(_ITEMSIZE) + r")\[([\d,]*)\](\{[^{}]*\})?")
_MEMORY_SPACE = re.compile(r"S\((\d+)\)")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device event: the chip it ran on and its interval."""

    device: int
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Trace:
    """What the reduction needs of one trace."""

    ops: tuple[Op, ...]        # the XLA Ops lines of every chip
    async_ops: tuple[Op, ...]  # the Async XLA Ops lines (DMAs, collectives)
    spans: dict                # host span name -> [(start_ns, end_ns)]
    devices: tuple[int, ...]


def read_trace(profile, span_prefix: str = "bench.") -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    ops, async_ops, spans, devices = [], [], {}, set()
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            devices.add(dev)
            for line in plane.lines:
                into = {OPS_LINE: ops, ASYNC_LINE: async_ops}.get(line.name)
                if into is None:
                    continue
                into.extend(Op(dev, e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    for v in spans.values():
        v.sort()
    return Trace(tuple(ops), tuple(async_ops), spans, tuple(sorted(devices)))


def load_trace_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    return read_trace(ProfileData.from_file(str(path)))


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _shape_bytes(text: str, hbm_only: bool) -> int:
    total = 0
    for dtype, dims, layout in _SHAPE.findall(text):
        space = _MEMORY_SPACE.search(layout or "")
        if hbm_only and space and space.group(1) != "0":
            continue
        total += _ITEMSIZE[dtype] * math.prod(
            int(d) for d in dims.split(",") if d)
    return total


def _operand_text(instruction: str, opcode_at: int) -> str:
    """The text of the operand list that opens at ``opcode_at``."""
    depth = 0
    for i in range(opcode_at, len(instruction)):
        c = instruction[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return instruction[opcode_at + 1:i]
    return instruction[opcode_at + 1:]


def call_bytes(instruction: str, hbm_only: bool = True) -> int:
    """Bytes of the results and operands of one HLO instruction's text.

    ``hbm_only`` leaves out the arrays the layout places in another
    memory space than HBM (``S(1)``: the chip's VMEM).  Attributes after
    the operand list, such as ``operand_layout_constraints``, are not
    counted."""
    head, sep, rest = instruction.partition(" = ")
    if not sep:
        return 0
    m = re.search(r" [a-z][a-z0-9-]*\(", rest)
    if m is None:
        return 0
    results = rest[:m.start()]
    operands = _operand_text(rest, m.end() - 1)
    return _shape_bytes(results, hbm_only) + _shape_bytes(operands, hbm_only)


def load_layers(path: Path = BENCH_DIR / "layers.json") -> dict:
    """Layer name -> compiled pattern, in the order they are tried."""
    spec = json.loads(Path(path).read_text())
    return {name: re.compile(v["pattern"]) for name, v in spec.items()
            if not name.startswith("_")}


def classify(name: str, layers: dict) -> str:
    for layer, pattern in layers.items():
        if pattern.search(name):
            return layer
    return EXECUTOR


def short_name(name: str, width: int = 96) -> str:
    """An instruction's name and result type, cut to ``width``."""
    head, _, rest = name.partition(" = ")
    m = re.search(r" [a-z][a-z0-9-]*\(", rest)
    kind = rest[:m.start() + len(m.group(0)) - 1] if m else rest
    return f"{head} = {kind}"[:width]


@dataclasses.dataclass(frozen=True)
class Reading:
    """One traced stretch of a run, as the metric readers see it."""

    trace: Trace
    calls: int                      # entry calls in the traced stretch
    window: tuple[float, float]     # first call start .. last call end
    layers: dict                    # layer -> pattern (layers.json)
    peaks: dict                     # this chip's row of peaks.json

    def ops_in_window(self, async_ops: bool = False):
        lo, hi = self.window
        src = self.trace.async_ops if async_ops else self.trace.ops
        return [o for o in src if o.end_ns > lo and o.start_ns < hi]

    def layer_ns(self, layer: str, async_ops: bool = False) -> dict:
        """Device -> ns of that layer's ops (union: nothing counts twice)."""
        per: dict = {d: [] for d in self.trace.devices}
        for o in self.ops_in_window(async_ops):
            if classify(o.name, self.layers) == layer:
                per[o.device].append((o.start_ns, o.end_ns))
        return {d: union_ns(v) for d, v in per.items()}

    def busy_ns(self) -> dict:
        """Device -> ns in which an XLA op ran, within the window."""
        lo, hi = self.window
        per: dict = {d: [] for d in self.trace.devices}
        for o in self.ops_in_window():
            per[o.device].append((max(o.start_ns, lo), min(o.end_ns, hi)))
        return {d: union_ns(v) for d, v in per.items()}

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def host_activity(self, t: float) -> str:
        """The benchmark span the host was in at ``t``, innermost first."""
        for name in ("bench.dispatch", "bench.wait", "bench.call"):
            for s, e in self.trace.spans.get(name, ()):
                if s <= t <= e:
                    return name
        return "bench.loop"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (seconds per chip, summed
        over the traced calls) and the longest idle gaps, each named by
        what the host was doing in it."""
        ndev = max(len(self.trace.devices), 1)
        by_name: dict = {}
        for o in self.ops_in_window():
            key = short_name(o.name)
            by_name[key] = by_name.get(key, 0.0) + o.dur_ns
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.window
        idle = []
        for d in self.trace.devices:
            iv = [(o.start_ns, o.end_ns) for o in self.ops_in_window()
                  if o.device == d]
            for s, e in gaps(iv, lo, hi):
                idle.append((f"{self.host_activity((s + e) / 2)} "
                             f"TPU:{d}", e - s))
        idle.sort(key=lambda kv: -kv[1])
        return {
            "device_ops": [[k, v / ndev / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in idle[:top]],
        }
