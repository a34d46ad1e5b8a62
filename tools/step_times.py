"""Device time per plan level and step of one sort, read off a profile.

    python tools/step_times.py --log2n 23 --calls 3 [--seed 0] [--out FILE]

Sorts uniform int32 keys with ``bucket_sort.argsort`` and the default
``SortConfig``: one call to compile, then ``--calls`` calls under
``jax.profiler.trace``.  Prints one JSON object (and writes it to
``--out``) with, per call:

  by_step   device ms of the executor's ops by plan level and step
            scope (``sort.level<d>`` / ``sort.relocate`` ...), plus
            ``unscoped`` (ops whose ``op_name`` has no step scope, or
            that have none) and ``unmatched`` (ops not found in the
            compiled text)
  host_ms   mean ms of each ``sort.*`` host span of the entry
  modules   executables launched, from the ``XLA Modules`` line

A TPU trace names each device op by its HLO text without the
``op_name`` metadata that holds the scopes.  So each op that runs
inside a ``jit__sort_canonical_packed`` module is joined, by
instruction name, with ``compiled.as_text()`` of the same program,
whose metadata has the scope path.  Ops outside that module (the
entry's eager ops) are left out of ``by_step``: they reuse names such
as ``%iota.1``.  The device part needs a TPU trace; elsewhere it is
empty.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import pathlib
import re
import sys
import tempfile

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

EXECUTOR_MODULE = "jit__sort_canonical_packed"
DEVICE_PLANE = "/device:TPU:0"  # the first chip
HOST_PLANE = "/host:CPU"
STEPS = ("local_sort", "splitters", "partition", "relocate", "compact", "pad")
HOST_SPANS = ("sort.argsort", "sort.plan", "sort.encode", "sort.launch",
              "sort.decode")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r"\bop_name=\"([^\"]*)\"")
_EVENT_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)")
_LEVEL = re.compile(r"sort\.(level\d+)(?=/|$)")
_STEP = re.compile(r"sort\.(" + "|".join(STEPS) + r")(?=/|$)")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata ("" where it has none),
    from HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op_name = _OP_NAME.search(line)
            out[m.group(1)] = op_name.group(1) if op_name else ""
    return out


def scope_of(op_name: str) -> str:
    """``sort.level<d>/sort.<step>`` of the innermost level and step
    scopes in ``op_name``, or ``unscoped``."""
    levels, steps = _LEVEL.findall(op_name), _STEP.findall(op_name)
    if not levels or not steps:
        return "unscoped"
    return f"sort.{levels[-1]}/sort.{steps[-1]}"


def step_times(ops, modules, names: dict[str, str], calls: int) -> dict:
    """Device ms per call by scope.

    Args:
        ops: (event name, start_ns, duration_ns) of the ``XLA Ops`` line.
        modules: (module name, start_ns, duration_ns) of ``XLA Modules``.
        names: :func:`op_names` of the executor's compiled text.
        calls: calls the trace holds.
    """
    spans = sorted((s, s + d) for n, s, d in modules
                   if n.startswith(EXECUTOR_MODULE))
    starts = [s for s, _ in spans]
    ns = collections.Counter()
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= spans[i][1]:
            continue
        instr = _EVENT_NAME.match(name).group(1)
        if instr not in names:
            ns["unmatched"] += dur
        else:
            ns[scope_of(names[instr])] += dur
    return {k: v / 1e6 / calls for k, v in sorted(ns.items())}


def read_profile(profile, names: dict[str, str], calls: int) -> dict:
    """by_step, host_ms and modules of a ``jax.profiler.ProfileData``;
    ``tpu_plane`` says whether it held the first chip's plane."""
    ops, modules, host = [], [], collections.defaultdict(float)
    tpu = False
    for plane in profile.planes:
        if plane.name == DEVICE_PLANE:
            tpu = True
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if into is not None:
                    into.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host[e.name] += e.duration_ns
    return {
        "tpu_plane": tpu,
        "by_step": step_times(ops, modules, names, calls),
        "host_ms": {k: host[k] / 1e6 / calls for k in HOST_SPANS},
        "modules": len(modules) / calls,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--log2n", type=int, default=17)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData

    from repro import compile_cache
    from repro.core import bucket_sort
    from repro.core.sort_config import SortConfig

    compile_cache.enable()
    n = 1 << args.log2n
    keys = jnp.asarray(np.random.default_rng(args.seed).integers(
        -2**31, 2**31, n, dtype=np.int64).astype(np.int32))
    plan = bucket_sort.resolve_plan(n, keys.dtype, SortConfig())
    words = (jax.ShapeDtypeStruct((1, n), jnp.uint32),)
    vals = jax.ShapeDtypeStruct((1, n), jnp.int32)
    text = bucket_sort._sort_canonical_packed.lower(
        words, vals, plan=plan, pad_base0=n).compile().as_text()
    names = op_names(text)
    jax.block_until_ready(bucket_sort.argsort(keys))  # compile, warm up

    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for _ in range(args.calls):
                jax.block_until_ready(bucket_sort.argsort(keys))
        finally:
            jax.profiler.stop_trace()
        path = sorted(pathlib.Path(d).rglob("*.xplane.pb"))[-1]
        result = read_profile(ProfileData.from_file(str(path)), names,
                              args.calls)
    result = {"n": n, "calls": args.calls, "backend": jax.default_backend(),
              "compiled_instructions": len(names), **result}
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
