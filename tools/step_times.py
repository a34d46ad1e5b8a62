"""Device time per plan level and step of one sort, read off a profile.

    python tools/step_times.py --log2n 23 --calls 3 [--seed 0] [--out FILE]
    python tools/step_times.py --log2n 25 --chips 4 --calls 2

Sorts 2^log2n uniform int32 keys with the default ``SortConfig``: on
one chip with ``bucket_sort.argsort``, or with ``--chips`` D > 1 with
``make_sharded_sort`` over the first D chips (one mesh axis, the keys
sharded evenly).  One call to compile, then ``--calls`` calls under
``jax.profiler.trace``.  Prints one JSON object (and writes it to
``--out``) with, per call:

  by_step   for each chip's plane (``/device:TPU:<i>``), device ms of
            the executor's ops by scope: plan level and step
            (``sort.level<d>/sort.relocate`` ...), under the mesh
            program's phase (``sort.phase_bucket/sort.level0/...``),
            and the mesh steps outside the local sorts
            (``sort.deal``, ``sort.partition``, ``sort.pack``,
            ``sort.exchange`` ...); collectives whose ``op_name`` was
            lost (an ``all_gather`` the compiler turned into an
            ``all-reduce``) as ``collective:<opcode>``; ``unscoped``
            (no step scope) and ``unmatched`` (not in the compiled
            text)
  host_ms   mean ms of each ``sort.*`` host span of the entry (one
            chip; the mesh calls run its compiled executable)
  modules   executables launched on the first chip, from its
            ``XLA Modules`` line

A TPU trace names each device op by its HLO text without the
``op_name`` metadata that holds the scopes.  So each op that runs
inside the executor's module (``jit__sort_canonical_packed``, or
``jit__sharded_argsort`` on a mesh) is joined, by instruction name,
with ``compiled.as_text()`` of the same program, whose metadata has the
scope path.  Ops outside that module (the entry's eager ops) are left
out of ``by_step``: they reuse names such as ``%iota.1``.  The device
part needs a TPU trace; elsewhere it is empty.
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
MESH_MODULE = "jit__sharded_argsort"
DEVICE_PLANE = "/device:TPU:{}"
HOST_PLANE = "/host:CPU"
STEPS = ("local_sort", "splitters", "partition", "relocate", "compact", "pad")
# steps of the mesh program outside its local sorts (no plan level)
MESH_STEPS = ("deal", "sample_exchange", "partition", "pack", "exchange")
HOST_SPANS = ("sort.argsort", "sort.plan", "sort.encode", "sort.launch",
              "sort.decode")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r"\bop_name=\"([^\"]*)\"")
_EVENT_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)")
_LEVEL = re.compile(r"sort\.(level\d+)(?=/|$)")
_STEP = re.compile(r"sort\.(" + "|".join(STEPS) + r")(?=/|$)")
_PHASE = re.compile(r"sort\.(phase_[a-z]+)(?=/|$)")
_MESH_STEP = re.compile(r"sort\.(" + "|".join(MESH_STEPS) + r")(?=/|$)")
_COLLECTIVE = re.compile(
    r"= \S+ (all-to-all|all-gather|all-reduce|collective-permute|"
    r"reduce-scatter)(?:-start|-done)?\(")


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
    scopes in ``op_name``, under ``sort.phase_<p>/`` where a phase of
    the mesh program holds it; a mesh step outside any level
    (``sort.deal`` ...); ``sort.phase_<p>/unscoped`` or ``unscoped``
    otherwise."""
    levels, steps = _LEVEL.findall(op_name), _STEP.findall(op_name)
    phases = _PHASE.findall(op_name)
    prefix = f"sort.{phases[-1]}/" if phases else ""
    if levels and steps:
        return f"{prefix}sort.{levels[-1]}/sort.{steps[-1]}"
    mesh_steps = _MESH_STEP.findall(op_name)
    if not levels and not phases and mesh_steps:
        return f"sort.{mesh_steps[-1]}"
    return prefix + "unscoped"


def step_times(ops, modules, names: dict[str, str], calls: int,
               module: str = EXECUTOR_MODULE) -> dict:
    """Device ms per call by scope.

    Args:
        ops: (event name, start_ns, duration_ns) of the ``XLA Ops`` line.
        modules: (module name, start_ns, duration_ns) of ``XLA Modules``.
        names: :func:`op_names` of the executor's compiled text.
        calls: calls the trace holds.
        module: name of the executor's module.
    """
    spans = sorted((s, s + d) for n, s, d in modules
                   if n.startswith(module))
    starts = [s for s, _ in spans]
    ns = collections.Counter()
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= spans[i][1]:
            continue
        instr = _EVENT_NAME.match(name).group(1)
        if instr not in names:
            ns["unmatched"] += dur
            continue
        scope = scope_of(names[instr])
        collective = _COLLECTIVE.search(name)
        if scope == "unscoped" and collective:
            scope = f"collective:{collective.group(1)}"
        ns[scope] += dur
    return {k: v / 1e6 / calls for k, v in sorted(ns.items())}


def read_profile(profile, names: dict[str, str], calls: int,
                 chips: int = 1, module: str = EXECUTOR_MODULE) -> dict:
    """by_step (one entry per chip plane the trace holds), host_ms and
    the first chip's modules of a ``jax.profiler.ProfileData``."""
    planes = {DEVICE_PLANE.format(i): ([], []) for i in range(chips)}
    host = collections.defaultdict(float)
    for plane in profile.planes:
        if plane.name in planes:
            ops, modules = planes[plane.name]
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
    found = {p: v for p, v in planes.items() if v[0] or v[1]}
    first = found.get(DEVICE_PLANE.format(0), ([], []))[1]
    return {
        "by_step": {p: step_times(ops, modules, names, calls, module)
                    for p, (ops, modules) in found.items()},
        "host_ms": {k: host[k] / 1e6 / calls for k in HOST_SPANS
                    if k in host},
        "modules": len(first) / calls,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--log2n", type=int, default=17)
    ap.add_argument("--chips", type=int, default=1,
                    help="1: bucket_sort.argsort on the first chip; more: "
                         "make_sharded_sort over the first CHIPS chips")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import compile_cache
    from repro.core import bucket_sort, distributed_sort
    from repro.core.sort_config import SortConfig

    compile_cache.enable()
    n = 1 << args.log2n
    keys = np.random.default_rng(args.seed).integers(
        -2**31, 2**31, n, dtype=np.int64).astype(np.int32)
    if args.chips == 1:
        keys = jnp.asarray(keys)
        plan = bucket_sort.resolve_plan(n, keys.dtype, SortConfig())
        words = (jax.ShapeDtypeStruct((1, n), jnp.uint32),)
        vals = jax.ShapeDtypeStruct((1, n), jnp.int32)
        text = bucket_sort._sort_canonical_packed.lower(
            words, vals, plan=plan, pad_base0=n).compile().as_text()
        module = EXECUTOR_MODULE

        def call():
            return bucket_sort.argsort(keys)
    else:
        mesh = jax.make_mesh(  # as bench/entries/sharded_argsort.py
            (args.chips,), ("data",), devices=jax.devices()[:args.chips],
            axis_types=(jax.sharding.AxisType.Auto,))
        keys = jax.device_put(keys, NamedSharding(mesh, PartitionSpec("data")))
        _, plan = distributed_sort.make_sharded_sort(mesh, "data", n)
        # The calls run the executable whose text is read: at 2^25 it
        # holds ~350 MB of code, which is slow to load a second time.
        compiled = distributed_sort._sharded_argsort.lower(
            keys, mesh, plan).compile()
        text = compiled.as_text()
        module = MESH_MODULE

        def call():
            return compiled(keys)
    names = op_names(text)
    jax.block_until_ready(call())  # compile, warm up

    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for _ in range(args.calls):
                jax.block_until_ready(call())
        finally:
            jax.profiler.stop_trace()
        path = sorted(pathlib.Path(d).rglob("*.xplane.pb"))[-1]
        result = read_profile(ProfileData.from_file(str(path)), names,
                              args.calls, args.chips, module)
    result = {"n": n, "chips": args.chips, "calls": args.calls,
              "backend": jax.default_backend(),
              "compiled_instructions": len(names), **result}
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
