"""Chip smoke test: the sort's main path on a TPU, through the public API.

    python chip_smoke.py              # phases a-c on one chip
    python chip_smoke.py --chips 4    # make_sharded_sort on a 4-chip mesh

One chip (the default config, data made from ``--seed``):

  a. ``argsort`` of uniform int32 keys at n = 2**27 (2**26 when the
     compiler reports that 2**27 does not fit HBM), against
     ``jnp.argsort(x, stable=True)`` on the device;
  b. ``sort`` of float32 keys with NaN, +-inf and -0.0 at n = 2**26,
     ascending and descending, bitwise against ``jnp.sort``;
  c. ``topk_batched`` at (B=64, V=151936, k=64) float32 against
     ``lax.top_k`` (equal values, equal index sets per row).

``--chips 4`` runs only ``make_sharded_sort`` over a 4-device mesh at
n_global = 2**25 int32, checked against ``np.sort`` and as an argsort
permutation, with no retry and no degradation.  The mesh sort admits
n_global <= 2**27 (8.2 GiB per chip compiled), but a local sort of
2**24 or more keys spends tens of seconds in XLA gathers on the chip,
so the smoke stays at a size that costs minutes of four chips.

Every phase asserts that its plan resolved to ``impl="pallas"`` with
``interpret=False``, that the compiled program holds Pallas kernels,
and that ``guard.degradation_log()`` stayed empty; a
``DegradationWarning`` is an error.  Informational lines go to stdout;
the last line is one JSON object naming the device.  Without a TPU the
script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import bucket_sort, distributed_sort, guard, partial_sort  # noqa: E402
from repro.core.plan import build_topk_plan  # noqa: E402
from repro.core.sort_config import DEFAULT_CONFIG, SortConfig  # noqa: E402

ARGSORT_LOG2N = (27, 26)  # preferred size first, then the fallback
SORT_N = 1 << 26
TOPK_SHAPE, TOPK_K = (64, 151936), 64
MESH_N = 1 << 25


def _check_native(what: str, impl: str, interpret: bool) -> None:
    if impl != "pallas" or interpret is not False:
        raise AssertionError(
            f"{what}: plan resolved to impl={impl} interpret={interpret}, "
            "not the native Pallas kernels"
        )
    print(f"  {what}: impl={impl} interpret={interpret}")


def _check_clean(what: str) -> None:
    log = guard.degradation_log()
    if log:
        raise AssertionError(f"{what}: degradation log not empty: {log}")


def _gib(nbytes: int) -> str:
    return f"{nbytes / 2**30:.2f} GiB"


def _memory(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _kernels(what: str, program_text: str) -> int:
    """Number of Pallas TPU kernels in a program; at least one."""
    kernels = program_text.count("tpu_custom_call")
    if kernels == 0:
        raise AssertionError(f"{what}: program holds no Pallas kernel")
    return kernels


def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args``; assert Pallas kernels are in it."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    return compiled, t_compile, _kernels("compiled", compiled.as_text())


def _timed(what: str, n, compiled, t_compile: float, kernels: int, *args):
    """First call (compile + run) and second call wall times."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    first = t_compile + time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    second = time.perf_counter() - t0
    print(f"  {what}: n={n} first_call_s={first:.3f} "
          f"second_call_s={second:.3f} compiled_memory="
          f"{_gib(_memory(compiled))} pallas_kernels={kernels}")
    return out


def _hbm_limit() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _fits(n: int, cfg: SortConfig):
    """Compile the argsort at n; (compiled, t, kernels) or the reason it
    does not fit the device's memory."""
    x = jax.ShapeDtypeStruct((n,), jnp.int32)
    try:
        compiled, t, kernels = _compile(
            lambda a: bucket_sort.argsort(a, cfg), x)
    except Exception as e:  # the TPU compiler refuses what exceeds HBM
        msg = str(e)
        if "RESOURCE_EXHAUSTED" not in msg and "memory" not in msg.lower():
            raise
        return None, msg.strip().splitlines()[0][:300]
    need, limit = _memory(compiled), _hbm_limit()
    if limit is not None and need > limit:
        return None, f"memory_analysis needs {_gib(need)} > {_gib(limit)}"
    return (compiled, t, kernels), None


def phase_argsort(key, cfg: SortConfig = DEFAULT_CONFIG,
                  log2ns=ARGSORT_LOG2N) -> None:
    print("phase a: argsort int32 uniform")
    for lg in log2ns:
        n = 1 << lg
        built, reason = _fits(n, cfg)
        if built is not None:
            break
        print(f"  n=2**{lg} does not fit: {reason}")
    else:
        raise AssertionError("no argsort size fits the device")
    plan = bucket_sort.resolve_plan(n, jnp.int32, cfg)
    _check_native("argsort plan", plan.impl, plan.interpret)
    x = jax.lax.bitcast_convert_type(
        jax.random.bits(key, (n,), jnp.uint32), jnp.int32)
    perm = _timed("argsort", n, *built, x)
    ok = bool(jnp.array_equal(perm, jnp.argsort(x, stable=True)))
    if not ok:
        raise AssertionError("argsort differs from jnp.argsort(stable=True)")
    print("  argsort == jnp.argsort(x, stable=True)")
    _check_clean("argsort")


def _special_floats(key, n: int) -> jax.Array:
    """Normal floats with 1/16 of them NaN, +inf, -inf or -0.0.

    No exact +0.0 is drawn: the library sorts in the IEEE total order
    (-0.0 before +0.0), while ``jnp.sort`` keeps tied zeros in input
    order, so a mix of both zeros has no bitwise-unique answer."""
    kx, ks = jax.random.split(key)
    x = jax.random.normal(kx, (n,), jnp.float32)
    pick = jax.random.randint(ks, (n,), 0, 64)
    specials = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf, -0.0], jnp.float32)
    return jnp.where(pick < 4, specials[jnp.minimum(pick, 3)], x)


def _bits(a: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(a, jnp.uint32)


def phase_sort(key, cfg: SortConfig = DEFAULT_CONFIG, n: int = SORT_N):
    print("phase b: sort float32 with NaN, +-inf, -0.0")
    x = _special_floats(key, n)
    for descending in (False, True):
        c = dataclasses.replace(cfg, descending=descending)
        what = "sort descending" if descending else "sort ascending"
        plan = bucket_sort.resolve_plan(n, jnp.float32, c)
        _check_native(f"{what} plan", plan.impl, plan.interpret)
        built = _compile(lambda a, c=c: bucket_sort.sort(a, c),
                         jax.ShapeDtypeStruct((n,), jnp.float32))
        y = _timed(what, n, *built, x)
        want = jnp.sort(x, descending=descending)
        if not bool(jnp.array_equal(_bits(y), _bits(want))):
            raise AssertionError(f"{what} differs bitwise from jnp.sort")
        print(f"  {what} == jnp.sort bitwise")
        del y, want
        _check_clean(what)


def phase_topk(key, cfg: SortConfig = DEFAULT_CONFIG, shape=TOPK_SHAPE,
               k: int = TOPK_K) -> None:
    print("phase c: topk_batched float32 (decode sampling)")
    b, v = shape
    plan = build_topk_plan(v, k, jnp.float32, cfg, rows=b)
    _check_native("topk plan", plan.impl, plan.interpret)
    x = jax.random.normal(key, shape, jnp.float32)
    built = _compile(lambda a: partial_sort.topk_batched(a, k, cfg),
                     jax.ShapeDtypeStruct(shape, jnp.float32))
    vals, idx = _timed("topk_batched", shape, *built, x)
    rv, ri = jax.lax.top_k(x, k)
    if not bool(jnp.array_equal(_bits(vals), _bits(rv))):
        raise AssertionError("topk values differ from lax.top_k")
    if not bool(jnp.array_equal(jnp.sort(idx, axis=1),
                                jnp.sort(ri.astype(jnp.int32), axis=1))):
        raise AssertionError("topk index sets differ from lax.top_k")
    print("  topk_batched == lax.top_k (values; index sets)")
    _check_clean("topk_batched")


def phase_mesh(key, cfg: SortConfig = DEFAULT_CONFIG, n: int = MESH_N,
               d: int = 4) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    print(f"phase mesh: make_sharded_sort int32 on {d} devices")
    mesh = make_mesh((d,), ("data",))
    run, plan = distributed_sort.make_sharded_sort(mesh, "data", n, cfg)
    _check_native("shard plan", plan.impl, plan.interpret)
    for name in ("run_plan", "dealt_plan", "sample_plan", "bucket_plan"):
        sub = getattr(plan, name)
        _check_native(f"  local {name}", sub.impl, sub.interpret)
    x = jax.device_put(
        jax.lax.bitcast_convert_type(
            jax.random.bits(key, (n,), jnp.uint32), jnp.int32),
        NamedSharding(mesh, P("data")))
    # The local sorts inside shard_map must lower to Pallas kernels.
    kernels = _kernels("sharded sort", distributed_sort._sharded_argsort
                       .lower(x, mesh, plan).as_text())
    t0 = time.perf_counter()
    jax.block_until_ready(run(x))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    sk, sv, counts, mw = jax.block_until_ready(run(x))
    second = time.perf_counter() - t0
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  make_sharded_sort: n={n} first_call_s={first:.3f} "
          f"second_call_s={second:.3f} device0_peak="
          f"{_gib(peak) if peak else 'not reported'} "
          f"pallas_kernels={kernels}")
    if run.last_stats != {"degraded": False, "retries": 0}:
        raise AssertionError(f"mesh run degraded: {run.last_stats}")
    xs = np.asarray(x)
    sk, sv, counts = np.asarray(sk), np.asarray(sv), np.asarray(counts)
    oc = plan.out_cap
    got = np.concatenate([sk[i * oc:i * oc + counts[i]] for i in range(d)])
    pv = np.concatenate([sv[i * oc:i * oc + counts[i]] for i in range(d)])
    if not np.array_equal(got, np.sort(xs)):
        raise AssertionError("sharded sort differs from np.sort")
    if not (np.array_equal(np.sort(pv), np.arange(n))
            and np.array_equal(xs[pv], got)):
        raise AssertionError("sharded payloads are not an argsort")
    if not (np.asarray(mw) < plan.c_pair).all():
        raise AssertionError("a per-pair chunk exceeded c_pair")
    print(f"  sharded sort == np.sort; payloads an argsort; "
          f"last_stats={run.last_stats}")
    _check_clean("make_sharded_sort")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # Progress lines reach a pipe as they happen, so a run cut short
    # still shows how far it got.
    sys.stdout.reconfigure(line_buffering=True)
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"no TPU: JAX found {dev[0].platform}", file=sys.stderr)
        return 2
    if len(dev) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(dev)}", file=sys.stderr)
        return 2
    print(f"compile cache: {compile_cache.enable()}")
    print(f"device: {dev[0].device_kind} x{len(dev)}")
    warnings.simplefilter("error", guard.DegradationWarning)
    guard.clear_degradation_log()
    keys = jax.random.split(jax.random.key(args.seed), 4)
    if args.chips == 4:
        phase_mesh(keys[3])
    else:
        phase_argsort(keys[0])
        gc.collect()
        phase_sort(keys[1])
        gc.collect()
        phase_topk(keys[2])
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
