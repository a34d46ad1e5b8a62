"""The counter-read metric: moved elements per key on the mesh, from the
sharded sort's own counters (CPU only; the mesh call runs on 4 forced
host devices in a child process, at ``tests/test_sharded_argsort.py``'s
size and configuration)."""

import collections
import json
import os
import subprocess
import sys

from harness.cell import BENCH_DIR, load_module
from repro.core import telemetry

MESH_SCRIPT = r"""
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax, jax.numpy as jnp, numpy as np
from harness.cell import load_module
from repro.core import make_sharded_sort, telemetry
from repro.core.sort_config import SortConfig

read = load_module("metrics", "mesh_moved_elements_per_key").read
before = read(None)
mesh = jax.make_mesh((4,), ("data",))
n = 8192
run, plan = make_sharded_sort(
    mesh, "data", n, SortConfig(impl="xla", tile=128, s=8, direct_max=256))
x = jnp.asarray(np.random.default_rng(0).integers(0, 99, n), jnp.int32)
deltas = []
for _ in range(2):
    c0 = telemetry.counts().get("sort.mesh_moved_elements", 0)
    jax.block_until_ready(run(x))
    deltas.append(telemetry.counts()["sort.mesh_moved_elements"] - c0)
phases = (plan.run_plan, plan.dealt_plan, plan.sample_plan, plan.bucket_plan)
want = plan.d * sum(p.moved_elements for p in phases)
print(json.dumps({{"before": before, "after": read(None), "deltas": deltas,
                  "want": want, "n": n}}))
"""


def test_eager_calls_advance_the_counter_by_the_plan():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT.format(bench=str(BENCH_DIR),
                                              src=str(BENCH_DIR.parent / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["before"] is None
    assert res["deltas"] == [res["want"], res["want"]] and res["want"] > 0
    assert res["after"] == res["want"] / res["n"]


def _read():
    return load_module("metrics", "mesh_moved_elements_per_key").read(None)


def test_reads_the_counter_over_the_keys(monkeypatch):
    monkeypatch.setattr(telemetry, "_COUNTS", collections.Counter(
        {"sort.keys": 1 << 25, "sort.mesh_moved_elements": 951877632}))
    assert _read() == 951877632 / (1 << 25)


def test_one_chip_calls_alone_give_no_reading(monkeypatch):
    monkeypatch.setattr(telemetry, "_COUNTS", collections.Counter(
        {"sort.keys": 4096, "sort.moved_elements": 12288}))
    assert _read() is None


def test_program_without_counters_no_reading(monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert _read() is None
