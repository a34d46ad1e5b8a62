"""The counter-read metric: exchange slots per key, from the sharded
sort's own counters (CPU only; the mesh call runs on 4 forced host
devices in a child process)."""

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

read = load_module("metrics", "exchange_slots_per_key").read
before = read(None)
mesh = jax.make_mesh((4,), ("data",))
n = 8192
run, plan = make_sharded_sort(
    mesh, "data", n, SortConfig(impl="xla", tile=128, s=8, direct_max=128))
x = jnp.asarray(np.random.default_rng(0).integers(0, 99, n), jnp.int32)
for _ in range(2):
    jax.block_until_ready(run(x))
print(json.dumps({{"before": before, "after": read(None),
                  "want": plan.d * plan.d * plan.c_pair / n}}))
"""


def test_reads_the_plans_exchange_slots_per_key():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT.format(bench=str(BENCH_DIR),
                                              src=str(BENCH_DIR.parent / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["before"] is None
    assert res["after"] == res["want"] > 1


def _read():
    return load_module("metrics", "exchange_slots_per_key").read(None)


def test_one_chip_calls_alone_give_no_reading(monkeypatch):
    monkeypatch.setattr(telemetry, "_COUNTS",
                        collections.Counter({"sort.keys": 4096}))
    assert _read() is None


def test_program_without_counters_no_reading(monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert _read() is None
