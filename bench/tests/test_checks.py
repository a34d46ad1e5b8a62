"""Whole runs on the CPU at small sizes: a sound run is correct, and the
control and each fault that a cell can have make it not correct.  The
look for a chip is skipped (``on_chip=False``); everything else is the
run's own path."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import run as bench
from control import ControlEntry
from harness.cell import load_module

BENCH_DIR = Path(__file__).resolve().parent.parent
UNIFORM = "argsort_i32.uniform_8m"
MOE = "argsort_i32.moe_dsv3_128k"
MESH = "sharded_argsort_i32_4chip.uniform_32m"  # rehearsed, not yet a cell
SMALL = {UNIFORM: {"n": 8192}, MOE: {"tokens": 512}}
# 2**18 uniform int32 keys hold ~8 pairs of equal keys, so a sort that
# is not stable differs from the reference.
TIES = {UNIFORM: {"n": 2**18}, MOE: {"tokens": 512}}


class Broken:
    """The entry with its result replaced by ``fn(entry, keys)``."""

    def __init__(self, entry, fn):
        self.entry, self.fn, self.sharding = entry, fn, entry.sharding

    def __call__(self, x):
        return self.fn(self.entry, x)

    def permutation(self, out):
        return np.asarray(out)

    def trace_count(self):
        return self.entry.trace_count()

    def faults(self):
        return self.entry.faults()


def _pallas_interpret(entry):
    """The one-chip entry on the Pallas kernels, in interpret mode."""
    entry.cfg = dataclasses.replace(entry.cfg, impl="pallas")
    return entry


def _unchanged(entry, x):  # the sort returns its input order
    return jnp.arange(x.shape[0], dtype=jnp.int32)


def _half_left_out(entry, x):  # only the first half is sorted
    h = x.shape[0] // 2
    return jnp.concatenate([jnp.argsort(x[:h], stable=True).astype(jnp.int32),
                            jnp.arange(h, x.shape[0], dtype=jnp.int32)])


def _answer_altered(entry, x):  # two entries of the answer swapped
    out = entry(x)
    return out.at[0].set(out[1]).at[1].set(out[0])


def _run(workload, traffic, **kw):
    return bench.run(workload, 2**31 + 99, 0.2, False, on_chip=False,
                     traffic_override=traffic, **kw)


@pytest.mark.parametrize("workload", [UNIFORM, MOE])
def test_sound_run_is_correct(workload):
    out = _run(workload, SMALL[workload], wrap_entry=_pallas_interpret)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("workload", [UNIFORM, MOE])
def test_control_is_not_correct(workload):
    control = load_module("configs", "stable_argsort").control
    out = _run(workload, TIES[workload],
               wrap_entry=lambda e: ControlEntry(e, control))
    assert not out["correct"]
    assert out["checks"]["mismatched_indices"]["value"] > 0


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered])
@pytest.mark.parametrize("workload", [UNIFORM, MOE])
def test_fault_is_not_correct(workload, fault):
    out = _run(workload, SMALL[workload],
               wrap_entry=lambda e: Broken(e, fault))
    assert not out["correct"]
    assert out["failed"] >= 1
    assert out["checks"]["mismatched_indices"]["value"] > 0


MESH_SCRIPT = r"""
import json, sys
sys.path.insert(0, {bench!r})
import jax.numpy as jnp, numpy as np
import run as bench
from harness.cell import BENCH_DIR, Cell

def mesh_cell(name):
    # the mesh configuration is not yet a cell of BENCHMARK.json
    read = lambda p: json.loads((BENCH_DIR / p).read_text())
    return Cell(name=name, chips=4,
                config=read("configs/sharded_argsort_i32_4chip.json"),
                traffic=read("traffic/uniform_32m.json"),
                end_to_end=(), per_layer=())

bench.load_cell = mesh_cell

def exchange_left_out(entry, x):
    # each chip sorts its own shard and nothing crosses chips
    d = 4
    m = x.shape[0] // d
    return jnp.concatenate([
        jnp.argsort(x[i * m:(i + 1) * m], stable=True).astype(jnp.int32)
        + i * m for i in range(d)])

class Broken:
    def __init__(self, entry):
        self.entry, self.sharding = entry, entry.sharding
    def __call__(self, x):
        return exchange_left_out(self.entry, x)
    def permutation(self, out):
        return np.asarray(out)
    def trace_count(self):
        return self.entry.trace_count()
    def faults(self):
        return self.entry.faults()

from control import ControlEntry
from harness.cell import load_module
control = load_module("configs", "stable_argsort").control
ties = {{"distribution": "moe_routing", "tokens": 8192, "experts": 256,
        "top_k": 8, "groups": 8, "topk_groups": 4}}
res = {{}}
for name, wrap, traffic in (
        ("sound", None, {{"n": 65536}}),
        ("sound_ties", None, ties),
        ("exchange_left_out", Broken, {{"n": 65536}}),
        ("control", lambda e: ControlEntry(e, control), ties)):
    out = bench.run({mesh!r}, 2**31 + 5, 0.2, False, on_chip=False,
                    traffic_override=traffic, wrap_entry=wrap)
    res[name] = [out["correct"], out["checks"]["mismatched_indices"]["value"]]
print(json.dumps(res))
"""


def test_mesh_sound_control_and_exchange_left_out():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = MESH_SCRIPT.format(bench=str(BENCH_DIR), mesh=MESH)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["sound"] == [True, 0]
    assert res["sound_ties"] == [True, 0]
    for unsound in ("exchange_left_out", "control"):
        assert res[unsound][0] is False and res[unsound][1] > 0, unsound


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", UNIFORM, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_with_no_result():
    p = _cli(BENCH_DIR.parent)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copy(BENCH_DIR.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
