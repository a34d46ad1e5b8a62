"""The 4-chip sharded argsort deployment (``bench/configs/
sharded_argsort_i32_4chip.json``) on 4 forced host devices, against the
plain reference ``np.argsort(kind="stable")``.

The configuration's own settings (D = 4, oversample 8, pair_align 8,
int32 keys, the global index as payload, Pallas kernels) at a tile and
sample count cut so that the phases have the chip plan's shape at 2^25
keys: two-level run and dealt sorts, and a two-level bucket sort at a
length that is not a power of two, whose level-1 buckets go direct.
One child process runs every check, since the main process keeps the
real one-CPU topology; each test reads its part of the child's report.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("run", "dealt", "sample", "bucket")
SCOPES = tuple(f"sort.phase_{p}" for p in PHASES) + (
    "sort.partition", "sort.pack", "sort.deal", "sort.sample_exchange",
    "sort.exchange")

CHILD = """
    import json, re
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import make_sharded_sort, telemetry
    from repro.core.distributed_sort import _sharded_argsort
    from repro.core.sort_config import SortConfig

    mesh = jax.make_mesh((4,), ("data",))
    n = 8192
    cfg = SortConfig(impl="pallas", tile=128, s=8, direct_max=256)
    run, plan = make_sharded_sort(mesh, "data", n, cfg, 8,
                                  dtype=jnp.int32, pair_align=8)
    rng = np.random.default_rng(2**31 + 15)
    keys = {
        "uniform": rng.integers(-2**31, 2**31, n, dtype=np.int64),
        # 512 equal keys a value, as the MoE dispatch mix has
        "ties": rng.integers(0, n // 512, n),
    }

    def counters():
        c = telemetry.counts()
        return [c.get("sort.keys", 0), c.get("sort.exchange_slots", 0)]

    def delta(fn):
        before = counters()
        fn()
        return [a - b for a, b in zip(counters(), before)]

    report = {"plan": {
        "d": plan.d, "c_pair": plan.c_pair,
        "levels": [getattr(plan, f"{p}_plan").num_levels
                   for p in ("run", "dealt", "sample", "bucket")],
        "bucket_length": plan.bucket_plan.length}}
    for name, x in keys.items():
        x = jnp.asarray(x.astype(np.int32))
        out = []
        report[name + "_delta"] = delta(
            lambda: out.append(jax.block_until_ready(run(x))))
        _, vals, counts, _ = map(np.asarray, out[0])
        cap = plan.out_cap
        got = np.concatenate([vals[i * cap:i * cap + counts[i]]
                              for i in range(plan.d)])
        want = np.argsort(np.asarray(x), kind="stable")
        report[name] = int(np.count_nonzero(got != want)
                           if got.shape == want.shape else n)
    jitted = jax.jit(run)
    report["jit_deltas"] = [
        delta(lambda: jax.block_until_ready(jitted(x))) for _ in range(2)]
    text = _sharded_argsort.lower(x, mesh, plan).compile().as_text()
    report["scopes"] = sorted(set(re.findall(
        r"sort\\.(?:phase_[a-z]+|partition|pack|deal|sample_exchange|"
        r"exchange)(?=/)", " ".join(re.findall(r'op_name="([^"]*)"',
                                               text)))))
    print(json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_phases_have_the_chip_plans_shape(report):
    plan = report["plan"]
    assert plan["levels"] == [2, 2, 0, 2]
    length = plan["bucket_length"]
    assert length == plan["d"] * plan["c_pair"]
    assert length & (length - 1) != 0


@pytest.mark.parametrize("keys", ["uniform", "ties"])
def test_payloads_are_the_stable_argsort(report, keys):
    assert report[keys] == 0


@pytest.mark.parametrize("keys", ["uniform", "ties"])
def test_eager_call_counts_keys_and_exchange_slots(report, keys):
    d, c_pair = report["plan"]["d"], report["plan"]["c_pair"]
    assert report[keys + "_delta"] == [8192, d * d * c_pair]


def test_counters_under_jit_count_the_trace_once(report):
    d, c_pair = report["plan"]["d"], report["plan"]["c_pair"]
    assert report["jit_deltas"] == [[8192, d * d * c_pair], [0, 0]]


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_program_names_each_phase_and_step(report, scope):
    assert scope in report["scopes"]
