"""Multi-device deterministic sample sort (shard_map + fixed-capacity
all_to_all), driven by the frozen ShardPlan IR (DESIGN.md §9).  Runs on
8 forced host devices:

  PYTHONPATH=src python examples/distributed_sort_demo.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax.numpy as jnp
import numpy as np

from repro.core import SortConfig, make_sharded_sort
from repro.core.distributed_sort import trace_count
from repro import compile_cache
from repro.launch.mesh import make_mesh

compile_cache.enable()
mesh = make_mesh((4, 2), ("data", "model"))
cfg = SortConfig(tile=1024, s=32, direct_max=2048, impl="xla")
n = 1 << 17

run, plan = make_sharded_sort(mesh, ("data", "model"), n, cfg, oversample=8)
print(plan.describe())
print(f"devices={plan.d} n={n} per-pair capacity={plan.c_pair} "
      f"(deterministic bound; randomized splitters admit NO static bound)")

rng = np.random.default_rng(0)
for dist, x in {
    "uniform": rng.integers(-2**31, 2**31 - 1, n).astype(np.int32),
    "zipf-skew": (rng.zipf(1.5, n) % 100000).astype(np.int32),
    "all-equal": np.full(n, 42, np.int32),
}.items():
    sk, sv, counts, mw = map(np.asarray, run(jnp.asarray(x)))
    oc = plan.out_cap
    got = np.concatenate([sk[i * oc : i * oc + counts[i]] for i in range(plan.d)])
    assert (got == np.sort(x)).all()
    print(f"{dist:10s}: OK  shard loads={counts.tolist()} max_pair_fill={mw.max()}/{plan.c_pair}")

# The plan is a jit static argument: a fresh make_sharded_sort with the
# same signature returns the SAME memoized plan -> zero retraces.
run2, plan2 = make_sharded_sort(mesh, ("data", "model"), n, cfg, oversample=8)
t0 = trace_count()
run2(jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)))
print(f"equal-signature rebuild: plan2 is plan={plan2 is plan}, "
      f"retraces={trace_count() - t0}")
