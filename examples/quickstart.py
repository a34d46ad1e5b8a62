"""Quickstart: the paper's algorithm as a library.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import (
    DEFAULT_CONFIG, PAPER_CONFIG, SortConfig, argsort, sort, sort_kv,
    sort_with_stats, topk,
)

compile_cache.enable()

rng = np.random.default_rng(0)

# 1. sort a million keys with GPU BUCKET SORT (TPU-adapted, static shapes)
x = jnp.asarray(rng.integers(-2**31, 2**31 - 1, 1_000_000).astype(np.int32))
y = sort(x)
assert bool((y[1:] >= y[:-1]).all())
print(f"sorted {x.shape[0]} keys; first={int(y[0])} last={int(y[-1])}")

# 2. stable argsort + key/value sort
keys = jnp.asarray(rng.integers(0, 10, 16).astype(np.int32))
vals = jnp.arange(16)
sk, sv = sort_kv(keys, vals)
print("stable kv sort:", np.asarray(sk)[:8], np.asarray(sv)[:8])

# 3. the paper's guarantee: bucket fill <= static capacity, ANY input
worst = jnp.asarray(np.full(200_000, 7, np.int32))  # all-equal adversary
_, _, stats = sort_with_stats(worst, DEFAULT_CONFIG)
for s in stats:
    print(f"round: capacity={s['capacity']} max_fill={int(np.asarray(s['totals']).max())} (guaranteed <=)")

# 4. partial sample sort: top-k over a vocab-sized array
logits = jnp.asarray(rng.normal(size=151_936).astype(np.float32))
v, i = topk(logits, 8)
lv, li = jax.lax.top_k(logits, 8)
assert (np.asarray(i) == np.asarray(li)).all()
print("top-8 ids:", np.asarray(i))

# 5. the paper's own geometry (2K tiles / s=64, Fig. 3)
y2 = sort(x[:100_000], PAPER_CONFIG)
assert bool((y2[1:] >= y2[:-1]).all())
print("paper-config sort OK")

# 6. the plan layer: the whole schedule is static data (DESIGN.md §7).
# Build a plan once and reuse it — every call with an equal plan hits
# the same compiled executable (zero retraces).
from repro.core import build_plan, sort_planned
from repro.core import bucket_sort

plan = build_plan(x.shape[0], x.dtype, DEFAULT_CONFIG)
print(plan.describe())
y3 = sort_planned(x, plan)
t0 = bucket_sort.trace_count()
y3 = sort_planned(x, plan)          # plan reuse: compiles nothing
assert bucket_sort.trace_count() == t0
print("plan reuse: zero retraces")

# 7. autotune-then-sort: measure the plan space once, persist the
# winner, serve every later same-signature call from the plan cache
# (~/.cache/repro_sort/plans.json or $REPRO_SORT_PLAN_CACHE).
# plan_for is exactly what SortConfig(plan="autotune") calls on a
# cache miss — invoked directly here so the demo can shrink the trial
# budget; the search runs ONCE, everything after is a cache hit.
from repro.core.autotune import plan_for

n_tune = 200_000
best = plan_for(n_tune, x.dtype, DEFAULT_CONFIG, max_trials=6, repeats=2)
print("autotuned winner:", best.describe().splitlines()[0])
y4 = sort_planned(x[:n_tune], best)
assert bool((y4[1:] >= y4[:-1]).all())

cfg_tuned = SortConfig(plan="autotune")      # the public-API spelling
t0 = bucket_sort.trace_count()
y5 = sort(x[:n_tune], cfg_tuned)             # cache hit: same plan object,
assert bucket_sort.trace_count() == t0       # zero retraces, no re-tuning
assert bool((y5 == y4).all())
print("autotuned sort OK (plan cached for future processes)")
