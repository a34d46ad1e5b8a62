"""Batched & segmented sort in one launch (DESIGN.md §5).

Serving-shaped workloads sort many SMALL independent arrays — a batch
of vocab-sized logit rows, ragged per-request candidate lists — where a
python loop of 1-D sorts wastes the machine on launch overhead.  The
paper's capacity bound holds per row, so the whole batch rides one
static-shape pipeline.

  PYTHONPATH=src python examples/batched_sort.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    DEFAULT_CONFIG,
    argsort_batched,
    segment_argsort,
    segment_sort,
    sort_batched,
    topk_batched,
)
from repro import compile_cache

compile_cache.enable()

rng = np.random.default_rng(0)

# --- Batched: (B, L) -> every row sorted independently, ONE launch. ---
xs = jnp.asarray(rng.integers(0, 1000, (8, 20000)).astype(np.int32))
ys = sort_batched(xs, DEFAULT_CONFIG)
perms = argsort_batched(xs, DEFAULT_CONFIG)
assert (np.asarray(ys) == np.sort(np.asarray(xs), axis=1)).all()
assert (np.asarray(perms)
        == np.argsort(np.asarray(xs), axis=1, kind="stable")).all()
print(f"sort_batched: {xs.shape} rows each sorted, stable; "
      f"row 0 head = {np.asarray(ys)[0, :5]}")

# --- Segmented: ragged independent sorts given host-known offsets. ---
x = jnp.asarray(rng.normal(size=50_000).astype(np.float32))
offsets = [0, 3, 3, 20_000, 50_000]  # empty + tiny + large segments
y = segment_sort(x, offsets, DEFAULT_CONFIG)
perm = segment_argsort(x, offsets, DEFAULT_CONFIG)
for lo, hi in zip(offsets, offsets[1:]):
    assert (np.asarray(y)[lo:hi] == np.sort(np.asarray(x)[lo:hi])).all()
    assert set(np.asarray(perm)[lo:hi]) == set(range(lo, hi))  # no leaks
print(f"segment_sort: {len(offsets) - 1} ragged segments of n={x.shape[0]}, "
      "no element crossed a boundary")

# --- Batched top-k: the serving hot path, (batch, vocab) logits. ---
logits = jnp.asarray(rng.normal(size=(8, 50_257)).astype(np.float32))
tv, ti = topk_batched(logits, 40, DEFAULT_CONFIG)
lv, li = jax.lax.top_k(logits, 40)
assert (np.asarray(tv) == np.asarray(lv)).all()
assert (np.asarray(ti) == np.asarray(li)).all()
print(f"topk_batched: top-40 of {logits.shape} logits == jax.lax.top_k")
