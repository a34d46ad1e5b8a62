"""Plain reference of a stable argsort, and its control.

``reference`` is what every argsort configuration must return: the
permutation that orders the keys ascending, equal keys in input order
(numpy's stable sort; nothing of the program is imported).

``control`` is the same sort with one guarantee broken: equal keys come
out in reverse input order.  It is put in the program's place to show
that the comparison fails a sort that is not stable
(``bench/control.py``); the benchmark's own runs never call it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def reference(keys: np.ndarray) -> np.ndarray:
    """Stable ascending argsort of a 1-D host array, as int32."""
    return np.argsort(keys, kind="stable").astype(np.int32)


@jax.jit
def control(keys: jax.Array) -> jax.Array:
    """Ascending argsort on the device with ties in reverse input order."""
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, neg = jax.lax.sort((keys, -iota), num_keys=2)
    return -neg
