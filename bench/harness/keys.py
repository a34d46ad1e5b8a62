"""The one general key generator: a traffic file's parameters -> keys.

The traffic file (``bench/traffic/<mix>.json``) names its
``distribution``, a module ``bench/distributions/<name>.py`` whose
``generate(key, dtype, params)`` returns one array of keys on the
device.  Each run sorts a pool of ``POOL`` such arrays, drawn from
``--seed`` in one jitted call and placed as the entry wants them; the
window cycles through the pool.
"""

from __future__ import annotations

import jax

from harness.cell import load_module

POOL = 4


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low 32 bits seed the key
    and the bits above them are folded in, so seeds past 2**32 differ."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    high = (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(key, high) if high else key


def key_count(dtype, traffic: dict) -> int:
    """Keys per array of the mix, from its shape alone (nothing runs)."""
    dist = load_module("distributions", traffic["distribution"])
    shape = jax.eval_shape(lambda k: dist.generate(k, dtype, traffic),
                           seed_key(0)).shape
    return int(shape[0])


def make_inputs(seed: int, dtype, traffic: dict, sharding,
                pool: int = POOL) -> list[jax.Array]:
    """``pool`` key arrays of the mix, made on the device(s) of
    ``sharding``; the same seed gives the same arrays."""
    dist = load_module("distributions", traffic["distribution"])

    def gen(key):
        return [dist.generate(k, dtype, traffic)
                for k in jax.random.split(key, pool)]

    out = jax.jit(gen, out_shardings=sharding)(seed_key(seed))
    return jax.block_until_ready(out)
