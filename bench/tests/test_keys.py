"""The key generator and its distributions (CPU only)."""

import jax
import jax.numpy as jnp
import numpy as np

from harness.keys import key_count, make_inputs, seed_key

UNIFORM = {"distribution": "uniform", "n": 4096}
MOE = {"distribution": "moe_routing", "tokens": 2048, "experts": 256,
       "top_k": 8, "groups": 8, "topk_groups": 4}


def test_seeds_past_32_bits_give_distinct_keys():
    a, b = 2**31 + 5, 2**32 + 2**31 + 5  # same low 32 bits
    ka, kb = (jax.random.key_data(seed_key(s)) for s in (a, b))
    assert not np.array_equal(np.asarray(ka), np.asarray(kb))


def test_same_seed_same_inputs_and_pool_arrays_differ():
    one = make_inputs(2**31 + 7, jnp.int32, UNIFORM, None)
    two = make_inputs(2**31 + 7, jnp.int32, UNIFORM, None)
    assert all(np.array_equal(a, b) for a, b in zip(one, two))
    assert not np.array_equal(one[0], one[1])
    assert one[0].dtype == jnp.int32 and one[0].shape == (4096,)


def test_key_count_from_shape_alone():
    assert key_count(jnp.int32, UNIFORM) == 4096
    assert key_count(jnp.int32, MOE) == 2048 * 8


def test_moe_routing_group_limited_and_balanced():
    ids = np.asarray(make_inputs(3, jnp.int32, MOE, None, pool=1)[0])
    per_token = ids.reshape(2048, 8)
    assert ids.min() >= 0 and ids.max() < 256
    assert all(len(set(row)) == 8 for row in per_token)
    # every token's experts lie in at most topk_groups of the 8 groups
    assert max(len(set(row // 32)) for row in per_token) <= 4
    counts = np.bincount(ids, minlength=256)
    assert counts.max() < 2 * counts.mean()  # balanced
