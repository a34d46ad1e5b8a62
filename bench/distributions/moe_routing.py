"""Flat expert ids of an MoE layer's top-k routing, as a router emits them.

Source of the routing: DeepSeek-V3's published config.json
(huggingface.co/deepseek-ai/DeepSeek-V3): ``n_routed_experts`` = 256,
``num_experts_per_tok`` = 8, and group-limited routing with ``n_group``
= 8 groups of experts of which each token may use ``topk_group`` = 4.
A token's groups are the ones whose two best expert scores sum highest;
its ``top_k`` distinct experts are the best-scoring ones inside them.
The ids are laid out token by token, each token's experts in the order
of their scores, which is the array an MoE layer sorts to group its
tokens by expert.

The routing is balanced, every expert equally likely, which is what
DeepSeek-V3's auxiliary-loss-free load balancing aims for
(arXiv:2412.19437, section 2.1.2): the scores are independent Gumbel
draws.  The token count is the traffic file's.

Parameters: ``tokens``, ``experts``, ``top_k``, ``groups``,
``topk_groups``.  The key count is ``tokens * top_k``.  Tokens are
drawn ``BLOCK`` at a time, so the scores of the whole batch never sit
in device memory at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 64  # tokens drawn at a time: the top-k temporaries grow with it


def _route(scores, groups: int, topk_groups: int, top_k: int):
    """Group-limited top-k of one block of (tokens, experts) scores."""
    t, e = scores.shape
    by_group = scores.reshape(t, groups, e // groups)
    group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)
    _, keep = jax.lax.top_k(group_score, topk_groups)
    allowed = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], keep].set(True)
    masked = jnp.where(allowed[:, :, None], by_group, -jnp.inf)
    return jax.lax.top_k(masked.reshape(t, e), top_k)[1]


def generate(key, dtype, params: dict) -> jax.Array:
    """``tokens * top_k`` expert ids in [0, experts) of ``dtype``."""
    tokens, experts = int(params["tokens"]), int(params["experts"])
    top_k = int(params["top_k"])
    groups, topk_groups = int(params["groups"]), int(params["topk_groups"])
    block = min(BLOCK, tokens)
    if tokens % block or experts % groups:
        raise ValueError(f"tokens {tokens} must be a multiple of {block} "
                         f"and experts {experts} of groups {groups}")
    _, k_blocks = jax.random.split(key)

    def one_block(k):
        scores = jax.random.gumbel(k, (block, experts), jnp.float32)
        return _route(scores, groups, topk_groups, top_k)

    ids = jax.lax.map(one_block, jax.random.split(k_blocks, tokens // block))
    return ids.reshape(-1).astype(dtype)
