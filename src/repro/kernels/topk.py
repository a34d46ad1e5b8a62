"""Pallas TPU kernel: row-wise bitonic top-k (MoE router / sampling).

Sorts each row of an (R, C) score matrix descending with a bitonic
network along the lane axis and emits the first k columns.  C is the
number of experts (64 / 128 for the assigned MoE archs) — small enough
that a full row sort is cheaper than iterative max-extraction, and the
bitonic network is branch-free (same rationale as the paper's Step 2).

Keys arrive already in the canonical descending encoding (the caller
uses a ``descending=True`` key codec, see ``ops.topk``): ascending
canonical order == descending score order, for any supported dtype
including the two-word 64-bit encodings.

Ties broken toward the smaller column index (matches jax.lax.top_k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic import (
    as_words,
    bitonic_network_rows,
    compiler_params,
    for_each_row_group,
    like_words,
    row_grid,
    tpu_block_rows,
)


def _topk_kernel(*refs, num_words: int, kk: int, block_rows: int):
    in_refs = refs[:num_words]  # (block_rows, C) canonical words
    out_word_refs = refs[num_words:2 * num_words]
    io_ref = refs[-1]

    def body(rows):
        words = tuple(r[rows, :] for r in in_refs)
        rb, c = words[0].shape
        idx = jax.lax.broadcasted_iota(jnp.int32, (rb, c), 1)
        words, idx = bitonic_network_rows(words, idx)
        for r, w in zip(out_word_refs, as_words(words)):
            r[rows, :] = w[:, :kk]
        io_ref[rows, :] = idx[:, :kk]

    for_each_row_group(block_rows, body)


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def topk_desc(
    keys, *, k: int, block_rows: int = 256, interpret: bool = True
):
    """Top-k per row of (R, C) canonical keys where SMALLER canonical
    value == HIGHER score (caller pre-encodes with a descending codec,
    see ops.topk).

    Args:
        keys: (R, C) uint32 canonical key words (bare array or tuple,
            msw first); C a power of two.
        k: columns to emit per row.
        block_rows: rows sorted per grid program (made TPU-legal by
            ``bitonic.tpu_block_rows``; the last block may be partial).
    Returns:
        (top_keys (R, k) in the input key structure, top_idx (R, k)
        int32) — the k smallest canonical keys per row, ties toward the
        smaller column index.
    """
    words = as_words(keys)
    nw = len(words)
    r, c = words[0].shape
    assert all(w.dtype == jnp.uint32 and w.shape == (r, c) for w in words)
    block_rows = tpu_block_rows(r, min(block_rows, r))
    spec_in = pl.BlockSpec((block_rows, c), lambda i: (i, 0))
    spec_out = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(
            _topk_kernel, num_words=nw, kk=k, block_rows=block_rows
        ),
        grid=row_grid(r, block_rows),
        in_specs=[spec_in] * nw,
        out_specs=[spec_out] * (nw + 1),
        out_shape=[jax.ShapeDtypeStruct((r, k), jnp.uint32)] * nw
        + [jax.ShapeDtypeStruct((r, k), jnp.int32)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="topk_desc",
    )(*words)
    return like_words(tuple(out[:nw]), keys), out[nw]
