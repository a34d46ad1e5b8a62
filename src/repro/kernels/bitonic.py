"""Pallas TPU kernel: row-blocked bitonic (key, value) sort of VMEM tiles.

This is the TPU adaptation of Steps 2/4/9 of GPU BUCKET SORT (Dehne &
Zaboli 2010).  The paper sorts 2K-item sublists per SM in shared memory
with a bitonic network because it is branch-free and SIMD-perfect; the
same argument holds on the TPU VPU: every compare-exchange pass is a
pair of lane rotations + vectorized compare/select with *no*
data-dependent control flow, so the whole network lowers to
straight-line vector code.

Layout notes (target = TPU v5e; see DESIGN.md §3):
  * One grid program sorts a ``(block_rows, T)`` BLOCK of tiles held in
    VMEM, running the compare-exchange network along the lane axis in
    groups of 8 rows (one dense 8-sublane x 128-lane vreg row per 128
    keys).  The network is traced once for an (8, T) group and a
    ``fori_loop`` walks the groups of the block.
  * ``block_rows`` is auto-picked by :func:`auto_block_rows` to fill a
    VMEM budget and made TPU-legal by :func:`tpu_block_rows` (a multiple
    of 8 rows, or all rows); the grid axis is declared ``parallel``
    (programs are independent) so Mosaic may pipeline blocks freely.
  * ``T`` must be a power of two.  A pass at stride ``d`` fetches each
    element's partner ``i ^ d`` with two lane rotations (``pltpu.roll``
    by d and T - d) and an iota mask, so no pass reshapes or gathers.
  * Comparison is LEXICOGRAPHIC on ``(*key_words, value)``.  Keys are
    tuples of canonical uint32 words, most significant first (one word
    for <= 32-bit dtypes, two for 64-bit — see ``core/key_codec``); the
    caller passes the original element index as the value, which (a)
    makes every compared pair unique so the regular-sampling bucket
    bound ≤ 2n/s holds for any duplicate distribution, and (b) makes
    the sort STABLE.  The compare cost is one extra vector cmp+select
    chain per extra word (DESIGN.md §6), data movement scales with the
    word count.
  * Step 3 of the algorithm (equidistant sample extraction) is a strided
    XLA slice of the sorted tiles taken right after the kernel: Mosaic
    refuses lane-strided reads inside the kernel.

Keys: one or more canonical uint32 word arrays; values: int32.  Every
public entry accepts either a bare ``(m, T)`` uint32 array (the one-word
fast path, bit-compatible with the pre-codec API) or a tuple of word
arrays, and returns keys in the same structure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budget for one grid program's block: in + out, key words + values
# (2*(num_words+1) buffers of block_rows * T * 4 bytes).  The pipeline
# double-buffers them, and the network's temporaries for one 8-row group
# come on top, so the scoped limit is raised above v5e's 16 MiB default
# (of 128 MiB VMEM per core).
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
_SUBLANES = 8


def as_words(keys) -> tuple[jax.Array, ...]:
    """Normalize a key argument to a tuple of uint32 word arrays.

    Args:
        keys: a single uint32 array (one-word keys) or a tuple/list of
            uint32 word arrays, most significant first.
    Returns:
        Tuple of word arrays (length >= 1, all the same shape).
    """
    if isinstance(keys, (tuple, list)):
        assert len(keys) >= 1
        return tuple(keys)
    return (keys,)


def like_words(words: tuple[jax.Array, ...], keys):
    """Return ``words`` in the structure of the original ``keys`` arg:
    a bare array if the caller passed one, else a tuple."""
    if isinstance(keys, (tuple, list)):
        return tuple(words)
    assert len(words) == 1
    return words[0]


def lex_gt(lo_parts, hi_parts):
    """Elementwise lexicographic ``lo > hi`` over parallel word lists.

    lo_parts/hi_parts: equal-length sequences of arrays compared word by
    word, most significant first (the caller appends the payload as the
    final word).  Returns a bool array of the common shape.
    """
    gt = lo_parts[0] > hi_parts[0]
    eq = lo_parts[0] == hi_parts[0]
    for a, b in zip(lo_parts[1:], hi_parts[1:]):
        gt = gt | (eq & (a > b))
        eq = eq & (a == b)
    return gt


def _row_compare_exchange(parts, d: int, size: int):
    """One bitonic compare-exchange pass at stride ``d`` within ``size``
    blocks, along the LAST axis of (..., C) arrays, applied jointly to
    every array in ``parts`` (key words + payload).

    Element i is paired with i ^ d; the pair is ascending iff
    (i & size) == 0.  The partner is fetched by two lane rotations (by
    ``d`` and by ``C - d``) and chosen with an iota mask on (i & d), so
    the pass is elementwise work plus rotations: no reshape, gather or
    data-dependent control flow, which is what Mosaic lowers to
    straight-line vector code.  Outside a kernel ``pltpu.roll`` lowers
    to ``jnp.roll``, so the same function is also the pure-jnp network.
    """
    c = parts[0].shape[-1]
    ax = parts[0].ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, ax)
    upper = (lane & d) != 0  # element is the high half of its pair
    desc = (lane & size) != 0  # pair sorts descending
    partner = [
        jax.lax.select(upper, pltpu.roll(p, d, ax), pltpu.roll(p, c - d, ax))
        for p in parts
    ]
    # An element keeps the smaller of its pair iff it is the low half of
    # an ascending pair or the high half of a descending one.  It takes
    # its partner iff that partner is smaller (keep_min) or larger (not
    # keep_min); for a pair of equal elements either choice is the same
    # value, so "take iff keep_min == (self > partner)" is exact.
    take = (upper == desc) == lex_gt(parts, partner)
    return tuple(jax.lax.select(take, q, p) for p, q in zip(parts, partner))


def bitonic_network_rows(keys, vals):
    """Bitonic sort along the last axis of (..., C); C = power of two.

    Args:
        keys: uint32 word array (or tuple of word arrays, msw first),
            shape (..., C).
        vals: int32 payload, same shape.
    Returns:
        (sorted keys in the input structure, sorted vals): every row
        ascending in the lexicographic (*words, payload) order.

    Unrolled at trace time: log2(C)*(log2(C)+1)/2 vectorized passes.
    """
    words = as_words(keys)
    c = words[0].shape[-1]
    assert c & (c - 1) == 0, f"row width {c} must be a power of two"
    parts = words + (vals,)
    size = 2
    while size <= c:
        d = size // 2
        while d >= 1:
            parts = _row_compare_exchange(parts, d, size)
            d //= 2
        size *= 2
    return like_words(parts[:-1], keys), parts[-1]


def largest_pow2_divisor(m: int, limit: int) -> int:
    """Largest power of two that divides ``m`` and is <= ``limit``.

    The single clamp rule every row-blocked kernel uses to turn a
    block-count bound into a grid-compatible block size.
    """
    b = 1
    while b * 2 <= limit and m % (b * 2) == 0:
        b *= 2
    return b


def auto_block_rows(
    m: int, t: int, vmem_budget_bytes: int = _VMEM_BUDGET_BYTES,
    num_words: int = 1,
) -> int:
    """Largest power-of-two divisor of ``m`` whose (block_rows, T) block
    fits the VMEM budget.

    Args:
        m: tile count.
        t: tile width.
        vmem_budget_bytes: VMEM to fill (default 8 MiB).
        num_words: uint32 key words per element; the block holds
            2*(num_words+1) buffers (in+out, words+values) of
            block_rows*T*4 bytes each.
    """
    per_row = 2 * (num_words + 1) * 4 * t
    return largest_pow2_divisor(m, max(vmem_budget_bytes // per_row, 1))


def tpu_block_rows(m: int, block_rows: int) -> int:
    """Make a row-block size one that Mosaic accepts: the second-minor
    block dim must be a multiple of the 8 sublanes or span all ``m``
    rows.  Smaller blocks are raised to 8 rows (or all rows when m < 8);
    the grid then rounds up and the last block may be partial (rows are
    independent, so its out-of-range rows are computed and dropped)."""
    if block_rows % _SUBLANES == 0 or block_rows == m:
        return block_rows
    return min(_SUBLANES, m)


def effective_block_rows(
    m: int, t: int, block_rows: int | None, num_words: int = 1
) -> int:
    """Resolve a requested block_rows against an actual tile count: None
    = auto VMEM fill; an explicit value is an upper bound — all ``m``
    rows when it covers them, else the largest power-of-two divisor of
    ``m`` below it.  Either way the result is then made TPU-legal by
    :func:`tpu_block_rows` (a multiple of 8 rows, or all of them).
    Idempotent: a resolved value (as plans carry) resolves to itself."""
    if block_rows is None:
        br = auto_block_rows(m, t, num_words=num_words)
    else:
        assert block_rows >= 1, block_rows
        br = m if block_rows >= m else largest_pow2_divisor(m, block_rows)
    return tpu_block_rows(m, br)


def row_grid(m: int, block_rows: int) -> tuple[int]:
    """Grid over row blocks; the last block may be partial."""
    return (-(-m // block_rows),)


def for_each_row_group(block_rows: int, body) -> None:
    """Run ``body(rows)`` over the block in groups of 8 sublanes, where
    ``rows`` is a ``pl.ds`` row slice.  The network is traced once for
    an (8, T) group, so Mosaic's unrolled code stays the size of one
    vreg row whatever ``block_rows`` is."""
    if block_rows <= _SUBLANES:
        body(pl.ds(0, block_rows))
        return

    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * _SUBLANES, _SUBLANES), _SUBLANES))
        return carry

    jax.lax.fori_loop(0, block_rows // _SUBLANES, step, 0)


def compiler_params() -> pltpu.CompilerParams:
    """Blocks are independent: let Mosaic parallelize the grid axis."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    )


def _block_kernel(*refs, num_words: int, block_rows: int):
    """Kernel body: refs = num_words+1 inputs (key words + vals), then
    num_words+1 outputs; the bitonic network sorts each row of the VMEM
    block."""
    nw1 = num_words + 1
    in_refs, out_refs = refs[:nw1], refs[nw1:]

    def body(rows):
        words = tuple(r[rows, :] for r in in_refs[:num_words])
        words, vals = bitonic_network_rows(words, in_refs[num_words][rows, :])
        for r, w in zip(out_refs, as_words(words) + (vals,)):
            r[rows, :] = w

    for_each_row_group(block_rows, body)


def tile_sort_call(words, vals, num_samples: int, block_rows,
                   interpret: bool):
    """Row-blocked pallas launch of the bitonic tile sort: grid over
    (block_rows, T) blocks, then the optional Step-3 samples.  The
    kernel is named ``bitonic_tile_sort`` in compiled code and traces.

    Sample j of a sorted row is element (j+1)*T/s - 1, the last element
    of chunk j.  Mosaic refuses every in-kernel form of that lane-strided
    read (strided value slice, strided ref load, (s, T/s) reshape), so
    the samples are a strided XLA slice of the kernel's output."""
    nw = len(words)
    m, t = words[0].shape
    assert vals.shape == (m, t)
    assert all(w.dtype == jnp.uint32 and w.shape == (m, t) for w in words)
    assert vals.dtype == jnp.int32
    block_rows = effective_block_rows(m, t, block_rows, num_words=nw)
    blk = pl.BlockSpec((block_rows, t), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_block_kernel, num_words=nw, block_rows=block_rows),
        grid=row_grid(m, block_rows),
        in_specs=[blk] * (nw + 1),
        out_specs=[blk] * (nw + 1),
        out_shape=[jax.ShapeDtypeStruct((m, t), jnp.uint32)] * nw
        + [jax.ShapeDtypeStruct((m, t), jnp.int32)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="bitonic_tile_sort",
    )(*words, vals)
    if not num_samples:
        return out
    assert t % num_samples == 0, (t, num_samples)
    chunk = t // num_samples
    return list(out) + [o[:, chunk - 1::chunk] for o in out]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sort_tiles_kv(
    keys,
    vals: jax.Array,
    *,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Sort each row of (m, T) keys/vals independently, lexicographically.

    Args:
        keys: uint32 canonical sort-key words — a single (m, T) array or
            a tuple of word arrays (msw first), T a power of two.
        vals: int32 payload (original indices for stability), same shape.
        block_rows: tiles sorted per grid program (None = auto VMEM fill;
            explicit values are clamped, see :func:`effective_block_rows`).
    Returns:
        (sorted_keys in the input structure, sorted_vals), each row
        ascending in the lexicographic (*words, payload) order.
    """
    words = as_words(keys)
    out = tile_sort_call(words, vals, 0, block_rows, interpret)
    return like_words(tuple(out[:-1]), keys), out[-1]


@functools.partial(
    jax.jit, static_argnames=("num_samples", "block_rows", "interpret")
)
def sort_tiles_sample_kv(
    keys,
    vals: jax.Array,
    *,
    num_samples: int,
    block_rows: int | None = None,
    interpret: bool = True,
):
    """Row-blocked tile sort plus the Step-3 equidistant samples.

    Args:
        keys/vals/block_rows: as :func:`sort_tiles_kv`.
        num_samples: s equidistant samples per sorted tile; must divide T.
    Returns:
        (sorted_keys (m, T), sorted_vals (m, T),
         sample_keys (m, s), sample_vals (m, s)) — keys in the input
        structure; sample j of row i is sorted element (j+1)*T/s - 1,
        the paper's s equidistant local samples.
    """
    words = as_words(keys)
    nw = len(words)
    out = tile_sort_call(words, vals, num_samples, block_rows, interpret)
    return (
        like_words(tuple(out[:nw]), keys),
        out[nw],
        like_words(tuple(out[nw + 1:2 * nw + 1]), keys),
        out[2 * nw + 1],
    )
