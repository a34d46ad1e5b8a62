"""Public jit'd entry points for the Pallas kernels.

Handles: key-codec encoding to totally-ordered uint32 word tuples
(``core/key_codec`` — one word for <= 32-bit dtypes, hi/lo pairs for
64-bit), pallas-vs-xla implementation dispatch, and interpret-mode
selection (Pallas kernels run interpret=True on the CPU container,
natively on TPU).

Every kernel entry accepts keys either as a bare uint32 array (the
one-word fast path, bit-compatible with the pre-codec API) or as a
tuple of canonical uint32 word arrays (most significant first), and
returns keys in the same structure.  ``to_sortable``/``from_sortable``
remain as one-word convenience shims over the codec layer for the
legacy 32-bit dtypes.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core.key_codec import codec_for
from repro.kernels import bitonic as _bitonic
from repro.kernels import ref as _ref
from repro.kernels import splitter as _splitter
from repro.kernels import topk as _topk
from repro.kernels.bitonic import as_words


def default_interpret() -> bool:
    """Pallas interpret-mode default.

    Returns:
        True off-TPU (kernels emulate on CPU), False on TPU (native).
    """
    return jax.default_backend() != "tpu"


def default_impl() -> str:
    """Kernel implementation default.

    Returns:
        The ``REPRO_SORT_IMPL`` env var if set to "pallas"/"xla", else
        "pallas" on TPU and "xla" (pure-jnp oracles) elsewhere.
    """
    env = os.environ.get("REPRO_SORT_IMPL")
    if env in ("pallas", "xla"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def to_sortable(x: jax.Array) -> jax.Array:
    """Map x to ONE uint32 word whose unsigned order == x's natural order.

    One-word convenience shim over :func:`repro.core.key_codec.codec_for`
    for the legacy 32-bit dtypes (int32/uint32/float32, bf16/f16 widened).
    64-bit dtypes need two words: use the codec API directly.

    Args:
        x: array of a one-word dtype.
    Returns:
        uint32 array of x's shape.
    Raises:
        TypeError: for unsupported or two-word dtypes.
    """
    codec = codec_for(x.dtype)
    if codec.num_words != 1:
        raise TypeError(
            f"{codec.dtype_name} keys encode to {codec.num_words} words; "
            "use repro.core.key_codec.codec_for(...).encode"
        )
    return codec.encode(x)[0]


def from_sortable(u: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`to_sortable` (one-word dtypes only).

    Args:
        u: uint32 canonical keys.
        dtype: target one-word dtype (int32/uint32/float32, widened
            bool/8/16-bit floats and ints).
    Returns:
        Array of ``dtype`` with the natural order of the uint32 input.
    Raises:
        TypeError: for unsupported or two-word (64-bit) dtypes.
    """
    codec = codec_for(dtype)
    if codec.num_words != 1:
        raise TypeError(
            f"{codec.dtype_name} keys decode from {codec.num_words} words; "
            "use repro.core.key_codec.codec_for(...).decode"
        )
    return codec.decode((u,))


def sort_tiles(
    keys,
    vals: jax.Array,
    *,
    impl: str | None = None,
    interpret: bool | None = None,
    block_rows: int | None = None,
):
    """Sort each row of (m, T) canonical keys (+int32 payload) with the
    bitonic network (DESIGN.md §8).

    Args:
        keys: (m, T) uint32 word array or tuple of word arrays (msw
            first, see ``core/key_codec``); T a power of two.
        vals: (m, T) int32 payloads (original indices for stability).
        impl: "pallas" | "xla" | None (auto via :func:`default_impl`).
        interpret: Pallas interpret mode (None = auto: True off-TPU).
        block_rows: tiles per grid program on the pallas path (None =
            auto VMEM fill, see bitonic.auto_block_rows); ignored on xla.
    Returns:
        (sorted keys in the input structure, sorted vals), each row
        lexicographically ascending on (*words, payload).
    """
    impl = impl or default_impl()
    interpret = default_interpret() if interpret is None else interpret
    faults.check("kernel.launch")  # trace-time chaos site (DESIGN.md §11)
    if impl == "pallas":
        return _bitonic.sort_tiles_kv(
            keys, vals, block_rows=block_rows, interpret=interpret
        )
    return _ref.sort_tiles_kv(keys, vals)


def sort_tiles_sample(
    keys,
    vals: jax.Array,
    *,
    num_samples: int,
    impl: str | None = None,
    interpret: bool | None = None,
    block_rows: int | None = None,
):
    """Fused Steps 2+3: sorted (m, T) tiles plus the s equidistant
    per-tile samples, from one read of the tiles.

    Args:
        As :func:`sort_tiles`, plus ``num_samples`` (must divide T).
    Returns:
        (sorted_keys, sorted_vals, sample_keys (m, s), sample_vals) —
        keys in the input structure.
    """
    impl = impl or default_impl()
    interpret = default_interpret() if interpret is None else interpret
    faults.check("kernel.launch")  # trace-time chaos site (DESIGN.md §11)
    if impl == "pallas":
        return _bitonic.sort_tiles_sample_kv(
            keys,
            vals,
            num_samples=num_samples,
            block_rows=block_rows,
            interpret=interpret,
        )
    return _ref.sort_tiles_sample_kv(keys, vals, num_samples=num_samples)


def splitter_ranks(
    keys, vals, sp_keys, sp_vals, *, impl: str | None = None,
    interpret: bool | None = None,
):
    """(m, S) rank of each splitter in each tile (canonical keys).

    Args:
        keys/vals: (m, T) canonical key words + int32 payloads.
        sp_keys/sp_vals: (m, S) per-tile splitters, same key structure.
        impl/interpret: as :func:`sort_tiles`.
    Returns:
        (m, S) int32 ranks (see kernels.splitter.splitter_ranks).
    """
    impl = impl or default_impl()
    if impl == "pallas":
        interpret = default_interpret() if interpret is None else interpret
        return _splitter.splitter_ranks(
            keys, vals, sp_keys, sp_vals, interpret=interpret
        )
    return _ref.splitter_ranks(keys, vals, sp_keys, sp_vals)


def splitter_partition(
    keys, vals, sp_keys, sp_vals, *, impl: str | None = None,
    interpret: bool | None = None, block_rows: int | None = None,
):
    """Fused Steps 6+7 epilogue: (ranks (m, S), counts (m, S+1)) per tile
    from one read of the tiles (canonical keys, multi-word accepted).

    Args/Returns: as :func:`splitter_ranks`, plus bucket counts.
    """
    impl = impl or default_impl()
    if impl == "pallas":
        interpret = default_interpret() if interpret is None else interpret
        return _splitter.splitter_partition(
            keys, vals, sp_keys, sp_vals,
            block_rows=block_rows, interpret=interpret,
        )
    return _ref.splitter_partition(keys, vals, sp_keys, sp_vals)


def topk(
    x: jax.Array,
    k: int,
    *,
    impl: str | None = None,
    interpret: bool | None = None,
):
    """Row-wise top-k (descending) of (R, C) scores.

    Args:
        x: (R, C) scores in any supported key dtype (int/uint/float,
            8..64-bit, bool — see ``core/key_codec``).
        k: 1 <= k <= C.
        impl/interpret: as :func:`sort_tiles`.
    Returns:
        (values (R, k) in x.dtype, indices (R, k) int32); ties toward
        the smaller index, matching jax.lax.top_k.  Non-power-of-two C
        (real vocab sizes: 50257, 151936, ...) is padded up with
        worst-score columns, which can never enter the top-k since
        k <= C (pad columns lose index ties too).
    """
    impl = impl or default_impl()
    # Descending codec: ascending canonical order == descending score.
    codec = codec_for(x.dtype, descending=True)
    words = codec.encode(x)
    r, c = words[0].shape
    assert 1 <= k <= c, (k, c)
    cp = 1
    while cp < c:
        cp *= 2
    if cp > c:  # all-ones == the worst possible encoded score
        words = tuple(
            jnp.concatenate(
                [w, jnp.full((r, cp - c), jnp.uint32(0xFFFFFFFF))], axis=1
            )
            for w in words
        )
        c = cp
    if impl == "pallas":
        interpret = default_interpret() if interpret is None else interpret
        block_rows = _bitonic.largest_pow2_divisor(r, 256)
        tk, ti = _topk.topk_desc(
            words, k=k, block_rows=block_rows, interpret=interpret
        )
    else:
        tk, ti = _ref.topk_desc(words, k=k)
    return codec.decode(as_words(tk)), ti
