"""Order-preserving key codecs: any supported dtype -> sortable uint32 words.

The whole sort engine (kernels + bucket pipeline) operates on tuples of
canonical **uint32 key words, most-significant word first**, compared
lexicographically with the int32 payload as the final tiebreak word.  A
:class:`KeyCodec` is the bridge between a user dtype and that canonical
domain: an order-preserving bijective encoding

    encode :  x  ->  (w_0, ..., w_{num_words-1})   (uint32 words)
    decode :  words -> x                            (exact inverse)

such that ``x < y`` in the dtype's total order **iff** ``encode(x) <
encode(y)`` lexicographically as unsigned words.  See DESIGN.md §6 for
the encoding tables and the two-word compare cost model.

Encodings (all classic radix-sort transforms):

  ==========  =====  =====================================================
  dtype       words  transform (per 32-bit word)
  ==========  =====  =====================================================
  uint32      1      identity
  int32       1      bitcast; flip sign bit (``^ 0x8000_0000``)
  float32     1      bitcast; sign bit set -> ``~u`` else ``u | SIGN``
  uint64      2      split into (hi, lo) uint32
  int64       2      flip sign bit of hi, split
  float64     2      64-bit float flip applied across (hi, lo), split
  bool        1      widen to uint32 (False=0 < True=1)
  u/int8,16   1      widen to u/int32, then the 32-bit transform
  bf16, f16   1      upcast to float32 (exact), then the float32 flip
  ==========  =====  =====================================================

The float transforms induce the IEEE-754 **total order**
``-NaN < -inf < ... < -0.0 < +0.0 < ... < +inf < +NaN`` — which places
``np.nan`` (a positive quiet NaN) last, matching ``jnp.sort`` /
``np.sort`` (see DESIGN.md §6 for why the orders agree on real inputs).

``descending=True`` is a *codec-level* complement: every encoded word is
inverted (``~w``), an order-reversing bijection of the canonical domain.
Payloads are never complemented, so equal keys still tie-break by
original index and descending sorts stay stable — matching
``jnp.sort(x, descending=True)`` / ``jnp.argsort(..., descending=True,
stable=True)``.

64-bit dtypes require x64 mode (``jax.config.update("jax_enable_x64",
True)`` or the ``jax.enable_x64(True)`` context manager); the
codec raises a clear error otherwise.  The 64 <-> 2x32 split uses
``lax.bitcast_convert_type``'s trailing-dimension form, so no 64-bit
arithmetic is emitted — only the input/output arrays themselves are
64-bit.

Example (doctested)::

    >>> import jax.numpy as jnp
    >>> from repro.core.key_codec import codec_for
    >>> c = codec_for(jnp.float32)
    >>> words = c.encode(jnp.asarray([1.5, -2.0, 0.0], jnp.float32))
    >>> len(words), words[0].dtype
    (1, dtype('uint32'))
    >>> c.decode(words)
    Array([ 1.5, -2. ,  0. ], dtype=float32)
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_SIGN = jnp.uint32(0x80000000)

#: dtypes with a codec, grouped by canonical word count.
ONE_WORD_DTYPES = (
    "uint32", "int32", "float32",
    "bfloat16", "float16",
    "int16", "int8", "uint16", "uint8", "bool",
)
TWO_WORD_DTYPES = ("uint64", "int64", "float64")
SUPPORTED_DTYPES = ONE_WORD_DTYPES + TWO_WORD_DTYPES


def _require_x64(name: str) -> None:
    if not jax.config.jax_enable_x64:
        raise TypeError(
            f"{name} keys require x64 mode: enable it globally with "
            'jax.config.update("jax_enable_x64", True) or locally with '
            "the jax.enable_x64(True) context manager"
        )


def _flip_f32(u):
    """uint32 bitcast of a float32 -> totally-ordered uint32."""
    return jnp.where((u & _SIGN) != 0, ~u, u | _SIGN)


def _unflip_f32(u):
    return jnp.where((u & _SIGN) != 0, u & ~_SIGN, ~u)


def _split64(x):
    """(hi, lo) uint32 words of a 64-bit array, via the trailing-dim
    bitcast (little-endian word order: index 0 is the LOW word)."""
    w = jax.lax.bitcast_convert_type(x, jnp.uint32)  # (..., 2) = [lo, hi]
    return w[..., 1], w[..., 0]


def _join64(hi, lo, dtype):
    w = jnp.stack([lo, hi], axis=-1)
    return jax.lax.bitcast_convert_type(w, dtype)


@dataclasses.dataclass(frozen=True)
class KeyCodec:
    """Order-preserving bijection between a user dtype and uint32 words.

    Attributes:
        dtype_name: canonical dtype name (e.g. ``"float64"``).
        num_words: uint32 words per key (1 for <= 32-bit, 2 for 64-bit).
        descending: if True, every encoded word is complemented so that
            ascending canonical order == descending user order.

    Hashable and trace-time static: derive it once per call site with
    :func:`codec_for` and close over it.
    """

    dtype_name: str
    num_words: int
    descending: bool = False

    @property
    def dtype(self):
        """The user-facing jnp dtype this codec encodes."""
        return jnp.dtype(self.dtype_name)

    # -- encode ---------------------------------------------------------

    def encode(self, x: jax.Array) -> tuple[jax.Array, ...]:
        """Map ``x`` (any shape, self.dtype) to canonical uint32 words.

        Args:
            x: array of ``self.dtype``.
        Returns:
            Tuple of ``num_words`` uint32 arrays of x's shape, most
            significant word first; lexicographic unsigned order of the
            tuples == the dtype's total order (reversed if descending).
        """
        dt = jnp.dtype(x.dtype)
        assert dt == self.dtype, (dt, self.dtype)
        name = self.dtype_name
        if name in ("bfloat16", "float16"):
            x = x.astype(jnp.float32)
            name = "float32"
        elif name in ("int8", "int16"):
            x = x.astype(jnp.int32)
            name = "int32"
        elif name in ("uint8", "uint16", "bool"):
            x = x.astype(jnp.uint32)
            name = "uint32"

        if name == "uint32":
            words = (x,)
        elif name == "int32":
            words = (jax.lax.bitcast_convert_type(x, jnp.uint32) ^ _SIGN,)
        elif name == "float32":
            words = (_flip_f32(jax.lax.bitcast_convert_type(x, jnp.uint32)),)
        elif name == "uint64":
            _require_x64(name)
            words = _split64(x)
        elif name == "int64":
            _require_x64(name)
            hi, lo = _split64(x)
            words = (hi ^ _SIGN, lo)
        elif name == "float64":
            _require_x64(name)
            hi, lo = _split64(x)
            neg = (hi & _SIGN) != 0
            words = (
                jnp.where(neg, ~hi, hi | _SIGN),
                jnp.where(neg, ~lo, lo),
            )
        else:  # pragma: no cover - codec_for validates
            raise TypeError(f"unsupported sort key dtype {self.dtype_name}")
        if self.descending:
            words = tuple(~w for w in words)
        return words

    # -- decode ---------------------------------------------------------

    def decode(self, words: tuple[jax.Array, ...]) -> jax.Array:
        """Exact inverse of :meth:`encode`.

        Args:
            words: tuple of ``num_words`` uint32 arrays (msw first).
        Returns:
            Array of ``self.dtype`` with ``decode(encode(x)) == x``.
        """
        assert len(words) == self.num_words, (len(words), self.num_words)
        if self.descending:
            words = tuple(~w for w in words)
        name = self.dtype_name
        if name in ("bfloat16", "float16", "float32"):
            f32 = jax.lax.bitcast_convert_type(
                _unflip_f32(words[0]), jnp.float32
            )
            return f32.astype(self.dtype)
        if name in ("int8", "int16", "int32"):
            i32 = jax.lax.bitcast_convert_type(words[0] ^ _SIGN, jnp.int32)
            return i32.astype(self.dtype)
        if name in ("uint8", "uint16", "uint32"):
            return words[0].astype(self.dtype)
        if name == "bool":
            return words[0] != 0
        hi, lo = words
        _require_x64(name)
        if name == "uint64":
            return _join64(hi, lo, jnp.uint64)
        if name == "int64":
            return _join64(hi ^ _SIGN, lo, jnp.int64)
        if name == "float64":
            pos = (hi & _SIGN) != 0  # encoded msb set <=> original >= +0.0
            return _join64(
                jnp.where(pos, hi & ~_SIGN, ~hi),
                jnp.where(pos, lo, ~lo),
                jnp.float64,
            )
        raise TypeError(f"unsupported sort key dtype {name}")


def codec_for(dtype, descending: bool = False) -> KeyCodec:
    """Build the :class:`KeyCodec` for a dtype.

    Args:
        dtype: anything ``jnp.dtype`` accepts (``jnp.float64``,
            ``"int64"``, ``np.int32``, an array's ``.dtype``, ...).
        descending: complement the encoding so canonical-ascending order
            == user-descending order (stable: payload ties untouched).
    Returns:
        A hashable, trace-time-static ``KeyCodec``.
    Raises:
        TypeError: for dtypes without a codec.
    """
    name = jnp.dtype(dtype).name
    if name in ONE_WORD_DTYPES:
        return KeyCodec(name, 1, descending)
    if name in TWO_WORD_DTYPES:
        return KeyCodec(name, 2, descending)
    raise TypeError(
        f"unsupported sort key dtype {name}; supported: {SUPPORTED_DTYPES}"
    )
