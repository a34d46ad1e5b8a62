"""Keys drawn uniformly over every value of an integer dtype.

Source: the uniform key distribution of the paper's own benchmark of
32-bit keys with values on one GPU (Dehne & Zaboli, "Deterministic
Sample Sort For GPUs", arXiv:1002.4464) and of Leischner, Osipov and
Sanders, "GPU sample sort" (IPDPS 2010).  Made on the device as random
bits reinterpreted as the key type, as ``chip_smoke.py`` does.

Parameters (the traffic file): ``n``, the number of keys.  Nothing is
assumed beyond the source.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def generate(key, dtype, params: dict) -> jax.Array:
    """``params["n"]`` keys of ``dtype``, every bit pattern equally likely."""
    dtype = jnp.dtype(dtype)
    unsigned = jnp.dtype(f"uint{8 * dtype.itemsize}")
    bits = jax.random.bits(key, (int(params["n"]),), unsigned)
    return jax.lax.bitcast_convert_type(bits, dtype)
