"""Production meshes.  Importing this module never touches jax device
state — meshes are built inside functions only."""

from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    assert len(devices) == n, (
        f"need {n} devices, have {len(jax.devices())} — the dry-run sets "
        "XLA_FLAGS=--xla_force_host_platform_device_count=512 first"
    )
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_mesh(shape, axes):
    """Arbitrary mesh over the first prod(shape) devices."""
    import jax

    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    assert len(devices) == n, (n, len(jax.devices()))
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
