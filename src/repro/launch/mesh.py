"""Mesh construction for the production topology.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init)."""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16x16 single-pod (256 chips) or
    2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes, *, devices=None):
    """``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_smoke_mesh(n_devices: int | None = None, *, pods: int = 1):
    """Small test mesh over the available (or host-flag-faked) devices."""
    n = n_devices or len(jax.devices())
    if pods > 1:
        assert n % pods == 0
        per = n // pods
        d = int(np.floor(np.sqrt(per)))
        while per % d:
            d -= 1
        return make_mesh((pods, d, per // d), ("pod", "data", "model"))
    d = int(np.floor(np.sqrt(n)))
    while n % d:
        d -= 1
    return make_mesh((d, n // d), ("data", "model"))


def dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in ("pod", "data")
                        if a in mesh.axis_names]))
