"""Production meshes (TPU v5e target).

Functions, never module-level constants — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD = (16, 16)                    # 256 chips
MULTI_POD = (2, 16, 16)                  # 2 pods x 256 = 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many real devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
