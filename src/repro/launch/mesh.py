"""Production mesh factory.

Single pod : (16, 16)      axes ("data", "model")        = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

A function, not a module constant: importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS *before* any jax import)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis Auto (XLA propagates shardings)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int = 1, axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    ndev = len(jax.devices())
    n = min(n, ndev)
    return make_mesh((n, 1), axes)
