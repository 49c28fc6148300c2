"""Production mesh construction (assignment-fixed shapes).

single pod : (data=16, model=16)            -- 256 chips (TPU v5e pod)
multi pod  : (pod=2, data=16, model=16)     -- 512 chips

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run forces 512 host devices via XLA_FLAGS before
any jax import; everything else sees the real device count).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """jax.make_mesh with every axis Auto-sharded."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (tests / CPU driver runs)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    names = tuple(mesh.axis_names)
    return ("pod", "data") if "pod" in names else ("data",)


def dp_size(mesh: jax.sharding.Mesh) -> int:
    return int(
        __import__("math").prod(mesh.shape[a] for a in dp_axes(mesh))
    )


def tp_size(mesh: jax.sharding.Mesh) -> int:
    return int(mesh.shape["model"])
