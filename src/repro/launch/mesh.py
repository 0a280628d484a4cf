"""Production meshes. A function, not a module constant: importing this module
must never touch jax device state (the dry-run sets XLA_FLAGS first)."""

from __future__ import annotations

import jax


def _axis_types(n: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"), **_axis_types(2))
