"""Production mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (dryrun.py sets XLA_FLAGS before jax initializes).
"""

from __future__ import annotations

import jax


def make_gspmd_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh whose axes are ``Auto``: the LM stack shards by sharding
    constraints and GSPMD propagation, which ``Explicit`` axes (the default
    of ``jax.make_mesh``) replace with sharding in types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """(16, 16) = 256 chips/pod (data, model), or (2, 16, 16) = 512 chips
    (pod, data, model) for the two-pod configuration."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_gspmd_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests, CPU examples)."""
    return make_gspmd_mesh((data, model), ("data", "model"))
