"""Jitted public wrapper for the tiled transpose kernel."""

from __future__ import annotations

import functools

import jax

from .transpose import transpose_tiled


@functools.partial(jax.jit, static_argnames=("block",))
def transpose(x: jax.Array, *, block: int = 128) -> jax.Array:
    return transpose_tiled(x, block=block)
