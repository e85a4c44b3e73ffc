"""Jitted public wrapper for the fused complex multiply kernel."""

from __future__ import annotations

import functools

import jax

from .twiddle import complex_multiply_pallas


@functools.partial(jax.jit, static_argnames=("block",))
def complex_multiply(a, b, *, block: int = 1024):
    return complex_multiply_pallas(a, b, block=block)
