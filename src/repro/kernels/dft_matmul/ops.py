"""Jitted public wrapper for the dft_matmul kernel.

The kernel runs compiled on a TPU and in Pallas interpret mode on the CPU
backend (see :func:`repro.kernels.interpret.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax

from .dft_matmul import fft_four_step_pallas


@functools.partial(jax.jit, static_argnames=("factors", "karatsuba", "permuted",
                                             "block_rows"))
def fft_four_step(x: Tuple[jax.Array, jax.Array], factors: Tuple[int, int],
                  *, karatsuba: bool = False, permuted: bool = False,
                  block_rows: int = 8) -> Tuple[jax.Array, jax.Array]:
    return fft_four_step_pallas(x, tuple(factors), karatsuba=karatsuba,
                                permuted=permuted, block_rows=block_rows)
