"""Jitted public wrapper for the dft_matmul kernel.

The kernel runs compiled on a TPU and in Pallas interpret mode on the CPU
backend (see :func:`repro.kernels.interpret.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax

from .dft_matmul import fft_four_step_pallas


@functools.partial(jax.jit, static_argnames=("factors", "sign", "karatsuba",
                                             "permuted"))
def fft_four_step(x: Tuple[jax.Array, jax.Array], factors: Tuple[int, int],
                  *, sign: int = -1, karatsuba: bool = False,
                  permuted: bool = False) -> Tuple[jax.Array, jax.Array]:
    return fft_four_step_pallas(x, tuple(factors), sign=sign,
                                karatsuba=karatsuba, permuted=permuted)
