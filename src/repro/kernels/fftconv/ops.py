"""Jitted public wrapper for the fused FFT-convolution kernel."""

from __future__ import annotations

import functools
from typing import Tuple

import jax

from .fftconv import fftconv_fused_pallas, filter_spectrum_permuted


@functools.partial(jax.jit, static_argnames=("factors", "block_rows"))
def fftconv_fused(x: jax.Array, h: jax.Array, factors: Tuple[int, int],
                  *, block_rows: int = 8) -> jax.Array:
    """y[b] = circular_conv(x[b], h), fused in VMEM. x (B, nf); h (nf,)."""
    h_spec = filter_spectrum_permuted(h, factors)
    return fftconv_fused_pallas(x, h_spec, factors, block_rows=block_rows)
