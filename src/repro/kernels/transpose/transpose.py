"""Pallas TPU kernel: tiled 2D transpose with write-contiguous blocks.

The paper's key shared-memory insight (§3.2): place the barrier so transpose
tasks WRITE contiguous memory.  On TPU that becomes: each grid step reads a
(bi, bj) tile and writes the (bj, bi) tile of the output — the *output*
BlockSpec walks row-major over the transposed array, so every store is a
contiguous lane-aligned VMEM->HBM burst, and the strided access pattern is
confined to the HBM->VMEM read side where the DMA engine amortizes it.

Used by the FFT pipelines between dimension passes and by the distributed
slab rearrange.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..interpret import resolve_interpret


def _transpose_kernel(x_ref, o_ref):
    o_ref[...] = jnp.swapaxes(x_ref[...], -1, -2)


def transpose_tiled(x: jax.Array, *, block: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """(..., n, m) -> (..., m, n). Batch dims are grid-mapped."""
    *batch, n, m = x.shape
    b = 1
    for s in batch:
        b *= s
    x3 = x.reshape(b, n, m)
    bi = min(block, n)
    bj = min(block, m)
    while n % bi:
        bi -= 1
    while m % bj:
        bj -= 1

    grid = (b, m // bj, n // bi)  # output-major walk: write-contiguous
    out = pl.pallas_call(
        _transpose_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bi, bj), lambda k, j, i: (k, i, j))],
        out_specs=pl.BlockSpec((1, bj, bi), lambda k, j, i: (k, j, i)),
        out_shape=jax.ShapeDtypeStruct((b, m, n), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x3)
    return out.reshape(*batch, m, n)
