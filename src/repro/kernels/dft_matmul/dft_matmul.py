"""Pallas TPU kernel: batched four-step FFT as MXU matmuls with fused twiddle.

One grid step processes a (block_rows, n1, n2) tile of the batch entirely in
VMEM: two complex DFT matmuls (4 real MXU matmuls each, or 3 with Karatsuba)
with the twiddle multiply fused between them — no HBM round-trip between the
four steps (the CPU version pays one per stage).

This is the paper's "task": the rows of one grid step (:func:`block_rows`)
are the task size, and the kernel IS the bulk-synchronous `for_loop` body —
all rows of a block run one fused schedule, matching the paper's winning
variant.

Layout notes (TPU):
  * n2 sits in the lane dimension and n1 in sublanes: the Planner offers
    the kernel only the splits of :data:`SPLITS`, in that order.
  * the step-1 contraction is expressed with
    dot_general over the middle axis so Mosaic keeps the lane layout.
  * DFT matrices / twiddles are f32 VMEM residents shared by all rows of the
    block; the matmuls run at Precision.HIGHEST with f32 accumulation (the
    default precision is one bf16 pass on a TPU).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..interpret import resolve_interpret

#: named scope of the ``pallas_call``; the profiler's trace carries it in
#: the kernel's ``op_name``
FUSED_SCOPE = "repro_dft_fused"

SUBLANES = 8

#: f32 elements of one operand's tile: 512 KiB, so that the double-buffered
#: in and out tiles and the body's intermediates (about a dozen arrays of a
#: tile's size) fit the 16 MiB of scoped VMEM
TILE_ELEMS = 8 * 128 * 128


def _cdot(ar, ai, br, bi, karatsuba: bool):
    """Complex contraction: (..., k) x (k, m) -> (..., m)."""
    dn = (((ar.ndim - 1,), (0,)), ((), ()))
    mm = functools.partial(jax.lax.dot_general, dimension_numbers=dn,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    if karatsuba:
        p1 = mm(ar, br)
        p2 = mm(ai, bi)
        p3 = mm(ar + ai, br + bi)
        return p1 - p2, p3 - p1 - p2
    return mm(ar, br) - mm(ai, bi), mm(ar, bi) + mm(ai, br)


def _four_step_kernel(xr_ref, xi_ref, w1r_ref, w1i_ref, twr_ref, twi_ref,
                      w2r_ref, w2i_ref, or_ref, oi_ref, *,
                      n1: int, n2: int, karatsuba: bool, permuted: bool):
    bm = xr_ref.shape[0]
    ar = xr_ref[...].reshape(bm, n1, n2)
    ai = xi_ref[...].reshape(bm, n1, n2)

    # step 1: DFT_n1 along axis 1. Work on the (bm, n2, n1) view so the
    # contraction is a last-axis MXU matmul.
    art = jnp.swapaxes(ar, 1, 2)
    ait = jnp.swapaxes(ai, 1, 2)
    btr, bti = _cdot(art, ait, w1r_ref[...], w1i_ref[...], karatsuba)
    br = jnp.swapaxes(btr, 1, 2)          # (bm, k1, n2)
    bi = jnp.swapaxes(bti, 1, 2)

    # step 2: fused twiddle T[k1, n2] — stays in VREGs
    twr = twr_ref[...]
    twi = twi_ref[...]
    cr = br * twr - bi * twi
    ci = br * twi + bi * twr

    # step 3: DFT_n2 along the last (lane) axis
    dr, di = _cdot(cr, ci, w2r_ref[...], w2i_ref[...], karatsuba)

    if permuted:
        or_ref[...] = dr.reshape(bm, n1 * n2)
        oi_ref[...] = di.reshape(bm, n1 * n2)
    else:
        # step 4: digit transpose X[k2*n1 + k1] = D[k1, k2]
        or_ref[...] = jnp.swapaxes(dr, 1, 2).reshape(bm, n1 * n2)
        oi_ref[...] = jnp.swapaxes(di, 1, 2).reshape(bm, n1 * n2)


#: the ``(n1, n2)`` splits the Planner gives the kernel: those compiled for
#: a v5e and run on the chip.  Step 1 swaps n1 onto the 128 lanes, so a
#: narrower n1 pads ``art``/``ait`` past what :func:`block_rows` sizes a
#: tile for (at (8, 128), 16 MiB for the pair), and (128, 64) is refused
#: by Mosaic for its 64 lanes.
SPLITS = ((64, 128), (128, 128))


def kernel_factors(factors: Tuple[int, ...]) -> Optional[Tuple[int, int]]:
    """``factors`` in the ``(n1, n2)`` order of :data:`SPLITS`, or None
    where the kernel is not given the split: 8192 runs as (64, 128),
    16384 as (128, 128)."""
    for split in (tuple(factors), tuple(factors)[::-1]):
        if split in SPLITS:
            return split
    return None


def block_rows(n: int) -> int:
    """Rows of one grid step for length ``n``: a multiple of 8 whose tile
    holds about :data:`TILE_ELEMS` elements."""
    return max(SUBLANES, TILE_ELEMS // n // SUBLANES * SUBLANES)


def fft_four_step_pallas(x: Tuple[jax.Array, jax.Array],
                         factors: Tuple[int, int],
                         *, sign: int = -1, karatsuba: bool = False,
                         permuted: bool = False,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Batched c2c DFT along the last axis, unscaled, with exponent sign
    ``sign`` (-1 forward, +1 inverse); x = (re, im), shape (..., n).

    The outputs take the inputs' buffers (each tile is read whole before
    it is written), so a stage holds one copy of its array; XLA copies an
    input that is still needed.  A batch that the tile's rows do not
    divide ends in a partial tile, whose rows past the end are computed
    and dropped.

    ``interpret=None`` interprets the kernel body on the CPU backend and
    compiles it on a TPU (:func:`repro.kernels.interpret.resolve_interpret`).
    Inside ``shard_map`` it runs compiled only: Pallas's interpreter
    refuses operands that vary over mesh axes.
    """
    from repro.core import algo

    n1, n2 = factors
    n = n1 * n2
    xr, xi = x
    assert xr.shape[-1] == n, (xr.shape, factors)
    batch_shape = xr.shape[:-1]
    b = math.prod(batch_shape)
    xr2 = xr.reshape(b, n)
    xi2 = xi.reshape(b, n)

    bm = min(block_rows(n), b)          # one tile: the block is the array

    tables = (algo.dft_matrix(n1, sign) + algo.twiddle_factors(n1, n2, sign)
              + algo.dft_matrix(n2, sign))

    # a last tile past the batch's end reads rows that no output keeps
    grid = (pl.cdiv(b, bm),)
    data_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    const = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))

    kernel = functools.partial(_four_step_kernel, n1=n1, n2=n2,
                               karatsuba=karatsuba, permuted=permuted)
    # inside shard_map the outputs vary over the mesh axes the rows do
    vma = jax.typeof(xr2).vma | jax.typeof(xi2).vma
    out_shape = [jax.ShapeDtypeStruct((b, n), jnp.float32, vma=vma)] * 2
    with jax.named_scope(FUSED_SCOPE):
        orr, oii = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[data_spec, data_spec,
                      const((n1, n1)), const((n1, n1)),
                      const((n1, n2)), const((n1, n2)),
                      const((n2, n2)), const((n2, n2))],
            out_specs=[data_spec, data_spec],
            out_shape=out_shape,
            input_output_aliases={0: 0, 1: 1},
            interpret=resolve_interpret(interpret),
        )(xr2, xi2, *tables)
    return orr.reshape(*batch_shape, n), oii.reshape(*batch_shape, n)
