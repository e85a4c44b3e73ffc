"""Pallas TPU kernel: fused FFT convolution (FlashFFTConv-style).

One grid step performs, entirely in VMEM for a (block_rows, nf) tile:

    spectrum = four_step_fft(x)          # 2 complex matmuls + twiddle
    spectrum *= filter_spectrum          # fused pointwise complex multiply
    y = inverse_four_step(spectrum)      # 2 complex matmuls + conj twiddle

i.e. the entire y = ifft(fft(x) * H) pipeline with ONE HBM read and ONE HBM
write per element, where the unfused jnp path pays ~6 HBM round-trips (fft
passes, pointwise, ifft passes) — this is the memory-pass fix identified in
EXPERIMENTS.md §Perf-A.  The digit transposes are skipped on BOTH sides
(permuted frequency order; the pointwise product commutes with the
permutation), so no in-kernel transposes are needed at all.

The filter spectrum is precomputed once per filter in permuted order by
``filter_spectrum_permuted`` (ref-validated) and broadcast to all rows of
the grid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..interpret import resolve_interpret


def _cdot(ar, ai, br, bi):
    dn = (((ar.ndim - 1,), (0,)), ((), ()))
    mm = functools.partial(jax.lax.dot_general, dimension_numbers=dn,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    return mm(ar, br) - mm(ai, bi), mm(ar, bi) + mm(ai, br)


def _fft2f(ar, ai, w1, tw, w2, n1, n2):
    """Two-factor four-step FFT on (bm, n1, n2) blocks, permuted output."""
    art = jnp.swapaxes(ar, 1, 2)
    ait = jnp.swapaxes(ai, 1, 2)
    btr, bti = _cdot(art, ait, w1[0], w1[1])       # DFT along n1
    br = jnp.swapaxes(btr, 1, 2)
    bi = jnp.swapaxes(bti, 1, 2)
    cr = br * tw[0] - bi * tw[1]                    # twiddle (k1, n2)
    ci = br * tw[1] + bi * tw[0]
    return _cdot(cr, ci, w2[0], w2[1])              # DFT along n2 -> C[k1,k2]


def _ifft2f(cr, ci, w1i, twi, w2i, n1, n2):
    """Inverse consuming permuted order (no transposes), unnormalized."""
    br, bi = _cdot(cr, ci, w2i[0], w2i[1])          # inv DFT along k2
    er = br * twi[0] - bi * twi[1]                  # conj twiddle
    ei = br * twi[1] + bi * twi[0]
    ert = jnp.swapaxes(er, 1, 2)
    eit = jnp.swapaxes(ei, 1, 2)
    atr, ati = _cdot(ert, eit, w1i[0], w1i[1])      # inv DFT along k1
    return jnp.swapaxes(atr, 1, 2), jnp.swapaxes(ati, 1, 2)


def _fftconv_kernel(x_ref, hr_ref, hi_ref,
                    w1r, w1i, twr, twi, w2r, w2i,
                    v1r, v1i, vtr, vti, v2r, v2i,
                    o_ref, *, n1: int, n2: int):
    bm = x_ref.shape[0]
    nf = n1 * n2
    xr = x_ref[...].reshape(bm, n1, n2).astype(jnp.float32)
    xi = jnp.zeros_like(xr)
    fr, fi = _fft2f(xr, xi, (w1r[...], w1i[...]), (twr[...], twi[...]),
                    (w2r[...], w2i[...]), n1, n2)
    hr = hr_ref[...].reshape(1, n1, n2)
    hi = hi_ref[...].reshape(1, n1, n2)
    pr = fr * hr - fi * hi                          # fused spectral multiply
    pi = fr * hi + fi * hr
    yr, _ = _ifft2f(pr, pi, (v1r[...], v1i[...]), (vtr[...], vti[...]),
                    (v2r[...], v2i[...]), n1, n2)
    o_ref[...] = (yr / nf).reshape(bm, nf)


def fftconv_fused_pallas(x: jax.Array, h_spec: Tuple[jax.Array, jax.Array],
                         factors: Tuple[int, int], *, block_rows: int = 8,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Circular convolution of real rows x (B, nf) with a filter given as a
    PERMUTED-order spectrum pair (nf,).  Returns real (B, nf)."""
    from repro.core import algo

    n1, n2 = factors
    nf = n1 * n2
    b = x.shape[0]
    assert x.shape == (b, nf)
    bm = min(block_rows, b)
    while b % bm:
        bm -= 1

    w1 = algo.dft_matrix(n1, -1)
    w2 = algo.dft_matrix(n2, -1)
    tw = algo.twiddle_factors(n1, n2, -1)
    v1 = algo.dft_matrix(n1, +1)
    v2 = algo.dft_matrix(n2, +1)
    vt = algo.twiddle_factors(n1, n2, +1)

    data = pl.BlockSpec((bm, nf), lambda i: (i, 0))
    vec = pl.BlockSpec((nf,), lambda i: (0,))
    c2 = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))

    kernel = functools.partial(_fftconv_kernel, n1=n1, n2=n2)
    return pl.pallas_call(
        kernel,
        grid=(b // bm,),
        in_specs=[data, vec, vec,
                  c2((n1, n1)), c2((n1, n1)), c2((n1, n2)), c2((n1, n2)),
                  c2((n2, n2)), c2((n2, n2)),
                  c2((n1, n1)), c2((n1, n1)), c2((n1, n2)), c2((n1, n2)),
                  c2((n2, n2)), c2((n2, n2))],
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct((b, nf), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.float32), h_spec[0], h_spec[1],
      w1[0], w1[1], tw[0], tw[1], w2[0], w2[1],
      v1[0], v1[1], vt[0], vt[1], v2[0], v2[1])


def filter_spectrum_permuted(h: jax.Array, factors: Tuple[int, int]):
    """Real filter (nf,) -> permuted-order spectrum pair, matching the
    kernel's internal FFT schedule."""
    from repro.core import algo
    hp = algo.fft((h.astype(jnp.float32), jnp.zeros_like(h, jnp.float32)),
                  factors=factors, permuted=True)
    return hp
