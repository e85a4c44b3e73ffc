"""Pallas TPU kernel: fused complex pointwise multiply (twiddle / spectral
filter application).

Used standalone by the FFT-convolution pipeline (y_hat = x_hat * k_hat in
frequency space) where fusing the 6-op complex product into one VMEM pass
halves HBM traffic versus two separate jnp multiplies.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..interpret import resolve_interpret


def _cmul_kernel(ar_ref, ai_ref, br_ref, bi_ref, or_ref, oi_ref):
    ar, ai = ar_ref[...], ai_ref[...]
    br, bi = br_ref[...], bi_ref[...]
    or_ref[...] = ar * br - ai * bi
    oi_ref[...] = ar * bi + ai * br


def complex_multiply_pallas(a, b, *, block: int = 1024, interpret: Optional[bool] = None):
    """Elementwise (re, im) * (re, im). b broadcasts over leading dims of a."""
    ar, ai = a
    br, bi = b
    br = jnp.broadcast_to(br, ar.shape)
    bi = jnp.broadcast_to(bi, ai.shape)
    shape = ar.shape
    flat = 1
    for s in shape:
        flat *= s
    bk = min(block, flat)
    while flat % bk:
        bk -= 1
    spec = pl.BlockSpec((bk,), lambda i: (i,))
    orr, oi = pl.pallas_call(
        _cmul_kernel,
        grid=(flat // bk,),
        in_specs=[spec] * 4,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((flat,), ar.dtype)] * 2,
        interpret=resolve_interpret(interpret),
    )(ar.reshape(flat), ai.reshape(flat), br.reshape(flat), bi.reshape(flat))
    return orr.reshape(shape), oi.reshape(shape)
