"""Four-step (Bailey) matmul FFT — the TPU-native 1D FFT substrate.

The paper's FFTW backend computes batched 1D FFTs with SIMD butterfly codelets.
On TPU the 128x128 MXU makes *dense DFT matmuls* the right primitive, so we use
the four-step factorization  N = N1*N2:

    A[n1, n2]   = x[n1*N2 + n2]                       (row-major reshape)
    B[k1, n2]   = sum_n1 A[n1, n2] * W_N1^{n1 k1}      (DFT along axis 0)
    B'[k1, n2]  = B[k1, n2] * W_N^{n2 k1}              (twiddle)
    C[k1, k2]   = sum_n2 B'[k1, n2] * W_N2^{n2 k2}     (DFT along axis 1)
    X[k2*N1+k1] = C[k1, k2]                            (digit transpose)

Sub-DFTs recurse until the factor is <= the planner's ``max_base`` and is
executed as a dense matmul.  Complex numbers are carried as (re, im) pairs of
real arrays (the MXU has no complex type); a complex contraction costs 4 real
matmuls, or 3 with the Karatsuba trick.

``permuted=True`` skips the final digit transpose (decimated frequency order).
``ifft_from_permuted`` consumes that order directly, which lets FFT
convolutions skip both transposes (FlashFFTConv-style) — pointwise products
commute with a fixed permutation.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Complex = Tuple[jax.Array, jax.Array]  # (re, im)

#: named scope of the r2c pack and unpack (the inner c2c FFT stays outside
#: it); the profiler's trace carries it in each op's ``op_name``
R2C_SCOPE = "repro_r2c"

# ---------------------------------------------------------------------------
# complex-pair helpers
# ---------------------------------------------------------------------------


def to_pair(z) -> Complex:
    """jnp/np complex array -> (re, im) pair."""
    z = jnp.asarray(z)
    return jnp.real(z), jnp.imag(z)


def to_complex(c: Complex) -> jax.Array:
    return jax.lax.complex(jnp.asarray(c[0], jnp.float32), jnp.asarray(c[1], jnp.float32))


def cmul(a: Complex, b: Complex) -> Complex:
    return a[0] * b[0] - a[1] * b[1], a[0] * b[1] + a[1] * b[0]


def cadd(a: Complex, b: Complex) -> Complex:
    return a[0] + b[0], a[1] + b[1]


def conj(a: Complex) -> Complex:
    return a[0], -a[1]


def cscale(a: Complex, s) -> Complex:
    return a[0] * s, a[1] * s


# ---------------------------------------------------------------------------
# DFT / twiddle tables (host-side numpy; closed over as constants)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _dft_matrix_np(n: int, sign: int) -> Tuple[np.ndarray, np.ndarray]:
    """W[j, k] = exp(sign * 2*pi*i * j*k / n); float64 then cast to f32."""
    jk = np.outer(np.arange(n), np.arange(n)).astype(np.float64)
    ang = sign * 2.0 * np.pi * jk / n
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _twiddle_np(n1: int, n2: int, sign: int) -> Tuple[np.ndarray, np.ndarray]:
    """T[k1, n2] = exp(sign * 2*pi*i * k1*n2 / (n1*n2))."""
    jk = np.outer(np.arange(n1), np.arange(n2)).astype(np.float64)
    ang = sign * 2.0 * np.pi * jk / (n1 * n2)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def dft_matrix(n: int, sign: int = -1) -> Complex:
    re, im = _dft_matrix_np(n, sign)
    return jnp.asarray(re), jnp.asarray(im)


def twiddle_factors(n1: int, n2: int, sign: int = -1) -> Complex:
    re, im = _twiddle_np(n1, n2, sign)
    return jnp.asarray(re), jnp.asarray(im)


# ---------------------------------------------------------------------------
# complex matmul (..., n) x (n, k) -> (..., k), 4-matmul or Karatsuba 3-matmul
# ---------------------------------------------------------------------------


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    # HIGHEST: on a TPU an f32 matmul at default precision is one bf16 pass,
    # and the transform then errs by 3e-3..4e-3 of max|X| (measured on a
    # v5e at n = 2^14) instead of 2e-7
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def complex_matmul(a: Complex, w: Complex, karatsuba: bool = False) -> Complex:
    """(ar + i*ai) @ (wr + i*wi), contracting a's last dim with w's first."""
    ar, ai = a
    wr, wi = w
    if karatsuba:
        # 3 real matmuls: p1 = ar@wr, p2 = ai@wi, p3 = (ar+ai)@(wr+wi)
        p1 = _mm(ar, wr)
        p2 = _mm(ai, wi)
        p3 = _mm(ar + ai, wr + wi)
        return p1 - p2, p3 - p1 - p2
    return _mm(ar, wr) - _mm(ai, wi), _mm(ar, wi) + _mm(ai, wr)


# ---------------------------------------------------------------------------
# factorization planning helper (the Planner in plan.py builds on this)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def default_factorization(n: int, max_base: int = 128) -> Tuple[int, ...]:
    """Split n into factors each <= max_base, minimizing (#factors, sum).

    The four-step cost is ~ N * sum(factors) MACs, so the sum is the flop
    count and fewer factors means fewer twiddle/transpose passes.  Balanced
    splits win: 256 -> (16, 16), 16384 -> (128, 128), 2**19 -> (128, 64, 64).
    """
    if n <= max_base:
        return (n,)
    best = None

    def key(fs):
        return (len(fs), sum(fs), -min(fs))

    for f in range(2, max_base + 1):
        if n % f == 0:
            try:
                rest = default_factorization(n // f, max_base)
            except ValueError:
                continue
            cand = tuple(sorted((f,) + rest, reverse=True))
            if best is None or key(cand) < key(best):
                best = cand
    if best is None:
        raise ValueError(f"cannot factor {n} with base <= {max_base}")
    return best


# ---------------------------------------------------------------------------
# core c2c FFT along the last axis
# ---------------------------------------------------------------------------


def _fft_base(x: Complex, sign: int, karatsuba: bool) -> Complex:
    """Dense DFT matmul along the last axis."""
    n = x[0].shape[-1]
    return complex_matmul(x, dft_matrix(n, sign), karatsuba)


def _fft_factors(x: Complex, factors: Sequence[int], sign: int,
                 karatsuba: bool, permuted: bool) -> Complex:
    """Four-step FFT along the last axis with the given factorization."""
    n = x[0].shape[-1]
    if len(factors) == 1:
        assert factors[0] == n, (factors, n)
        return _fft_base(x, sign, karatsuba)
    n1 = factors[0]
    n2 = n // n1
    batch = x[0].shape[:-1]
    a = (x[0].reshape(batch + (n1, n2)), x[1].reshape(batch + (n1, n2)))

    # step 1: DFT_n1 along axis -2. Contract with W1 via last-axis matmul on the
    # transposed view (..., n2, n1) — this is the "columns" FFT of the paper.
    at = (jnp.swapaxes(a[0], -1, -2), jnp.swapaxes(a[1], -1, -2))
    bt = complex_matmul(at, dft_matrix(n1, sign), karatsuba)  # (..., n2, k1)
    b = (jnp.swapaxes(bt[0], -1, -2), jnp.swapaxes(bt[1], -1, -2))  # (..., k1, n2)

    # step 2: twiddle T[k1, n2]
    tw = twiddle_factors(n1, n2, sign)
    b = cmul(b, tw)

    # step 3: DFT_n2 along the last axis (recurse on remaining factors)
    c = _fft_factors(b, tuple(factors[1:]), sign, karatsuba, permuted=False) \
        if len(factors) > 2 else _fft_base(b, sign, karatsuba)
    # note: recursing with permuted=False keeps inner ordering canonical; only
    # the *top level* may skip its digit transpose.

    if permuted:
        return c[0].reshape(batch + (n,)), c[1].reshape(batch + (n,))
    # step 4: digit transpose  X[k2*n1 + k1] = C[k1, k2]
    ct = (jnp.swapaxes(c[0], -1, -2), jnp.swapaxes(c[1], -1, -2))
    return ct[0].reshape(batch + (n,)), ct[1].reshape(batch + (n,))


def fft(x: Complex, *, sign: int = -1, factors: Sequence[int] | None = None,
        max_base: int = 128, karatsuba: bool = False,
        permuted: bool = False) -> Complex:
    """c2c FFT along the last axis of an (re, im) pair."""
    n = x[0].shape[-1]
    if factors is None:
        factors = default_factorization(n, max_base)
    return _fft_factors(x, tuple(factors), sign, karatsuba, permuted)


def ifft(x: Complex, *, factors: Sequence[int] | None = None,
         max_base: int = 128, karatsuba: bool = False) -> Complex:
    n = x[0].shape[-1]
    y = fft(x, sign=+1, factors=factors, max_base=max_base, karatsuba=karatsuba)
    return cscale(y, 1.0 / n)


def ifft_from_permuted(x: Complex, *, factors: Sequence[int] | None = None,
                       max_base: int = 128, karatsuba: bool = False) -> Complex:
    """Inverse FFT consuming the ``permuted=True`` forward output.

    Forward (permuted) stopped at C[k1, k2].  The inverse of the *ordered*
    transform composed with the missing digit-transpose cancels to: inverse
    DFT along k2, conjugate twiddle, inverse DFT along k1, flatten — no
    transposes at all.  Only valid for two-factor plans (the planner enforces
    this when it selects permuted mode).
    """
    n = x[0].shape[-1]
    if factors is None:
        factors = default_factorization(n, max_base)
    if len(factors) != 2:
        raise ValueError("permuted mode requires a two-factor plan")
    n1, n2 = factors
    batch = x[0].shape[:-1]
    c = (x[0].reshape(batch + (n1, n2)), x[1].reshape(batch + (n1, n2)))
    # inverse DFT along k2 (last axis)
    b = complex_matmul(c, dft_matrix(n2, +1), karatsuba)
    # conjugate twiddle
    b = cmul(b, twiddle_factors(n1, n2, +1))
    # inverse DFT along k1 (axis -2)
    bt = (jnp.swapaxes(b[0], -1, -2), jnp.swapaxes(b[1], -1, -2))
    at = complex_matmul(bt, dft_matrix(n1, +1), karatsuba)
    a = (jnp.swapaxes(at[0], -1, -2), jnp.swapaxes(at[1], -1, -2))
    out = (a[0].reshape(batch + (n,)), a[1].reshape(batch + (n,)))
    return cscale(out, 1.0 / n)


# ---------------------------------------------------------------------------
# real-to-complex (the paper's transform kind) via pack-as-complex
# ---------------------------------------------------------------------------


def _half_twiddle(n: int, sign: int) -> Complex:
    m = n // 2
    k = np.arange(m + 1).astype(np.float64)
    ang = sign * 2.0 * np.pi * k / n
    return jnp.asarray(np.cos(ang).astype(np.float32)), jnp.asarray(np.sin(ang).astype(np.float32))


def rfft(x: jax.Array, inner=None, **kw) -> Complex:
    """r2c FFT along the last axis. len must be even; output length n//2 + 1.

    Packs even/odd samples into a complex signal of length n/2, runs one c2c
    FFT, and unpacks with conjugate symmetry — halving MXU work exactly like
    FFTW's real codelets halve flops.  ``inner``: the c2c FFT of the packed
    pair, if not :func:`fft` with ``kw``.
    """
    n = x.shape[-1]
    assert n % 2 == 0, "rfft requires even length"
    m = n // 2
    with jax.named_scope(R2C_SCOPE):
        z = (x[..., 0::2], x[..., 1::2])
    zf = inner(z) if inner else fft(z, sign=-1, **kw)  # (..., m)
    with jax.named_scope(R2C_SCOPE):
        # Z[(-k) mod m], k = 0..m  (index m wraps to 0)
        idx = (-np.arange(m + 1)) % m
        zr = (zf[0][..., idx], zf[1][..., idx])
        zk = (jnp.concatenate([zf[0], zf[0][..., :1]], -1),
              jnp.concatenate([zf[1], zf[1][..., :1]], -1))
        xe = cscale(cadd(zk, conj(zr)), 0.5)                   # even spectrum
        xo_t = cadd(zk, cscale(conj(zr), -1.0))                # Z - conj(Zrev)
        xo = (0.5 * xo_t[1], -0.5 * xo_t[0])                   # /(2i)
        w = _half_twiddle(n, -1)
        return cadd(xe, cmul(w, xo))


def irfft(x: Complex, inner=None, **kw) -> jax.Array:
    """c2r inverse FFT; input (..., n//2+1), output real (..., n).
    ``inner``: the scaled inverse c2c FFT of the packed pair, if not
    :func:`ifft` with ``kw``."""
    m = x[0].shape[-1] - 1
    n = 2 * m
    with jax.named_scope(R2C_SCOPE):
        w = _half_twiddle(n, +1)
        xr = (x[0][..., ::-1], x[1][..., ::-1])                # X[m-k]
        xe = cscale(cadd(x, conj(xr)), 0.5)
        xo_f = cscale(cadd(x, cscale(conj(xr), -1.0)), 0.5)
        xo = cmul(w, xo_f)                              # undo half twiddle
        # Z[k] = Xe[k] + i*Xo[k], k = 0..m-1
        z = (xe[0][..., :m] - xo[1][..., :m],
             xe[1][..., :m] + xo[0][..., :m])
    zi = inner(z) if inner else ifft(z, **kw)
    with jax.named_scope(R2C_SCOPE):
        out = jnp.stack([zi[0], zi[1]], axis=-1)                # interleave
        return out.reshape(out.shape[:-2] + (n,))


# ---------------------------------------------------------------------------
# multidimensional transforms (the paper's 2D algorithm, axis-by-axis)
# ---------------------------------------------------------------------------


def fft2(x: Complex, **kw) -> Complex:
    """2D c2c FFT over the last two axes: rows then columns via transpose."""
    y = fft(x, **kw)                                            # along axis -1
    yt = (jnp.swapaxes(y[0], -1, -2), jnp.swapaxes(y[1], -1, -2))
    zt = fft(yt, **kw)                                          # along old axis -2
    return jnp.swapaxes(zt[0], -1, -2), jnp.swapaxes(zt[1], -1, -2)


def ifft2(x: Complex, **kw) -> Complex:
    y = ifft(x, **kw)
    yt = (jnp.swapaxes(y[0], -1, -2), jnp.swapaxes(y[1], -1, -2))
    zt = ifft(yt, **kw)
    return jnp.swapaxes(zt[0], -1, -2), jnp.swapaxes(zt[1], -1, -2)


def rfft2(x: jax.Array, **kw) -> Complex:
    """2D r2c: r2c along the contiguous rows, then c2c along columns."""
    y = rfft(x, **kw)                                           # (..., N, M//2+1)
    yt = (jnp.swapaxes(y[0], -1, -2), jnp.swapaxes(y[1], -1, -2))
    zt = fft(yt, **kw)
    return jnp.swapaxes(zt[0], -1, -2), jnp.swapaxes(zt[1], -1, -2)


def fftn(x: Complex, ndim: int, **kw) -> Complex:
    """n-D c2c FFT over the last ``ndim`` axes."""
    y = x
    for ax in range(ndim):
        axis = -1 - ax
        yt = (jnp.moveaxis(y[0], axis, -1), jnp.moveaxis(y[1], axis, -1))
        zt = fft(yt, **kw)
        y = (jnp.moveaxis(zt[0], -1, axis), jnp.moveaxis(zt[1], -1, axis))
    return y
