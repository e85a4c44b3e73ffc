"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp ref."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dft_matmul import fft_four_step, fft_four_step_ref
from repro.kernels.dft_matmul.dft_matmul import block_rows
from repro.kernels.transpose import transpose, transpose_ref
from repro.kernels.twiddle import complex_multiply, complex_multiply_ref

RNG = np.random.default_rng(2)


def _pair(shape):
    return (jnp.asarray(RNG.standard_normal(shape), jnp.float32),
            jnp.asarray(RNG.standard_normal(shape), jnp.float32))


@pytest.mark.parametrize("factors", [(8, 8), (16, 16), (16, 32), (32, 64),
                                     (128, 128), (8, 128), (128, 8)])
@pytest.mark.parametrize("batch", [1, 5, 16])
def test_dft_matmul_shapes(factors, batch):
    n = factors[0] * factors[1]
    x = _pair((batch, n))
    k = fft_four_step(x, factors)
    r = fft_four_step_ref(x, factors)
    scale = float(jnp.max(jnp.abs(r[0]))) + 1e-6
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(r[0]),
                               atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(k[1]), np.asarray(r[1]),
                               atol=1e-4 * scale)


@pytest.mark.parametrize("karatsuba", [False, True])
@pytest.mark.parametrize("permuted", [False, True])
def test_dft_matmul_modes(karatsuba, permuted):
    x = _pair((4, 1024))
    k = fft_four_step(x, (32, 32), karatsuba=karatsuba, permuted=permuted)
    r = fft_four_step_ref(x, (32, 32), karatsuba=karatsuba, permuted=permuted)
    scale = float(jnp.max(jnp.abs(r[0]))) + 1e-6
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(r[0]),
                               atol=1e-4 * scale)


@pytest.mark.parametrize("rows", [1, 3, 8, 17])
def test_dft_matmul_block_rows(rows):
    """The tile's rows come from n (8 at 16384); a batch they do not
    divide ends in a partial tile."""
    assert block_rows(128 * 128) == 8
    x = _pair((rows, 128 * 128))
    k = fft_four_step(x, (128, 128))
    r = fft_four_step_ref(x, (128, 128))
    assert k[0].shape == x[0].shape
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(r[0]), atol=1e-3)
    np.testing.assert_allclose(np.asarray(k[1]), np.asarray(r[1]), atol=1e-3)


def test_dft_matmul_batched_nd():
    x = _pair((2, 3, 256))
    k = fft_four_step(x, (16, 16))
    r = fft_four_step_ref(x, (16, 16))
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(r[0]), atol=1e-3)


@pytest.mark.parametrize("shape", [(16, 16), (64, 128), (128, 64),
                                   (3, 40, 56), (2, 2, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_transpose_sweep(shape, dtype):
    if dtype == jnp.int32:
        x = jnp.asarray(RNG.integers(0, 100, shape), dtype)
    else:
        x = jnp.asarray(RNG.standard_normal(shape), dtype)
    np.testing.assert_array_equal(np.asarray(transpose(x)),
                                  np.asarray(transpose_ref(x)))


@pytest.mark.parametrize("block", [8, 32, 128])
def test_transpose_blocks(block):
    x = jnp.asarray(RNG.standard_normal((96, 160)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(transpose(x, block=block)),
                                  np.asarray(transpose_ref(x)))


@pytest.mark.parametrize("shape", [(128,), (4, 300), (2, 3, 64)])
def test_twiddle_sweep(shape):
    a = _pair(shape)
    b = _pair(shape)
    k = complex_multiply(a, b)
    r = complex_multiply_ref(a, b)
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(r[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k[1]), np.asarray(r[1]), atol=1e-5)


def test_twiddle_broadcast():
    a = _pair((4, 300))
    b = _pair((300,))
    k = complex_multiply(a, b)
    bb = (jnp.broadcast_to(b[0], a[0].shape), jnp.broadcast_to(b[1], a[1].shape))
    r = complex_multiply_ref(a, bb)
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(r[0]), atol=1e-5)
