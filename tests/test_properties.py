"""Hypothesis property tests on the FFT system's mathematical invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import algo  # noqa: E402

SIZES = st.sampled_from([8, 16, 32, 64, 128, 256, 512])
BATCH = st.integers(min_value=1, max_value=4)


def _signal(n, b, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((b, n)).astype(np.float32)
            + 1j * rng.standard_normal((b, n)).astype(np.float32))


@settings(max_examples=25, deadline=None)
@given(n=SIZES, b=BATCH, seed=st.integers(0, 2 ** 20),
       alpha=st.floats(-3, 3), beta=st.floats(-3, 3))
def test_linearity(n, b, seed, alpha, beta):
    x = _signal(n, b, seed)
    y = _signal(n, b, seed + 1)
    lhs = algo.to_complex(algo.fft(algo.to_pair(alpha * x + beta * y)))
    rhs = (alpha * algo.to_complex(algo.fft(algo.to_pair(x)))
           + beta * algo.to_complex(algo.fft(algo.to_pair(y))))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               atol=1e-3 * n)


@settings(max_examples=25, deadline=None)
@given(n=SIZES, b=BATCH, seed=st.integers(0, 2 ** 20))
def test_parseval(n, b, seed):
    x = _signal(n, b, seed)
    fx = np.asarray(algo.to_complex(algo.fft(algo.to_pair(x))))
    np.testing.assert_allclose(np.sum(np.abs(fx) ** 2, -1),
                               n * np.sum(np.abs(x) ** 2, -1),
                               rtol=1e-4)


@settings(max_examples=25, deadline=None)
@given(n=SIZES, b=BATCH, seed=st.integers(0, 2 ** 20))
def test_inverse(n, b, seed):
    x = _signal(n, b, seed)
    back = np.asarray(algo.to_complex(algo.ifft(algo.fft(algo.to_pair(x)))))
    np.testing.assert_allclose(back, x, atol=1e-4 * n)


@settings(max_examples=20, deadline=None)
@given(n=SIZES, seed=st.integers(0, 2 ** 20), shift=st.integers(0, 63))
def test_shift_theorem(n, seed, shift):
    """FFT(roll(x, s))[k] == FFT(x)[k] * exp(-2 pi i k s / n)."""
    shift = shift % n
    x = _signal(n, 1, seed)
    fx = np.asarray(algo.to_complex(algo.fft(algo.to_pair(x))))
    fs = np.asarray(algo.to_complex(algo.fft(algo.to_pair(
        np.roll(x, shift, axis=-1)))))
    k = np.arange(n)
    phase = np.exp(-2j * np.pi * k * shift / n)
    np.testing.assert_allclose(fs, fx * phase, atol=2e-3 * n)


@settings(max_examples=20, deadline=None)
@given(n=SIZES, seed=st.integers(0, 2 ** 20))
def test_convolution_theorem(n, seed):
    """ifft(fft(x) * fft(h)) == circular_conv(x, h), incl permuted plans."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((1, n)).astype(np.float32)
    h = rng.standard_normal((1, n)).astype(np.float32)
    ref = np.real(np.fft.ifft(np.fft.fft(x) * np.fft.fft(h)))
    factors = algo.default_factorization(n)
    xp = algo.to_pair(x.astype(np.complex64))
    hp = algo.to_pair(h.astype(np.complex64))
    if len(factors) == 2:
        fx = algo.fft(xp, factors=factors, permuted=True)
        fh = algo.fft(hp, factors=factors, permuted=True)
        out = algo.ifft_from_permuted(algo.cmul(fx, fh), factors=factors)
    else:
        fx = algo.fft(xp)
        fh = algo.fft(hp)
        out = algo.ifft(algo.cmul(fx, fh))
    np.testing.assert_allclose(np.asarray(out[0]), ref, atol=2e-3 * n)


@settings(max_examples=15, deadline=None)
@given(n=st.sampled_from([16, 64, 256]), seed=st.integers(0, 2 ** 20))
def test_rfft_conjugate_symmetry_consistency(n, seed):
    """rfft equals fft of the real signal on the half spectrum."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2, n)).astype(np.float32)
    half = np.asarray(algo.to_complex(algo.rfft(x)))
    full = np.asarray(algo.to_complex(algo.fft(
        algo.to_pair(x.astype(np.complex64)))))
    np.testing.assert_allclose(half, full[..., :n // 2 + 1], atol=1e-3 * n)


# ---------------------------------------------------------------------------
# planning invariants: plans round-trip for every kind x backend, and wisdom
# survives serialization byte-identically
# ---------------------------------------------------------------------------

from repro.core import plan as plan_mod  # noqa: E402

PLAN_BACKENDS = st.sampled_from(plan_mod.BACKENDS)
PLAN_SIZES = st.sampled_from([16, 64, 256])
#: the c2c lengths the pallas backends plan in place of PLAN_SIZES: each has
#: a split the fused kernel runs ((64, 128), (128, 128)), and r2c plans take
#: twice these, whose inner c2c is the same length
PALLAS_SIZES = {16: 8192, 64: 8192, 256: 16384}


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(n=PLAN_SIZES, backend=PLAN_BACKENDS, seed=st.integers(0, 2 ** 20),
       b=st.integers(1, 3))
def test_plan_execute_roundtrip_c2c(n, backend, seed, b):
    """execute -> execute_inverse is the identity for every backend a Plan
    can hold (permuted pallas plans invert through ifft_from_permuted)."""
    if backend.startswith("pallas"):
        n = PALLAS_SIZES[n]
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((b, n))
         + 1j * rng.standard_normal((b, n))).astype(np.complex64)
    p = plan_mod.Planner(mode="estimate", backends=(backend,))
    pl = p.plan(n, "c2c", batch=b)
    back = plan_mod.execute_inverse(pl, plan_mod.execute(pl, algo.to_pair(x)))
    z = np.asarray(back[0]) + 1j * np.asarray(back[1])
    np.testing.assert_allclose(z, x, atol=2e-3 * max(np.abs(x).max(), 1))


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(n=PLAN_SIZES, backend=PLAN_BACKENDS, seed=st.integers(0, 2 ** 20))
def test_plan_execute_roundtrip_r2c_c2r(n, backend, seed):
    """The r2c/c2r plan pair round-trips real signals for every backend."""
    if backend.startswith("pallas"):
        n = 2 * PALLAS_SIZES[n]
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((2, n)).astype(np.float32)
    p = plan_mod.Planner(mode="estimate", backends=(backend,))
    back = plan_mod.execute(p.plan(n, "c2r"),
                            plan_mod.execute(p.plan(n, "r2c"), x))
    np.testing.assert_allclose(np.asarray(back), x,
                               atol=2e-3 * max(np.abs(x).max(), 1))


# ---------------------------------------------------------------------------
# the planned N-D front-end: fftn/rfftn round-trip and match numpy over
# random shapes (odd/prime axis lengths and leading batch dims included) on
# every decomposition the mesh supports.  In the main pytest process the
# meshes are 1-device (the plumbing + pad-and-crop math); the same sweep
# runs on real 4- and 8-device CPU meshes in tests/_dist_worker.py.
# ---------------------------------------------------------------------------

import jax  # noqa: E402

from repro.core import api  # noqa: E402

AXIS_SIZES = st.sampled_from([4, 6, 7, 8, 9, 12, 13, 16])
N_BATCH = st.integers(min_value=0, max_value=2)
DECOMP = st.sampled_from(["local", "slab", "pencil"])


def _fftn_meshes():
    """1-, 4- and 8-device meshes, as the running process allows (pytest's
    main process sees 1 device; tests/_dist_worker.py re-runs with 8)."""
    n = len(jax.devices())
    out = {}
    for count, shape2 in ((1, (1, 1)), (4, (2, 2)), (8, (4, 2))):
        if count <= n:
            out[count] = (jax.make_mesh((count,), ("fft",)),
                          jax.make_mesh(shape2, ("mx", "my")))
    return out


_MESHES = _fftn_meshes()
_PLANNER = plan_mod.Planner(backends=("jnp",))


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(dims=st.lists(AXIS_SIZES, min_size=2, max_size=3),
       nb=N_BATCH, decomp=DECOMP, seed=st.integers(0, 2 ** 20),
       devices=st.sampled_from([1, 4, 8]))
def test_fftn_rfftn_roundtrip_matches_numpy(dims, nb, decomp, seed, devices):
    shape = tuple(dims)
    if decomp == "pencil" and len(shape) != 3:
        decomp = "slab"
    meshes = _MESHES.get(devices) or _MESHES[1]
    mesh, axes = ((meshes[0], ("fft",)) if decomp == "slab"
                  else (meshes[1], ("mx", "my")) if decomp == "pencil"
                  else (None, None))
    rng = np.random.default_rng(seed)
    batch = tuple(rng.integers(1, 3, size=nb))
    x = rng.standard_normal(batch + shape).astype(np.float32)
    tf_axes = tuple(range(-len(shape), 0))

    nd = api.plan_nd(shape, "r2c", mesh=mesh, planner=_PLANNER,
                     decomp=decomp, axes=axes)
    re, im = api.rfftn(x, mesh=mesh, plan=nd, planner=_PLANNER,
                       ndim=len(shape))
    ref = np.fft.rfftn(x, axes=tf_axes)
    got = np.asarray(re) + 1j * np.asarray(im)
    assert got.shape == ref.shape
    scale = max(np.max(np.abs(ref)), 1.0)
    np.testing.assert_allclose(got, ref, atol=2e-4 * scale * len(shape))
    back = api.irfftn((re, im), shape=shape, mesh=mesh, plan=nd,
                      planner=_PLANNER)
    np.testing.assert_allclose(np.asarray(back), x,
                               atol=2e-4 * scale)

    ndc = api.plan_nd(shape, "c2c", mesh=mesh, planner=_PLANNER,
                      decomp=decomp, axes=axes)
    cre, cim = api.fftn(x, mesh=mesh, plan=ndc, planner=_PLANNER,
                        ndim=len(shape))
    refc = np.fft.fftn(x, axes=tf_axes)
    gotc = np.asarray(cre) + 1j * np.asarray(cim)
    np.testing.assert_allclose(gotc, refc, atol=2e-4 * scale * len(shape))


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(ns=st.lists(PLAN_SIZES, min_size=1, max_size=3, unique=True),
       kind=st.sampled_from(["c2c", "r2c"]), b=st.integers(1, 64))
def test_measured_wisdom_export_import_byte_identical(ns, kind, b):
    """Measured wisdom survives an export -> import cycle byte-identically,
    whatever mix of sizes/kinds/batch buckets was planned."""
    p = plan_mod.Planner(mode="measured", backends=("jnp", "xla_native"),
                         hardware=plan_mod.CPU_LOCAL)
    for n in ns:
        p.plan(n, kind, batch=b)
    text = p.export_wisdom()
    q = plan_mod.Planner(mode="measured", backends=("jnp", "xla_native"),
                         hardware=plan_mod.CPU_LOCAL)
    assert q.import_wisdom(text) == len(ns)
    assert q.export_wisdom() == text
    for n in ns:                      # imported wisdom fully serves plans
        q.plan(n, kind, batch=b)
        assert q.last_plan_seconds == 0.0
