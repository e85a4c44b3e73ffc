"""The fused Pallas four-step kernel as a planned 1D stage.

Which plans the Planner gives the kernel (on a TPU by default, never on
the CPU unless asked, and only splits that Mosaic runs), and that its
stages match ``numpy.fft`` in interpret mode: c2c, the inverse with sign
+1, r2c and c2r around it, and a local ``rfftn`` with the kernel in it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Planner, irfftn, plan_nd, rfftn
from repro.core import plan as plan_mod
from repro.core.algo import to_pair
from repro.kernels.dft_matmul import ops as dft_ops
from repro.kernels.dft_matmul.dft_matmul import FUSED_SCOPE, kernel_factors

RNG = np.random.default_rng(14)


def _complex(rows, n):
    return (RNG.standard_normal((rows, n))
            + 1j * RNG.standard_normal((rows, n))).astype(np.complex64)


def _z(pair):
    return np.asarray(pair[0]) + 1j * np.asarray(pair[1])


def _rel(got, ref):
    return float(np.abs(got - ref).max() / np.abs(ref).max())


@pytest.fixture
def on_tpu(monkeypatch):
    """The platform a Planner resolves its default backends from, as on a
    TPU (nothing is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# -- which plans the kernel gets ----------------------------------------------


@pytest.mark.parametrize("n,kind,batch,factors", [
    (8192, "c2c", 16384, (64, 128)),
    (16384, "r2c", 16384, (64, 128)),         # the local cell's rows
    (16384, "c2c", 8200, (128, 128)),         # the local cell's columns
    (32768, "r2c", 8192, (128, 128)),         # the slab cell's rows
])
def test_default_planner_on_tpu_picks_the_kernel(on_tpu, n, kind, batch,
                                                 factors):
    p = Planner()
    assert p.backends == ("pallas", "jnp")
    pl = p.plan(n, kind, batch=batch)
    assert (pl.backend, pl.factors) == ("pallas", factors)


@pytest.mark.parametrize("n,kind", [
    (32768, "c2c"),            # no two-factor split at or under 128
    (512, "c2c"),              # (4, 128): 4 sublanes
    (1024, "c2c"),             # (8, 128): not compiled for the chip
    (4096, "c2c"),             # (32, 128): likewise
    (1000, "c2c"),             # no factor a multiple of 128
    (2187, "c2c"),             # 3^7
    (256, "r2c"),              # inner 128: one factor
])
def test_default_planner_on_tpu_keeps_jnp_without_a_kernel_split(
        on_tpu, n, kind):
    assert Planner().plan(n, kind, batch=1024).backend == "jnp"


def test_default_planner_on_cpu_is_jnp_only():
    p = Planner()
    assert jax.default_backend() == "cpu"
    assert p.backends == ("jnp",)
    assert p.plan(16384, "r2c", batch=16384).backend == "jnp"


@pytest.mark.parametrize("factors,expected", [
    ((128, 128), (128, 128)),
    ((128, 64), (64, 128)),
    ((64, 128), (64, 128)),
    ((128, 8), None),          # 8 lanes after step 1's swap
    ((32, 128), None),
    ((128, 4), None),          # 4 sublanes
    ((128, 256), None),
    ((64, 64), None),          # 64 lanes
    ((96, 96), None),
    ((32, 32, 32), None),      # three factors
    ((128,), None),
])
def test_kernel_factors(factors, expected):
    assert kernel_factors(factors) == expected


@pytest.mark.parametrize("kind", ["c2c", "r2c", "c2r"])
def test_no_pallas_plan_for_a_split_the_kernel_cannot_run(kind):
    p = Planner(backends=("pallas", "pallas_karatsuba", "jnp"))
    lengths = {m << k for k in range(1, 17) for m in (1, 3, 5, 7, 9, 15)}
    for n in sorted(x for x in lengths if x <= 1 << 16):
        for cand in p._candidates(n, kind, permuted=False):
            if cand.backend.startswith("pallas"):
                assert kernel_factors(cand.factors) == cand.factors, cand


def test_pallas_only_planner_refuses_a_length_the_kernel_cannot_run():
    with pytest.raises(ValueError, match="no plan candidates"):
        Planner(backends=("pallas",)).plan(256, "c2c")


def test_pallas_stage_is_priced_as_one_pass():
    fused = plan_mod.Plan(16384, "c2c", (128, 128), "pallas")
    chain = plan_mod.Plan(16384, "c2c", (128, 128), "jnp")
    assert fused.bytes_moved(8) == 2 * 8 * 16384 * 8
    assert chain.bytes_moved(8) == 3 * fused.bytes_moved(8)


# -- the kernel's stages against numpy (interpret mode) -----------------------


@pytest.mark.parametrize("factors,rows", [((64, 128), 3), ((128, 128), 2)])
def test_c2c_stage_matches_numpy(factors, rows):
    n = factors[0] * factors[1]
    x = _complex(rows, n)
    pl = plan_mod.Plan(n, "c2c", factors, "pallas")
    assert _rel(_z(plan_mod.execute(pl, to_pair(x))), np.fft.fft(x)) < 2e-6


@pytest.mark.parametrize("factors,rows", [((64, 128), 3), ((128, 128), 2)])
def test_inverse_sign_matches_numpy(factors, rows):
    n = factors[0] * factors[1]
    x = _complex(rows, n)
    unscaled = dft_ops.fft_four_step(to_pair(x), factors, sign=+1)
    assert _rel(_z(unscaled), n * np.fft.ifft(x)) < 2e-6
    pl = plan_mod.Plan(n, "c2c", factors, "pallas")
    assert _rel(_z(plan_mod.execute_inverse(pl, to_pair(x))),
                np.fft.ifft(x)) < 2e-6


@pytest.mark.parametrize("factors,rows", [((64, 128), 5), ((128, 128), 2)])
def test_r2c_and_c2r_stages_match_numpy(factors, rows):
    n = 2 * factors[0] * factors[1]
    x = RNG.standard_normal((rows, n)).astype(np.float32)
    spec = plan_mod.execute(plan_mod.Plan(n, "r2c", factors, "pallas"),
                            jnp.asarray(x))
    ref = np.fft.rfft(x)
    assert _rel(_z(spec), ref) < 2e-6
    back = plan_mod.execute(plan_mod.Plan(n, "c2r", factors, "pallas"),
                            to_pair(ref.astype(np.complex64)))
    assert _rel(np.asarray(back), x) < 2e-6


def test_local_rfftn_through_the_kernel_matches_numpy():
    planner = Planner(backends=("pallas", "jnp"))
    shape = (8, 16384)
    nd = plan_nd(shape, "r2c", planner=planner)
    assert planner.plan(16384, "r2c", batch=8).backend == "pallas"
    x = RNG.standard_normal(shape).astype(np.float32)
    spec = rfftn(x, plan=nd, planner=planner)
    ref = np.fft.rfftn(x)
    assert _rel(_z(spec), ref) < 2e-6
    back = irfftn(spec, plan=nd, planner=planner)
    assert _rel(np.asarray(back), x) < 2e-6


def test_kernel_carries_its_named_scope():
    pl = plan_mod.Plan(8192, "c2c", (64, 128), "pallas")
    x = to_pair(_complex(2, 8192))
    hlo = jax.jit(lambda c: plan_mod.execute(pl, c)).lower(x).as_text(
        debug_info=True)
    assert FUSED_SCOPE in hlo
