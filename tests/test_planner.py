"""Planner behaviour: estimate vs measured, wisdom, cost model."""

import os

import jax
import numpy as np
import pytest

from repro.core import algo, plan


def test_estimate_plan_valid():
    p = plan.Planner(mode="estimate", backends=("jnp",))
    pl = p.plan(4096, "c2c", batch=8)
    assert np.prod(pl.factors) == 4096
    assert all(f <= 128 for f in pl.factors)


def test_estimate_prefers_mxu_sized_factors():
    """The v5e cost model penalizes tiny factors (MXU underutilization)."""
    p = plan.Planner(hardware=plan.TPU_V5E, mode="estimate", backends=("jnp",))
    pl = p.plan(16384, "c2c", batch=64)
    assert min(pl.factors) >= 32, pl.factors


def test_measured_planning_runs_and_caches(tmp_path):
    w = str(tmp_path / "wisdom.json")
    p = plan.Planner(mode="measured", backends=("jnp", "xla_native"),
                     hardware=plan.CPU_LOCAL, wisdom_path=w)
    pl = p.plan(512, "c2c", batch=16)
    assert pl.measured_cost > 0
    t_first = p.last_plan_seconds
    assert t_first > 0
    pl2 = p.plan(512, "c2c", batch=16)       # wisdom hit
    assert p.last_plan_seconds == 0.0
    assert pl2.factors == pl.factors
    # wisdom persists across planner instances (FFTW wisdom file semantics)
    p3 = plan.Planner(mode="measured", backends=("jnp", "xla_native"),
                      hardware=plan.CPU_LOCAL, wisdom_path=w)
    p3.plan(512, "c2c", batch=16)
    assert p3.last_plan_seconds == 0.0


def test_execute_matches_backend_choices():
    # 8192 = (64, 128): a length the fused kernel's planner plans
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 8192)).astype(np.float32)
    ref = np.fft.fft(x)
    for backend in ("jnp", "jnp_karatsuba", "xla_native", "pallas"):
        p = plan.Planner(mode="estimate", backends=(backend,))
        pl = p.plan(8192, "c2c")
        out = plan.execute(pl, algo.to_pair(x.astype(np.complex64)))
        z = np.asarray(out[0]) + 1j * np.asarray(out[1])
        if pl.permuted:
            continue
        np.testing.assert_allclose(z, ref, rtol=2e-4,
                                   atol=2e-4 * np.abs(ref).max())


def test_plan_flops_karatsuba_saves_quarter():
    p4 = plan.Plan(4096, "c2c", (64, 64), "jnp")
    p3 = plan.Plan(4096, "c2c", (64, 64), "jnp_karatsuba")
    assert abs(p3.flops(1) / p4.flops(1) - 0.75) < 1e-6


def test_inverse_execute():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 256)).astype(np.float32) \
        + 1j * rng.standard_normal((2, 256)).astype(np.float32)
    for backend in ("jnp", "xla_native"):
        p = plan.Planner(mode="estimate", backends=(backend,))
        pl = p.plan(256, "c2c")
        back = plan.execute_inverse(pl, plan.execute(pl, algo.to_pair(x)))
        z = np.asarray(back[0]) + 1j * np.asarray(back[1])
        np.testing.assert_allclose(z, x, atol=1e-3)


def test_wisdom_key_includes_batch_bucket():
    """Regression: a plan measured at batch=1 must not be silently reused at
    batch=4096 — the wisdom key carries a log2 batch bucket."""
    p = plan.Planner(mode="estimate", backends=("jnp",))
    p.plan(1024, "c2c", batch=1)
    assert len(list(p.wisdom.keys("plan/"))) == 1
    p.plan(1024, "c2c", batch=4096)          # different bucket: new entry
    assert len(list(p.wisdom.keys("plan/"))) == 2
    assert p.last_plan_seconds > 0.0
    p.plan(1024, "c2c", batch=4096)          # same bucket: wisdom hit
    assert p.last_plan_seconds == 0.0
    p.plan(1024, "c2c", batch=5000)          # 4096..8191 share bucket 12
    assert p.last_plan_seconds == 0.0
    assert len(list(p.wisdom.keys("plan/"))) == 2


@pytest.mark.parametrize("content", [
    "",                                       # empty file
    "{ not json",                             # corrupt
    '["wrong", "container"]',                 # valid JSON, wrong shape
    '{"1024/c2c/estimate": {"factors": []}}',  # pre-wisdom flat schema
    '{"schema": "repro-wisdom", "version": 999, "entries": {}}',  # stale
])
def test_corrupt_wisdom_file_degrades_to_empty(tmp_path, content):
    """A broken wisdom file must warn and start empty, never crash."""
    w = tmp_path / "wisdom.json"
    w.write_text(content)
    with pytest.warns(UserWarning):
        p = plan.Planner(mode="estimate", backends=("jnp",),
                         wisdom_path=str(w))
    assert len(p.wisdom) == 0
    pl = p.plan(256, "c2c")                   # planner still fully functional
    assert np.prod(pl.factors) == 256
    # and the rewrite produced a loadable, current-schema file
    p2 = plan.Planner(mode="estimate", backends=("jnp",), wisdom_path=str(w))
    p2.plan(256, "c2c")
    assert p2.last_plan_seconds == 0.0


def test_wisdom_export_import_byte_identical(tmp_path):
    """FFTW-style wisdom string API: export -> import -> export is
    byte-identical, including measured-mode entries."""
    p = plan.Planner(mode="measured", backends=("jnp", "xla_native"),
                     hardware=plan.CPU_LOCAL)
    p.plan(128, "c2c", batch=4)
    p.plan(64, "r2c")
    text = p.export_wisdom()
    p2 = plan.Planner(mode="measured", backends=("jnp", "xla_native"),
                      hardware=plan.CPU_LOCAL)
    assert p2.import_wisdom(text) == 2
    assert p2.export_wisdom() == text
    p2.plan(128, "c2c", batch=4)              # imported wisdom serves plans
    assert p2.last_plan_seconds == 0.0
    # forget_wisdom by namespace mirrors fftw_forget_wisdom
    assert p2.forget_wisdom("plan/") == 2
    assert len(p2.wisdom) == 0
    with pytest.raises(ValueError):
        p2.import_wisdom('{"schema": "other", "version": 1, "entries": {}}')


@pytest.mark.parametrize("backend", ["jnp", "jnp_karatsuba", "xla_native",
                                     "pallas", "pallas_karatsuba"])
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_plan_roundtrip_matrix(backend, kind):
    """execute/execute_inverse (or the r2c/c2r plan pair) round-trips for
    every kind x backend a Plan can hold (the fused kernel's plans at a
    length with a split it runs: 16384 = (128, 128), r2c's inner 8192 =
    (64, 128))."""
    n = 16384 if backend.startswith("pallas") else 256
    rng = np.random.default_rng(7)
    p = plan.Planner(mode="estimate", backends=(backend,))
    if kind == "c2c":
        x = (rng.standard_normal((2, n)) +
             1j * rng.standard_normal((2, n))).astype(np.complex64)
        pl = p.plan(n, "c2c")
        back = plan.execute_inverse(pl, plan.execute(pl, algo.to_pair(x)))
        z = np.asarray(back[0]) + 1j * np.asarray(back[1])
        np.testing.assert_allclose(z, x, atol=2e-3)
    else:
        x = rng.standard_normal((2, n)).astype(np.float32)
        fwd = p.plan(n, "r2c")
        inv = p.plan(n, "c2r")
        back = plan.execute(inv, plan.execute(fwd, x))
        np.testing.assert_allclose(np.asarray(back), x, atol=2e-3)
