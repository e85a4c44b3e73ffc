"""Unit tests for the comm-backend subsystem (single device, fast).

Exchange *numerics* across real multi-device meshes live in
tests/_dist_worker.py; here we cover spec resolution, the roofline
planners, and the degenerate p=1 exchange (which also smoke-tests
jax.shard_map inside tier-1's fast path).
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import comm


def test_get_backend_resolution():
    assert isinstance(comm.get_backend("collective"), comm.CollectiveBackend)
    assert isinstance(comm.get_backend("agas"), comm.AgasBackend)
    b = comm.get_backend("pipelined", chunks=6)
    assert isinstance(b, comm.PipelinedBackend) and b.chunks == 6
    # inline chunk override spelling
    assert comm.get_backend("pipelined:8").chunks == 8
    # idempotent on instances
    assert comm.get_backend(b) is b
    with pytest.raises(ValueError):
        comm.get_backend("parcelport")
    with pytest.raises(TypeError):
        comm.get_backend(42)


def test_resolve_axis_backends():
    axes = ("mx", "my")
    # one spec fans out to every axis
    b = comm.resolve_axis_backends("pipelined", axes)
    assert [x.name for x in b] == ["pipelined", "pipelined"]
    # per-axis sequence, ordered as axes
    b = comm.resolve_axis_backends(("collective", "agas"), axes)
    assert [x.name for x in b] == ["collective", "agas"]
    # dict keyed by mesh-axis name; missing axes default to collective
    b = comm.resolve_axis_backends({"my": "pipelined:2"}, axes)
    assert [x.name for x in b] == ["collective", "pipelined"]
    assert b[1].chunks == 2
    with pytest.raises(ValueError):
        comm.resolve_axis_backends(("collective",), axes)
    # a typo'd mesh-axis key must not silently fall back to collective
    with pytest.raises(ValueError):
        comm.resolve_axis_backends({"mz": "agas"}, axes)


def test_plan_comm_pencil_model():
    from repro.core.plan import HardwareSpec
    fast_link = HardwareSpec("x", flops=1e14, hbm_bw=1e12, link_bw=1e13,
                             matmul_dim=128, vmem_bytes=1 << 27)
    slow_link = HardwareSpec("y", flops=1e15, hbm_bw=1e12, link_bw=1e8,
                             matmul_dim=128, vmem_bytes=1 << 27)
    shape, mesh_shape = (1 << 10, 1 << 10, 1 << 10), (16, 16)
    assert comm.plan_comm_pencil(shape, mesh_shape, hw=fast_link) == \
        ("collective", "collective")
    assert comm.plan_comm_pencil(shape, mesh_shape, hw=slow_link) == \
        ("pipelined", "pipelined")
    assert comm.plan_comm_pencil(shape, mesh_shape, hw=slow_link,
                                 overlap_capable=False) == \
        ("collective", "collective")
    # a trivial communicator never pipelines
    assert comm.plan_comm_pencil(shape, (1, 16), hw=slow_link)[0] == \
        "collective"


def test_planner_comm_methods():
    from repro.core.plan import HardwareSpec, Planner
    slow_link = HardwareSpec("y", flops=1e15, hbm_bw=1e12, link_bw=1e8,
                             matmul_dim=128, vmem_bytes=1 << 27)
    pl = Planner(hardware=slow_link, backends=("jnp",))
    assert pl.plan_comm(1 << 14, 1 << 14, 256) == "pipelined"
    assert pl.plan_comm_pencil((1 << 10,) * 3, (16, 16)) == \
        ("pipelined", "pipelined")


def test_exchange_identity_on_one_device():
    """p=1: every backend's exchange must be the identity redistribution."""
    mesh = jax.make_mesh((1,), ("ax",))
    x = np.arange(24, dtype=np.float32).reshape(4, 6)
    pair = (x, -x)
    for spec in comm.COMM_BACKENDS:
        backend = comm.get_backend(spec, chunks=3)

        def local(a, b, _bk=backend):
            return _bk.exchange((a, b), "ax", split=1, concat=0, p=1)

        re, im = jax.shard_map(local, mesh=mesh,
                               in_specs=(P("ax", None), P("ax", None)),
                               out_specs=(P(None, "ax"), P(None, "ax")))(*pair)
        np.testing.assert_allclose(np.asarray(re), x)
        np.testing.assert_allclose(np.asarray(im), -x)


def test_dfft_reexports_stable():
    """plan_comm / COMM_BACKENDS keep their historical dfft home."""
    from repro.core import dfft
    assert dfft.COMM_BACKENDS == ("collective", "pipelined", "agas")
    assert dfft.plan_comm is comm.plan_comm
    assert dfft.padded_half(512, 8) % 8 == 0


# ---------------------------------------------------------------------------
# MEASURE mode (the autotuner itself runs on real meshes in
# tests/_dist_worker.py; here we pin the caching contract and edge cases)
# ---------------------------------------------------------------------------


class _FakeMesh:
    """Just enough mesh for the keyed measure_comm_* wrappers (the raw
    timer is monkeypatched out, so no devices are needed)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


@pytest.fixture
def clean_measure_state():
    comm.forget_measurements()
    before = comm.MEASURE_STATS["timed"]
    yield
    comm.forget_measurements()
    comm.MEASURE_STATS["timed"] = before


def test_get_backend_rejects_unresolved_modes():
    """"auto"/"measure" are entry-point arguments, not backends."""
    for spec in ("auto", "measure"):
        with pytest.raises(ValueError, match="entry point"):
            comm.get_backend(spec)


def test_measure_comm_trivial_communicator():
    """p=1: nothing to measure, collective wins by fiat."""
    mesh = jax.make_mesh((1,), ("ax",))
    best, timings = comm.measure_comm(mesh, "ax", (4, 8), split=1, concat=0)
    assert best == "collective" and timings == {}
    assert comm.measure_comm_slab(64, 64, mesh, "ax") == "collective"


def test_effective_chunks_matches_pipelined_backend():
    """The sweep must time the chunk counts PipelinedBackend will use."""
    assert comm._effective_chunks(4, 32) == 4
    assert comm._effective_chunks(8, 33) == 3     # falls to a divisor
    assert comm._effective_chunks(2, 33) == 1     # no even divisor
    assert comm._effective_chunks(16, 4) == 4     # clamped to width


def test_measure_memo_one_measurement_per_key(monkeypatch,
                                              clean_measure_state):
    """The acceptance contract: the sweep runs once per key — repeat calls
    (e.g. jit retraces) hit the memo, wisdom hits skip it entirely."""
    from repro.core.wisdom import WisdomStore
    calls = []

    def fake_measure(mesh, axis, local_shape, **kw):
        calls.append((axis, tuple(local_shape)))
        return "pipelined:3", {"collective": 2e-3, "pipelined:3": 1e-3,
                               "agas": float("inf")}

    monkeypatch.setattr(comm, "measure_comm", fake_measure)
    mesh = _FakeMesh(fft=8)
    w = WisdomStore()
    assert comm.measure_comm_slab(64, 512, mesh, "fft", wisdom=w) \
        == "pipelined:3"
    assert len(calls) == 1
    # same key again: memo + wisdom hit, no new sweep
    assert comm.measure_comm_slab(64, 512, mesh, "fft", wisdom=w) \
        == "pipelined:3"
    assert len(calls) == 1
    # wisdom carries the verdict to a fresh process (memo cleared)
    rec = w.get("comm/slab/64x512/p8/r2c")
    assert rec["backend"] == "pipelined:3" and rec["seconds"] == 1e-3
    assert rec["candidates"]["agas"] is None      # inf sanitized for JSON
    comm.forget_measurements()
    assert comm.measure_comm_slab(64, 512, mesh, "fft", wisdom=w) \
        == "pipelined:3"
    assert len(calls) == 1
    # no wisdom at all: the process memo still guarantees one sweep per key
    comm.forget_measurements()
    comm.measure_comm_slab(64, 512, mesh, "fft")
    comm.measure_comm_slab(64, 512, mesh, "fft")
    assert len(calls) == 2
    # a different shape is a different key
    comm.measure_comm_slab(64, 1024, mesh, "fft")
    assert len(calls) == 3


def test_measure_pencil_which_mask(monkeypatch, clean_measure_state):
    """Mixed per-axis comm: only the axes that ask get measured."""
    calls = []

    def fake_measure(mesh, axis, local_shape, **kw):
        calls.append(axis)
        return "collective", {"collective": 1e-3}

    monkeypatch.setattr(comm, "measure_comm", fake_measure)
    mesh = _FakeMesh(mx=4, my=2)
    s0, s1 = comm.measure_comm_pencil((16, 32, 64), mesh, ("mx", "my"),
                                      which=(False, True))
    assert s0 is None and s1 == "collective"
    assert calls == ["my"]


def test_measure_pencil_c2r_shares_r2c_key(monkeypatch,
                                           clean_measure_state):
    """The c2r inverse retraces r2c's exchanges with byte-identical probes,
    so it must reuse the forward's verdict instead of re-measuring."""
    calls = []

    def fake_measure(mesh, axis, local_shape, **kw):
        calls.append((axis, tuple(local_shape)))
        return "pipelined:2", {"pipelined:2": 1e-3}

    monkeypatch.setattr(comm, "measure_comm", fake_measure)
    mesh = _FakeMesh(mx=4, my=2)
    fwd = comm.measure_comm_pencil((16, 32, 64), mesh, ("mx", "my"),
                                   kind="r2c")
    assert len(calls) == 2
    inv = comm.measure_comm_pencil((16, 32, 64), mesh, ("mx", "my"),
                                   kind="c2r")
    assert inv == fwd and len(calls) == 2         # zero re-measurement
    # c2c is a genuinely different exchange size (no padded half): new keys
    comm.measure_comm_pencil((16, 32, 64), mesh, ("mx", "my"), kind="c2c")
    assert len(calls) == 4


def test_gather_backends_agree_on_one_device():
    """Chunked vs monolithic gather: identical stacked result."""
    mesh = jax.make_mesh((1,), ("ax",))
    q = np.arange(24, dtype=np.float32).reshape(6, 4)
    s = np.arange(6, dtype=np.float32).reshape(6, 1)
    outs = {}
    for spec in ("collective", "pipelined:3", "agas"):
        backend = comm.get_backend(spec)

        def local(a, b, _bk=backend):
            return _bk.gather((a, b), "ax")

        outs[spec] = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("ax", None), P("ax", None)),
            out_specs=(P(None, "ax", None), P(None, "ax", None)))(q, s)
    for spec, (qg, sg) in outs.items():
        np.testing.assert_allclose(np.asarray(qg), q[None], err_msg=spec)
        np.testing.assert_allclose(np.asarray(sg), s[None], err_msg=spec)


def test_plan_comm_conv_and_gather_models():
    from repro.core.plan import HardwareSpec
    fast_link = HardwareSpec("x", flops=1e14, hbm_bw=1e12, link_bw=1e13,
                             matmul_dim=128, vmem_bytes=1 << 27)
    slow_link = HardwareSpec("y", flops=1e15, hbm_bw=1e12, link_bw=1e8,
                             matmul_dim=128, vmem_bytes=1 << 27)
    assert comm.plan_comm_conv(8, 64, 256, 256, 8, hw=fast_link) \
        == "collective"
    assert comm.plan_comm_conv(8, 64, 256, 256, 8, hw=slow_link) \
        == "pipelined"
    assert comm.plan_comm_conv(8, 64, 256, 256, 1, hw=slow_link) \
        == "collective"
    # the gather has almost no compute to hide behind (a dequantize-sum),
    # so only an extreme link/compute ratio keeps the monolithic collective
    extreme_link = HardwareSpec("z", flops=1e9, hbm_bw=1e12, link_bw=1e13,
                                matmul_dim=128, vmem_bytes=1 << 27)
    assert comm.plan_comm_gather(1 << 20, 4, hw=extreme_link) == "collective"
    assert comm.plan_comm_gather(1 << 20, 4, hw=slow_link) == "pipelined"
    assert comm.plan_comm_gather(1 << 20, 1, hw=slow_link) == "collective"
