"""Paper Fig. 6: distributed strong scaling — communication backends.

Compares the monolithic all_to_all ("MPI parcelport"), the chunked pipelined
exchange ("LCI parcelport" analogue), and the AGAS gather emulation, on 8
fake devices: wall time (structural on CPU) + per-device collective bytes
parsed from the compiled HLO (the roofline-relevant number: AGAS moves ~P x
the bytes; pipelined moves the same bytes as collective but in overlap-ready
chunks).

Everything goes through the planned front-end (`repro.core.api.plan_nd` +
the `fftn` family) with forced decompositions: the 1D slab layout (8-way
mesh, 2D r2c, including the planned transposed output layout that skips
the restore exchange), the 2D pencil layout (4x2 mesh, 3D c2c with
row/column communicators, mixed per-axis backend selection), the 4D k=3
pencil chain (2x2x2 mesh), and the factor-split distributed 1D transform
(three 1/P exchanges vs one full gather).

A final section reproduces the paper's plan-mode trade-off at BOTH planning
layers: the comm layer (roofline ESTIMATE choice vs on-mesh MEASURE choice
per exchange, with proof that the second measured call is a pure wisdom
hit) and the new decomposition layer (`mode="estimate"` vs
`mode="measured"` in `plan_nd`, with the one-off finalist-timing cost).

The multi-device part runs in a subprocess (device-count override is
process-local).
"""

from __future__ import annotations

import os
import subprocess
import sys


def run() -> None:
    env = dict(os.environ)
    # fake CPU devices for structure checks: the child must never take
    # the accelerator, which the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig6_distributed", "--worker"],
        env=env, cwd=root, capture_output=True, text=True, timeout=1200)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError("fig6 worker failed")


def _worker() -> None:
    import time

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import api
    from repro.core import comm as comm_mod
    from repro.core import plan
    from repro.launch.dryrun import parse_collectives

    from benchmarks.common import emit, time_fn

    mesh = jax.make_mesh((8,), ("fft",))
    planner = plan.Planner(mode="estimate", backends=("jnp",))
    rng = np.random.default_rng(0)
    for n in (256, 512):
        x = rng.standard_normal((n, n)).astype(np.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("fft", None)))
        base = None
        for comm in ("collective", "pipelined", "agas"):
            nd = api.plan_nd((n, n), "r2c", mesh=mesh, comm=comm,
                             planner=planner, decomp="slab", axes=("fft",))
            fn = jax.jit(lambda a, _p=nd: api.execute_nd(
                _p, a, mesh=mesh, planner=planner))
            t = time_fn(fn, xs)
            lowered = fn.lower(xs)
            _, counts, wire = parse_collectives(
                lowered.compile().as_text(), with_wire=True)
            wb = sum(wire.values())
            if comm == "collective":
                base = wb
            emit(f"fig6/{comm}/n{n}", t,
                 f"wire_bytes_per_dev={wb:.0f};rel_wire={wb / base:.2f};"
                 f"n_collectives={sum(counts.values())}")
        # beyond-paper: the PLANNED transposed output layout (skip exchange
        # #2) — the §Perf-A winning configuration, now an NdPlan field
        # instead of a 2D-only executor flag; wall-clock ground truth
        nd = api.plan_nd((n, n), "r2c", mesh=mesh, comm="collective",
                         planner=planner, decomp="slab", axes=("fft",),
                         output_layout="transposed")
        fn_kt = jax.jit(lambda a, _p=nd: api.execute_nd(
            _p, a, mesh=mesh, planner=planner))
        t_kt = time_fn(fn_kt, xs)
        _, counts, wire = parse_collectives(
            fn_kt.lower(xs).compile().as_text(), with_wire=True)
        wb = sum(wire.values())
        emit(f"fig6/transposed_layout/n{n}", t_kt,
             f"wire_bytes_per_dev={wb:.0f};rel_wire={wb / base:.2f};"
             f"n_collectives={sum(counts.values())}")

    # distributed 1D (factor split): the gather-local alternative moves the
    # whole array through one link; the factor split moves 3 x 1/p of it
    n1d = 1 << 20
    nd1 = api.plan_nd((n1d,), "c2c", mesh=mesh, comm="collective",
                      planner=planner, decomp="factor1d", axes=("fft",))
    pair1 = tuple(
        jax.device_put(rng.standard_normal((n1d,)).astype(np.float32),
                       NamedSharding(mesh, P("fft"))) for _ in range(2))
    fn1 = jax.jit(lambda a, b, _p=nd1: api.execute_nd(
        _p, (a, b), mesh=mesh, planner=planner))
    t1 = time_fn(fn1, *pair1)
    _, counts, wire = parse_collectives(
        fn1.lower(*pair1).compile().as_text(), with_wire=True)
    emit(f"fig6/factor1d/n{n1d}", t1,
         f"wire_bytes_per_dev={sum(wire.values()):.0f};"
         f"n_collectives={sum(counts.values())};"
         f"factors={nd1.factors[0]}x{nd1.factors[1]}")

    # pencil decomposition (P3DFFT-style) x comm backend on a 4x2 mesh:
    # same exchange layer, but collectives stay inside row/column
    # communicators, so per-exchange wire bytes scale with the communicator
    # size rather than the full device count.
    mesh2 = jax.make_mesh((4, 2), ("mx", "my"))
    nx, ny, nz = 32, 64, 64
    pair = tuple(
        jax.device_put(rng.standard_normal((nx, ny, nz)).astype(np.float32),
                       NamedSharding(mesh2, P("mx", "my", None)))
        for _ in range(2))
    base = None
    pencil_comms = [("collective",) * 2, ("pipelined",) * 2, ("agas",) * 2,
                    ("collective", "pipelined")]
    for comms in pencil_comms:
        tag = "+".join(sorted(set(comms))) if len(set(comms)) > 1 \
            else comms[0]
        ndp = api.plan_nd((nx, ny, nz), "c2c", mesh=mesh2, comm=comms,
                          planner=planner, decomp="pencil",
                          axes=("mx", "my"))
        fn = jax.jit(lambda a, b, _p=ndp: api.execute_nd(
            _p, (a, b), mesh=mesh2, planner=planner))
        t = time_fn(fn, *pair)
        _, counts, wire = parse_collectives(
            fn.lower(*pair).compile().as_text(), with_wire=True)
        wb = sum(wire.values())
        if base is None:
            base = wb
        emit(f"fig6/pencil_{tag}/x{nx}y{ny}z{nz}", t,
             f"wire_bytes_per_dev={wb:.0f};rel_wire={wb / base:.2f};"
             f"n_collectives={sum(counts.values())}")
    # 4D multi-axis pencil: the k=3 exchange chain on a 2x2x2 mesh (one
    # exchange per adjacent pair of sharded axes, each inside its own
    # plane communicator)
    mesh3 = jax.make_mesh((2, 2, 2), ("ma", "mb", "mc"))
    shape4 = (16, 16, 32, 32)
    pair4 = tuple(
        jax.device_put(rng.standard_normal(shape4).astype(np.float32),
                       NamedSharding(mesh3, P("ma", "mb", "mc", None)))
        for _ in range(2))
    nd4 = api.plan_nd(shape4, "c2c", mesh=mesh3, comm="collective",
                      planner=planner, decomp="pencil",
                      axes=("ma", "mb", "mc"))
    fn4 = jax.jit(lambda a, b, _p=nd4: api.execute_nd(
        _p, (a, b), mesh=mesh3, planner=planner))
    t4 = time_fn(fn4, *pair4)
    _, counts, wire = parse_collectives(
        fn4.lower(*pair4).compile().as_text(), with_wire=True)
    emit(f"fig6/pencil4d_k3/{'x'.join(str(s) for s in shape4)}", t4,
         f"wire_bytes_per_dev={sum(wire.values()):.0f};"
         f"n_collectives={sum(counts.values())}")

    # r2c pencil (padded half spectrum) with the planned backend choice
    xr = jax.device_put(
        rng.standard_normal((nx, ny, nz)).astype(np.float32),
        NamedSharding(mesh2, P("mx", "my", None)))
    ndr = api.plan_nd((nx, ny, nz), "r2c", mesh=mesh2, comm="auto",
                      planner=planner, decomp="pencil", axes=("mx", "my"))
    fn = jax.jit(lambda a, _p=ndr: api.execute_nd(
        _p, a, mesh=mesh2, planner=planner))
    t = time_fn(fn, xr)
    _, counts, wire = parse_collectives(
        fn.lower(xr).compile().as_text(), with_wire=True)
    wb = sum(wire.values())
    emit(f"fig6/pencil_r2c_auto/x{nx}y{ny}z{nz}", t,
         f"wire_bytes_per_dev={wb:.0f};rel_wire={wb / base:.2f};"
         f"n_collectives={sum(counts.values())}")

    # ------------------------------------------------------------------
    # estimate vs measure: the paper's plan-mode trade-off applied to the
    # parcelport choice, side by side (Figs. 3-5 logic at the comm layer)
    # ------------------------------------------------------------------
    for n in (256, 512):
        x = rng.standard_normal((n, n)).astype(np.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("fft", None)))
        est_choice = comm_mod.plan_comm(n, n, 8, hw=planner.hw)
        t0 = time.perf_counter()
        meas_choice = comm_mod.measure_comm_slab(n, n, mesh, "fft",
                                                 wisdom=planner.wisdom)
        plan_cost = time.perf_counter() - t0

        def timed_slab(choice):
            nd = api.plan_nd((n, n), "r2c", mesh=mesh, comm=choice,
                             planner=planner, decomp="slab", axes=("fft",))
            return time_fn(jax.jit(lambda a, _p=nd: api.execute_nd(
                _p, a, mesh=mesh, planner=planner)), xs)

        t_meas = timed_slab(meas_choice)
        t_est = timed_slab(est_choice)
        # second measured call: pure wisdom hit, zero timing probes
        probes = comm_mod.MEASURE_STATS["timed"]
        comm_mod.measure_comm_slab(n, n, mesh, "fft", wisdom=planner.wisdom)
        assert comm_mod.MEASURE_STATS["timed"] == probes
        emit(f"fig6/choice_slab/n{n}", t_meas,
             f"estimate={est_choice};measured={meas_choice};"
             f"t_estimate_choice={t_est * 1e3:.2f}ms;"
             f"measure_cost_s={plan_cost:.2f};rehit_probes=0")
    est0, est1 = comm_mod.plan_comm_pencil((nx, ny, nz), (4, 2),
                                           hw=planner.hw)
    t0 = time.perf_counter()
    m0, m1 = comm_mod.measure_comm_pencil((nx, ny, nz), mesh2, ("mx", "my"),
                                          wisdom=planner.wisdom)
    plan_cost = time.perf_counter() - t0
    ndm = api.plan_nd((nx, ny, nz), "c2c", mesh=mesh2, comm=(m0, m1),
                      planner=planner, decomp="pencil", axes=("mx", "my"))
    t_meas = time_fn(jax.jit(lambda a, b, _p=ndm: api.execute_nd(
        _p, (a, b), mesh=mesh2, planner=planner)), *pair)
    emit(f"fig6/choice_pencil/x{nx}y{ny}z{nz}", t_meas,
         f"estimate={est0}+{est1};measured={m0}+{m1};"
         f"measure_cost_s={plan_cost:.2f}")

    # ------------------------------------------------------------------
    # the same trade-off one layer up: decomposition choice by roofline
    # ESTIMATE vs on-mesh MEASURED finalist timing (plan_nd's two modes)
    # ------------------------------------------------------------------
    for shape, kind, m, axes in (((64, 512), "r2c", mesh, ("fft",)),
                                 ((nx, ny, nz), "c2c", mesh2, ("mx", "my"))):
        est_nd = api.plan_nd(shape, kind, mesh=m, axes=axes, planner=planner)
        t0 = time.perf_counter()
        meas_nd = api.plan_nd(shape, kind, mesh=m, axes=axes,
                              planner=planner, mode="measured")
        plan_cost = time.perf_counter() - t0
        tag = "x".join(str(s) for s in shape)
        emit(f"fig6/choice_decomp/{tag}", meas_nd.measured_cost,
             f"estimate={est_nd.decomp};measured={meas_nd.decomp};"
             f"est_cost={est_nd.est_cost:.2e};"
             f"measure_cost_s={plan_cost:.2f}")


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        run()
