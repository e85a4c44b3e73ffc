"""Multi-device worker, launched by test_distributed.py in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the device-count override
is process-local — it must never leak into the main pytest process).

Each check prints "PASS <name>"; any exception fails the subprocess.
"""

import os
import sys

assert "--xla_force_host_platform_device_count=8" in os.environ.get(
    "XLA_FLAGS", ""), "launch me via test_distributed.py"

import warnings                 # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import api      # noqa: E402
from repro.core import comm as comm_mod             # noqa: E402
from repro.core import dfft, fftconv, plan          # noqa: E402
from repro.launch.mesh import make_gspmd_mesh       # noqa: E402

# the *_slab/*_pencil checks below exercise the deprecated shims on purpose
warnings.filterwarnings("ignore", category=DeprecationWarning)
from repro.models import lm                         # noqa: E402
from repro.optim import choose_psum_comm, compressed_psum   # noqa: E402
from repro.parallel import pipeline_forward         # noqa: E402

RNG = np.random.default_rng(0)
PLANNER = plan.Planner(backends=("jnp",))


def check_fft2_slab():
    mesh = jax.make_mesh((8,), ("fft",))
    n, m = 64, 512      # m chosen so the pipelined exchange REALLY chunks
    x = RNG.standard_normal((n, m)).astype(np.float32)
    ref = np.fft.rfft2(x)
    xs = jax.device_put(x, NamedSharding(mesh, P("fft", None)))
    for comm in dfft.COMM_BACKENDS:
        for chunks in (1, 3, 4):
            re, im = dfft.fft2_slab(xs, mesh, "fft", PLANNER, comm=comm,
                                    chunks=chunks)
            z = np.asarray(re)[:, :m // 2 + 1] \
                + 1j * np.asarray(im)[:, :m // 2 + 1]
            err = np.max(np.abs(z - ref)) / np.max(np.abs(ref))
            assert err < 1e-4, (comm, chunks, err)
            if comm != "pipelined":
                break
        # roundtrip per backend (ifft2_slab honors comm too)
        back = dfft.ifft2_slab(dfft.fft2_slab(xs, mesh, "fft", PLANNER,
                                              comm=comm),
                               mesh, "fft", m, PLANNER, comm=comm)
        assert np.max(np.abs(np.asarray(back) - x)) < 1e-4, comm
    # permuted-order columns (digit-transpose elision) roundtrip
    x2 = RNG.standard_normal((256, 256)).astype(np.float32)
    xs2 = jax.device_put(x2, NamedSharding(mesh, P("fft", None)))
    c2 = dfft.fft2_slab(xs2, mesh, "fft", PLANNER, permuted_cols=True)
    back2 = dfft.ifft2_slab(c2, mesh, "fft", 256, PLANNER, permuted_cols=True)
    assert np.max(np.abs(np.asarray(back2) - x2)) < 1e-4
    # transposed-spectrum path (the §Perf-A winning config)
    ct = dfft.fft2_slab(xs, mesh, "fft", PLANNER, keep_transposed=True)
    backt = dfft.ifft2_slab(ct, mesh, "fft", m, PLANNER, from_transposed=True)
    assert np.max(np.abs(np.asarray(backt) - x)) < 1e-4
    print("PASS fft2_slab")


def check_fft3_pencil():
    mesh = jax.make_mesh((4, 2), ("mx", "my"))
    x = (RNG.standard_normal((16, 32, 64)).astype(np.float32)
         + 1j * RNG.standard_normal((16, 32, 64)).astype(np.float32))
    pair = (jax.device_put(np.real(x).astype(np.float32),
                           NamedSharding(mesh, P("mx", "my", None))),
            jax.device_put(np.imag(x).astype(np.float32),
                           NamedSharding(mesh, P("mx", "my", None))))
    ref = np.fft.fftn(x)
    refmax = np.max(np.abs(ref))
    # every comm backend: forward == numpy oracle AND full inverse roundtrip
    for comm in dfft.COMM_BACKENDS:
        rr, ri = dfft.fft3_pencil(pair, mesh, ("mx", "my"), PLANNER,
                                  comm=comm)
        err = np.max(np.abs((np.asarray(rr) + 1j * np.asarray(ri)) - ref)) \
            / refmax
        assert err < 1e-4, (comm, err)
        br, bi = dfft.ifft3_pencil((rr, ri), mesh, ("mx", "my"), PLANNER,
                                   comm=comm)
        back = np.asarray(br) + 1j * np.asarray(bi)
        assert np.max(np.abs(back - x)) < 1e-4, comm
    # per-axis backend selection: row/column communicators differ (incl.
    # measured/planned entries mixed with explicit specs)
    for comm in (("pipelined", "collective"), {"my": "agas"}, "auto",
                 "measure", ("measure", "collective"), {"mx": "measure"}):
        rr, ri = dfft.fft3_pencil(pair, mesh, ("mx", "my"), PLANNER,
                                  comm=comm)
        err = np.max(np.abs((np.asarray(rr) + 1j * np.asarray(ri)) - ref)) \
            / refmax
        assert err < 1e-4, (comm, err)
    print("PASS fft3_pencil")


def check_rfft3_pencil():
    mesh = jax.make_mesh((4, 2), ("mx", "my"))
    nx, ny, nz = 16, 32, 64
    x = RNG.standard_normal((nx, ny, nz)).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("mx", "my", None)))
    ref = np.fft.rfftn(x)
    refmax = np.max(np.abs(ref))
    for comm in dfft.COMM_BACKENDS:
        re, im = dfft.rfft3_pencil(xs, mesh, ("mx", "my"), PLANNER,
                                   comm=comm)
        z = (np.asarray(re)[..., :nz // 2 + 1]
             + 1j * np.asarray(im)[..., :nz // 2 + 1])
        err = np.max(np.abs(z - ref)) / refmax
        assert err < 1e-4, (comm, err)
        # c2r roundtrip through the padded half spectrum
        back = dfft.irfft3_pencil((re, im), mesh, ("mx", "my"), nz, PLANNER,
                                  comm=comm)
        assert np.max(np.abs(np.asarray(back) - x)) < 1e-4, comm
    print("PASS rfft3_pencil")


def check_fftconv_seq_sharded():
    mesh = jax.make_mesh((8,), ("sp",))
    b, l, d = 2, 512, 4
    u = RNG.standard_normal((b, l, d)).astype(np.float32)
    k = (RNG.standard_normal((d, l))
         * np.exp(-np.arange(l) / 32)[None]).astype(np.float32)
    nf = 2 * l
    ref = np.fft.irfft(
        np.fft.rfft(np.pad(u, ((0, 0), (0, nf - l), (0, 0))), axis=1)
        * np.fft.rfft(np.pad(k.T[None], ((0, 0), (0, nf - l), (0, 0))), axis=1),
        axis=1, n=nf)[:, :l, :]
    us = jax.device_put(u, NamedSharding(mesh, P(None, "sp", None)))
    for comm in dfft.COMM_BACKENDS + ("auto", "measure"):
        y = fftconv.fft_conv_seq_sharded(us, jnp.asarray(k), mesh, "sp",
                                         PLANNER, comm=comm)
        err = np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref))
        assert err < 1e-4, (comm, err)
    print("PASS fftconv_seq_sharded")


def check_compressed_psum():
    mesh = jax.make_mesh((8,), ("pod",))
    xs = RNG.standard_normal((8, 1000)).astype(np.float32)
    ref = xs.sum(axis=0)

    # every gather backend, plus the measured choice resolved outside
    # shard_map via choose_psum_comm (wisdom-cached like the FFT paths)
    measured = choose_psum_comm(mesh, "pod", (1000,), mode="measure",
                                wisdom=PLANNER.wisdom)
    assert PLANNER.wisdom.get("comm/gather/1000/b256/p8") is not None
    for comm in ("collective", "pipelined:2", "agas", measured,
                 choose_psum_comm(mesh, "pod", (1000,), mode="auto")):

        def body(x, _c=comm):
            out, err = compressed_psum(x[0], "pod", comm=_c)
            return out[None], err[None]

        out, err = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("pod", None),
            out_specs=(P("pod", None), P("pod", None))))(xs)
        got = np.asarray(out)[0]
        rel = np.abs(got - ref) / (np.abs(ref) + 1e-3)
        assert np.median(rel) < 0.02, (comm, np.median(rel))
        # error feedback residual is bounded by the quantization step
        assert np.max(np.abs(np.asarray(err))) < 0.05, comm
    print("PASS compressed_psum")


def check_measure_comm():
    """The comm="measure" acceptance contract on a REAL 8-device mesh:
    on-mesh timing picks a backend, the verdict lands in the unified
    wisdom store, and repeat calls perform ZERO measurements — including
    across planner instances through a wisdom file."""
    import tempfile

    mesh = jax.make_mesh((8,), ("fft",))
    n, m = 64, 512
    x = RNG.standard_normal((n, m)).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("fft", None)))
    ref = np.fft.rfft2(x)

    wpath = tempfile.mktemp(suffix=".json")
    planner = plan.Planner(backends=("jnp",), wisdom_path=wpath)
    before = comm_mod.MEASURE_STATS["timed"]
    re, im = dfft.fft2_slab(xs, mesh, "fft", planner, comm="measure")
    timed = comm_mod.MEASURE_STATS["timed"] - before
    assert timed >= 3, timed          # collective + agas + >=1 chunk count
    z = np.asarray(re)[:, :m // 2 + 1] + 1j * np.asarray(im)[:, :m // 2 + 1]
    assert np.max(np.abs(z - ref)) / np.max(np.abs(ref)) < 1e-4

    # the verdict is a concrete, resolvable backend in comm/* wisdom
    rec = planner.wisdom.get(f"comm/slab/{n}x{m}/p8/r2c")
    assert rec is not None and rec["backend"] is not None
    comm_mod.get_backend(rec["backend"])
    assert rec["candidates"]["collective"] is not None

    # second call + inverse: zero new measurements (memo + wisdom hits)
    snap = comm_mod.MEASURE_STATS["timed"]
    back = dfft.ifft2_slab(
        dfft.fft2_slab(xs, mesh, "fft", planner, comm="measure"),
        mesh, "fft", m, planner, comm="measure")
    assert np.max(np.abs(np.asarray(back) - x)) < 1e-4
    assert comm_mod.MEASURE_STATS["timed"] == snap

    # a fresh planner reading the wisdom file needs no measurements either,
    # even after the in-process memo is dropped (FFTW wisdom semantics)
    comm_mod.forget_measurements()
    planner2 = plan.Planner(backends=("jnp",), wisdom_path=wpath)
    re2, im2 = dfft.fft2_slab(xs, mesh, "fft", planner2, comm="measure")
    assert comm_mod.MEASURE_STATS["timed"] == snap
    z2 = np.asarray(re2)[:, :m // 2 + 1] + 1j * np.asarray(im2)[:, :m // 2 + 1]
    assert np.max(np.abs(z2 - ref)) / np.max(np.abs(ref)) < 1e-4

    # pencil: per-communicator measurement, then a zero-measurement retrace
    mesh2 = jax.make_mesh((4, 2), ("mx", "my"))
    xc = RNG.standard_normal((16, 32, 64)).astype(np.float32)
    pair = (jax.device_put(xc, NamedSharding(mesh2, P("mx", "my", None))),
            jax.device_put(np.zeros_like(xc),
                           NamedSharding(mesh2, P("mx", "my", None))))
    rr, ri = dfft.fft3_pencil(pair, mesh2, ("mx", "my"), planner2,
                              comm="measure")
    for ax in ("ax0", "ax1"):
        assert planner2.wisdom.get(
            f"comm/pencil/16x32x64/mesh4x2/c2c/{ax}") is not None
    snap2 = comm_mod.MEASURE_STATS["timed"]
    br, bi = dfft.ifft3_pencil((rr, ri), mesh2, ("mx", "my"), planner2,
                               comm="measure")
    assert comm_mod.MEASURE_STATS["timed"] == snap2
    back3 = np.asarray(br) + 1j * np.asarray(bi)
    assert np.max(np.abs(back3 - xc)) < 1e-4

    # r2c/c2r pencil: the inverse shares the forward's verdict (byte-
    # identical exchanges), so the roundtrip measures only on the forward
    re3, im3 = dfft.rfft3_pencil(pair[0], mesh2, ("mx", "my"), planner2,
                                 comm="measure")
    snap3 = comm_mod.MEASURE_STATS["timed"]
    back_r = dfft.irfft3_pencil((re3, im3), mesh2, ("mx", "my"), 64,
                                planner2, comm="measure")
    assert comm_mod.MEASURE_STATS["timed"] == snap3
    assert np.max(np.abs(np.asarray(back_r) - xc)) < 1e-4

    # wisdom export -> import round-trips the comm verdicts byte-identically
    text = planner2.export_wisdom()
    p3 = plan.Planner(backends=("jnp",))
    p3.import_wisdom(text)
    assert p3.export_wisdom() == text
    os.unlink(wpath)
    print("PASS measure_comm")


def check_plan_nd():
    """The plan_nd acceptance contract on a REAL 8-device mesh: the
    roofline picks local for small shapes and slab/pencil for large ones,
    dfft/* verdicts persist to the unified wisdom file, mode="measured"
    times the finalists exactly once, and fftn/rfftn match numpy on
    non-divisible shapes and batched pencil inputs."""
    import tempfile

    mesh = jax.make_mesh((8,), ("fft",))
    mesh2 = jax.make_mesh((4, 2), ("mx", "my"))
    wpath = tempfile.mktemp(suffix=".json")
    planner = plan.Planner(backends=("jnp",), wisdom_path=wpath)

    # roofline decomposition choice (ESTIMATE mode)
    assert api.plan_nd((64, 64), "r2c", mesh=mesh,
                       planner=planner).decomp == "local"
    large = api.plan_nd((1024, 1024), "r2c", mesh=mesh, planner=planner)
    assert large.decomp == "slab", large
    big3 = api.plan_nd((128, 128, 128), "c2c", mesh=mesh2, planner=planner)
    assert big3.decomp == "pencil", big3
    assert set(big3.mesh_axes) == {"mx", "my"}

    # verdicts persisted under dfft/* in the unified wisdom file; a fresh
    # planner reading the file reconstructs identical plans
    keys = list(planner.wisdom.keys("dfft/"))
    assert len(keys) == 3, keys
    planner2 = plan.Planner(backends=("jnp",), wisdom_path=wpath)
    assert api.plan_nd((1024, 1024), "r2c", mesh=mesh,
                       planner=planner2) == large

    # measured mode: every finalist timed once (with its exchanges resolved
    # through measure_comm_*), wisdom hit re-times nothing.  The shape is
    # deliberately NOT one check_measure_comm later measures fresh — the
    # comm verdict memo is process-global.
    probes = api.PLAN_ND_STATS["timed"]
    ndm = api.plan_nd((64, 320), "r2c", mesh=mesh, planner=planner,
                      mode="measured")
    timed = api.PLAN_ND_STATS["timed"] - probes
    assert timed >= 2, timed            # local + slab at least
    assert ndm.measured_cost > 0
    assert planner.wisdom.get("comm/slab/64x320/p8/r2c") is not None
    snap = api.PLAN_ND_STATS["timed"]
    ndm2 = api.plan_nd((64, 320), "r2c", mesh=mesh, planner=planner,
                       mode="measured")
    assert api.PLAN_ND_STATS["timed"] == snap and ndm2 == ndm

    # regression (non-divisible Mh on a 3-device mesh): m=12 -> mh=7 which
    # does not divide p=3, and n=10 does not either; collect() crops via
    # the NdPlan instead of assuming the padded column count
    mesh3 = jax.make_mesh((3,), ("s",))
    x = RNG.standard_normal((10, 12)).astype(np.float32)
    nd3 = api.plan_nd((10, 12), "r2c", mesh=mesh3, planner=planner,
                      decomp="slab", axes=("s",))
    assert nd3.padded_spectrum_shape == (12, 9)
    padded = api.execute_nd(nd3, x, mesh=mesh3, planner=planner)
    re, im = dfft.collect(padded, nd3)
    ref = np.fft.rfftn(x)
    assert re.shape == ref.shape == (10, 7)
    assert np.max(np.abs((re + 1j * im) - ref)) / np.max(np.abs(ref)) < 1e-4
    back = api.irfftn(api.plan_nd((10, 12), "r2c", mesh=mesh3,
                                  planner=planner, decomp="slab",
                                  axes=("s",)).crop_pair(padded),
                      shape=(10, 12), mesh=mesh3, plan=nd3, planner=planner)
    assert np.max(np.abs(np.asarray(back) - x)) < 1e-4

    # fftn/rfftn vs numpy across decompositions and device counts,
    # including odd/prime axes and leading batch dims (the multi-device
    # complement of the hypothesis property in tests/test_properties.py)
    mesh4 = jax.make_mesh((4,), ("fft4",))
    mesh22 = jax.make_mesh((2, 2), ("qx", "qy"))
    cases = [
        ((16, 24), (), "slab", mesh, ("fft",)),
        ((10, 7), (2,), "slab", mesh4, ("fft4",)),          # odd/prime
        ((8, 12, 16), (), "pencil", mesh2, ("mx", "my")),
        ((6, 10, 9), (2,), "pencil", mesh2, ("mx", "my")),  # batched+mixed
        ((7, 6, 13), (3,), "pencil", mesh22, ("qx", "qy")),
        ((12, 8, 16), (2,), "slab", mesh, ("fft",)),        # batched 3D slab
    ]
    for shape, batch, decomp, m, axes in cases:
        xr = RNG.standard_normal(batch + shape).astype(np.float32)
        tf_axes = tuple(range(-len(shape), 0))
        ndr = api.plan_nd(shape, "r2c", mesh=m, planner=planner,
                          decomp=decomp, axes=axes)
        rr, ri = api.rfftn(xr, mesh=m, plan=ndr, planner=planner,
                           ndim=len(shape))
        refr = np.fft.rfftn(xr, axes=tf_axes)
        got = np.asarray(rr) + 1j * np.asarray(ri)
        assert got.shape == refr.shape, (shape, batch, decomp)
        err = np.max(np.abs(got - refr)) / np.max(np.abs(refr))
        assert err < 1e-4, (shape, batch, decomp, err)
        backr = api.irfftn((rr, ri), shape=shape, mesh=m, plan=ndr,
                           planner=planner)
        assert np.max(np.abs(np.asarray(backr) - xr)) < 1e-3, (shape, decomp)

        ndc = api.plan_nd(shape, "c2c", mesh=m, planner=planner,
                          decomp=decomp, axes=axes)
        cr, ci = api.fftn(xr, mesh=m, plan=ndc, planner=planner,
                          ndim=len(shape))
        refc = np.fft.fftn(xr, axes=tf_axes)
        gotc = np.asarray(cr) + 1j * np.asarray(ci)
        errc = np.max(np.abs(gotc - refc)) / np.max(np.abs(refc))
        assert errc < 1e-4, (shape, batch, decomp, errc)

    os.unlink(wpath)
    print("PASS plan_nd")


def check_plan_nd_generalized():
    """PR-4 acceptance on REAL 8-device meshes: multi-axis pencil beyond
    3D (k=2 on a 4-D shape over a 2-axis mesh, k=3 over a 3-axis mesh,
    mixed radix and batched), the factor-split distributed-1D candidate
    selected and executed numpy-exactly, and the planned transposed layout
    saving one exchange each way."""
    mesh42 = jax.make_mesh((4, 2), ("mx", "my"))
    mesh222 = jax.make_mesh((2, 2, 2), ("ma", "mb", "mc"))
    mesh8 = jax.make_mesh((8,), ("fft",))
    planner = plan.Planner(backends=("jnp",))

    # a 4-D c2c shape over a 2-axis mesh enumerates multi-axis pencil
    # candidates (and over a 3-axis mesh, the full k=3 chain)
    cands = api._candidates((8, 6, 5, 8), "c2c", {"mx": 4, "my": 2})
    assert ("pencil", ("mx", "my")) in cands, cands
    cands3 = api._candidates((8, 6, 5, 8), "c2c",
                             {"ma": 2, "mb": 2, "mc": 2})
    assert ("pencil", ("ma", "mb", "mc")) in cands3, cands3

    # k=2 and k=3 pencil chains execute numpy-exactly: mixed radix
    # (nothing divides every communicator) AND a leading batch dim
    shape = (8, 6, 5, 8)
    x = (RNG.standard_normal((2,) + shape)
         + 1j * RNG.standard_normal((2,) + shape)).astype(np.complex64)
    ref = np.fft.fftn(x, axes=(-4, -3, -2, -1))
    refmax = np.max(np.abs(ref))
    for mesh, axes in ((mesh42, ("mx", "my")),
                       (mesh222, ("ma", "mb", "mc"))):
        nd = api.plan_nd(shape, "c2c", mesh=mesh, planner=planner,
                         decomp="pencil", axes=axes)
        re, im = api.fftn(x, mesh=mesh, plan=nd, planner=planner, ndim=4)
        got = np.asarray(re) + 1j * np.asarray(im)
        assert got.shape == ref.shape, axes
        assert np.max(np.abs(got - ref)) / refmax < 1e-4, axes
        br, bi = api.ifftn((re, im), mesh=mesh, plan=nd, planner=planner,
                           ndim=4)
        back = np.asarray(br) + 1j * np.asarray(bi)
        assert np.max(np.abs(back - x)) < 1e-3, axes
    # r2c through the k=3 chain (padded half spectrum, odd middle axes)
    xr = RNG.standard_normal((6, 10, 5, 12)).astype(np.float32)
    ndr = api.plan_nd((6, 10, 5, 12), "r2c", mesh=mesh222, planner=planner,
                      decomp="pencil", axes=("ma", "mb", "mc"))
    rr, ri = api.rfftn(xr, mesh=mesh222, plan=ndr, planner=planner)
    refr = np.fft.rfftn(xr)
    gotr = np.asarray(rr) + 1j * np.asarray(ri)
    assert gotr.shape == refr.shape
    assert np.max(np.abs(gotr - refr)) / np.max(np.abs(refr)) < 1e-4
    backr = api.irfftn((rr, ri), shape=(6, 10, 5, 12), mesh=mesh222,
                       plan=ndr, planner=planner)
    assert np.max(np.abs(np.asarray(backr) - xr)) < 1e-3

    # distributed 1D: the roofline picks the factor split over gather-local
    # for a large transform, and the executor matches numpy.fft.fft
    n = 1 << 20
    nd1 = api.plan_nd((n,), "c2c", mesh=mesh8, planner=planner)
    assert nd1.decomp == "factor1d", nd1
    assert nd1.factors[0] * nd1.factors[1] == n
    assert nd1.factors[0] % 8 == 0 and nd1.factors[1] % 8 == 0
    xc = (RNG.standard_normal((n,))
          + 1j * RNG.standard_normal((n,))).astype(np.complex64)
    xs = (jax.device_put(np.real(xc), NamedSharding(mesh8, P("fft"))),
          jax.device_put(np.imag(xc), NamedSharding(mesh8, P("fft"))))
    re1, im1 = api.fftn(xs, mesh=mesh8, plan=nd1, planner=planner)
    ref1 = np.fft.fft(xc)
    got1 = np.asarray(re1) + 1j * np.asarray(im1)
    err1 = np.max(np.abs(got1 - ref1)) / np.max(np.abs(ref1))
    assert err1 < 1e-3, err1            # 1M-point f32 accumulations
    b1r, b1i = api.ifftn((re1, im1), mesh=mesh8, plan=nd1, planner=planner)
    back1 = np.asarray(b1r) + 1j * np.asarray(b1i)
    assert np.max(np.abs(back1 - xc)) < 1e-3
    # small 1D still stays local (three latencies beat one gather)
    assert api.plan_nd((4096,), "c2c", mesh=mesh8,
                       planner=planner).decomp == "local"

    # comm="measure" through the NEW paths: the k=3 pencil chain (one
    # on-mesh-timed verdict per plane communicator, probe shapes from the
    # executor's own padded chain) and the factor1d stage-A exchange
    ndm = api.plan_nd(shape, "c2c", mesh=mesh222, planner=planner,
                      decomp="pencil", axes=("ma", "mb", "mc"),
                      comm="measure")
    assert len(ndm.comm) == 3
    assert all(s not in ("auto", "measure") for s in ndm.comm), ndm.comm
    shape_tag = "x".join(str(s) for s in shape)
    for j in range(3):
        assert planner.wisdom.get(
            f"comm/pencil/{shape_tag}/mesh2x2x2/c2c/ax{j}") is not None
    rem, imm = api.fftn(x, mesh=mesh222, plan=ndm, planner=planner, ndim=4)
    gotm = np.asarray(rem) + 1j * np.asarray(imm)
    assert np.max(np.abs(gotm - ref)) / refmax < 1e-4
    nm = 1 << 16
    nd1m = api.plan_nd((nm,), "c2c", mesh=mesh8, planner=planner,
                       decomp="factor1d", axes=("fft",), comm="measure")
    (spec1m,) = nd1m.comm
    assert spec1m not in ("auto", "measure"), spec1m
    f1, f2 = nd1m.factors
    assert planner.wisdom.get(
        f"comm/factor1d/{nm}/{f1}x{f2}/p8") is not None
    xm = (RNG.standard_normal((nm,))
          + 1j * RNG.standard_normal((nm,))).astype(np.complex64)
    rem1, imm1 = api.fftn(xm, mesh=mesh8, plan=nd1m, planner=planner)
    refm1 = np.fft.fft(xm)
    errm1 = np.max(np.abs((np.asarray(rem1) + 1j * np.asarray(imm1))
                          - refm1)) / np.max(np.abs(refm1))
    assert errm1 < 1e-3, errm1

    # planned transposed layout: one exchange forward, one backward
    # (counted through a spy backend), numpy-exact values either way
    class Spy(comm_mod.CollectiveBackend):
        count = 0

        def exchange(self, c, axis_name, **kw):
            Spy.count += 1
            return super().exchange(c, axis_name, **kw)

    xt = RNG.standard_normal((64, 512)).astype(np.float32)
    xts = jax.device_put(xt, NamedSharding(mesh8, P("fft", None)))
    for layout, n_fwd in (("natural", 2), ("transposed", 1)):
        ndt = api.plan_nd((64, 512), "r2c", mesh=mesh8, planner=planner,
                          decomp="slab", axes=("fft",), comm=Spy(),
                          output_layout=layout)
        Spy.count = 0
        ct = api.execute_nd(ndt, xts, mesh=mesh8, planner=planner)
        assert Spy.count == n_fwd, (layout, Spy.count)
        z = (np.asarray(ct[0]) + 1j * np.asarray(ct[1]))[:, :512 // 2 + 1]
        reft = np.fft.rfft2(xt)
        assert np.max(np.abs(z - reft)) / np.max(np.abs(reft)) < 1e-4
        Spy.count = 0
        backt = api.execute_nd_inverse(ndt, ct, mesh=mesh8, planner=planner)
        assert Spy.count == n_fwd, (layout, Spy.count)
        assert np.max(np.abs(np.asarray(backt)[:64] - xt)) < 1e-4
    print("PASS plan_nd_generalized")


def check_pipeline_forward():
    mesh = jax.make_mesh((4,), ("pod",))
    m_mb, mb, d = 8, 4, 16
    x = RNG.standard_normal((m_mb, mb, d)).astype(np.float32)
    w = RNG.standard_normal((4, d, d)).astype(np.float32) * 0.3

    def stage(wl, xin):                    # each stage: x @ w_stage
        return jnp.tanh(xin @ wl[0])

    def run(w_all, xin):
        return pipeline_forward(stage, w_all, xin, "pod")

    y = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P("pod", None, None), P(None, None, None)),
        out_specs=P(None, None, None), check_vma=False))(w, x)
    # reference: sequential stages
    ref = x
    for s in range(4):
        ref = np.tanh(ref @ w[s])
    err = np.max(np.abs(np.asarray(y) - ref))
    assert err < 1e-5, err

    # differentiability (GPipe backward through ppermute)
    def loss(w_all):
        return jnp.sum(jax.shard_map(
            run, mesh=mesh, in_specs=(P("pod", None, None),
                                      P(None, None, None)),
            out_specs=P(None, None, None), check_vma=False)(w_all, x) ** 2)

    g = jax.jit(jax.grad(loss))(w)
    assert np.all(np.isfinite(np.asarray(g)))
    assert np.abs(np.asarray(g)).sum() > 0
    print("PASS pipeline_forward")


def check_sharded_train_equivalence():
    """4-device FSDP+TP train step == single-device step (GSPMD correctness)."""
    from repro.configs import get_smoke_config
    from repro.models.params import sharding_rules
    from repro.parallel import make_rules, logical_shardings

    cfg = get_smoke_config("granite_8b")
    params = lm.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    loss1 = float(lm.loss_fn(params, cfg, batch)[0])

    mesh = make_gspmd_mesh((2, 2), ("data", "model"))
    rules = make_rules(mesh)
    pspecs = logical_shardings(mesh, lm.model_meta(cfg), rules)
    params_sh = jax.tree_util.tree_map(jax.device_put, params, pspecs)

    def sharded_loss(p, b):
        with sharding_rules(mesh, rules):
            return lm.loss_fn(p, cfg, b, num_groups=2)[0]

    loss2 = float(jax.jit(sharded_loss)(params_sh, batch))
    assert abs(loss1 - loss2) < 5e-3, (loss1, loss2)
    print("PASS sharded_train_equivalence")


def check_dryrun_cell_tiny():
    """build_cell compiles on a small mesh (structure check for specs.py)."""
    from repro.launch.specs import cache_pspecs
    from repro.parallel import make_rules, sanitized_shardings
    from repro.configs import get_smoke_config

    mesh = make_gspmd_mesh((4, 2), ("data", "model"))
    rules = make_rules(mesh)
    for arch in ("granite_8b", "zamba2_7b", "xlstm_1_3b", "phi35_moe_42b"):
        cfg = get_smoke_config(arch)
        cache_abs = jax.eval_shape(lambda c=cfg: lm.init_cache(c, 8, 64))
        specs = cache_pspecs(cfg, 8, mesh, rules)
        sh = sanitized_shardings(mesh, cache_abs, specs)   # structure match
        assert jax.tree_util.tree_structure(sh) == \
            jax.tree_util.tree_structure(cache_abs)
    print("PASS dryrun_cell_tiny")


def check_pipelined_lm_equivalence():
    """Pod-axis GPipe loss == plain loss (same params, same batch)."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.parallel import make_rules
    from repro.parallel.pipelined_lm import (pipelined_loss_fn,
                                             pipeline_param_shardings)

    cfg = dataclasses.replace(get_smoke_config("granite_8b"), num_layers=4)
    params = lm.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    ref = float(lm.loss_fn(params, cfg, batch)[0])

    mesh = make_gspmd_mesh((2, 2, 2), ("pod", "data", "model"))
    rules = make_rules(mesh, pipeline_pods=True)
    pspecs = pipeline_param_shardings(mesh, lm.model_meta(cfg), rules)
    params_sh = jax.tree_util.tree_map(jax.device_put, params, pspecs)

    loss = float(jax.jit(
        lambda p, b: pipelined_loss_fn(p, cfg, b, mesh, rules,
                                       num_microbatches=4)[0]
    )(params_sh, batch))
    assert abs(loss - ref) < 5e-3, (loss, ref)

    # gradients flow through the pipeline (ppermute transpose)
    g = jax.jit(jax.grad(
        lambda p: pipelined_loss_fn(p, cfg, batch, mesh, rules,
                                    num_microbatches=4)[0]))(params_sh)
    gn = sum(float(jnp.sum(jnp.abs(l.astype(jnp.float32))))
             for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
    print("PASS pipelined_lm_equivalence")


def check_serve_profile_equivalence():
    """Weight-stationary serve layout (bf16 reduce, expert-resident weights)
    computes the same loss as the training layout."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models.params import sharding_rules
    from repro.parallel import make_rules, logical_shardings

    cfg = get_smoke_config("phi35_moe_42b")
    params = lm.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    ref = float(lm.loss_fn(params, cfg, batch)[0])

    cfg_s = dataclasses.replace(cfg, reduce_dtype="bfloat16")
    mesh = make_gspmd_mesh((4, 2), ("data", "model"))
    rules = make_rules(mesh, profile="serve")
    pspecs = logical_shardings(mesh, lm.model_meta(cfg_s), rules)
    params_sh = jax.tree_util.tree_map(jax.device_put, params, pspecs)

    def f(p, b):
        with sharding_rules(mesh, rules):
            return lm.loss_fn(p, cfg_s, b, num_groups=4)[0]

    got = float(jax.jit(f)(params_sh, batch))
    assert abs(got - ref) < 2e-2, (got, ref)   # bf16 reductions: loose tol
    print("PASS serve_profile_equivalence")


def check_chip_smoke_four_chip_phases():
    """chip_smoke.py's four-chip phases (slab, 2x2 pencil, factor1d; each
    checked against numpy and for a spectrum sharded over every device) at
    a tiny size on four of the fake devices, through eager front-end
    calls on jax.make_mesh meshes."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke
    devs = jax.devices()[:4]
    slab = jax.make_mesh((4,), ("fft",), devices=devs)
    pencil = jax.make_mesh((2, 2), ("px", "py"), devices=devs)
    chip_smoke.four_chip_phases(slab, pencil, plan.Planner(), n2d=64,
                                n3d=16, n1d=1 << 12)
    print("PASS chip_smoke_four_chip_phases")


if __name__ == "__main__":
    check_chip_smoke_four_chip_phases()
    check_fft2_slab()
    check_fft3_pencil()
    check_rfft3_pencil()
    check_fftconv_seq_sharded()
    check_plan_nd()
    check_plan_nd_generalized()
    check_measure_comm()
    check_compressed_psum()
    check_pipeline_forward()
    check_sharded_train_equivalence()
    check_dryrun_cell_tiny()
    check_pipelined_lm_equivalence()
    check_serve_profile_equivalence()
    print("ALL_DIST_OK")
