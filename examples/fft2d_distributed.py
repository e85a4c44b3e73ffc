"""Distributed N-D FFT through the planned front-end (the paper's §5.3
experiment) on the devices present: `plan_nd` scores local vs slab vs
pencil decompositions (with mesh-axis assignment), resolves the exchange
backends (roofline "auto" or on-mesh-timed "measure"), and the `fftn`
family executes the plan — numpy-exact shapes, mixed-radix meshes and
batch dims included.  The slab mesh spans every device; the pencil mesh
lays the same devices out as rows x cols with rows >= cols.

    # eight virtual CPU devices
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/fft2d_distributed.py
    PYTHONPATH=src python examples/fft2d_distributed.py --comm measure \
        --wisdom /tmp/fft_wisdom.json   # rerun: zero re-measurement
"""

import argparse
import time

import jax
import numpy as np

from repro.core import Planner, fftn, ifftn, irfftn, plan_nd, rfftn
from repro.launch.compile_cache import use_compile_cache

COMM_CHOICES = ("collective", "pipelined", "agas", "auto", "measure")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--comm", choices=COMM_CHOICES, default=None,
                    help="run a single exchange backend / selection mode "
                         "(default: sweep them all)")
    ap.add_argument("--wisdom", default=None,
                    help="wisdom JSON path shared by plan + comm + dfft "
                         "autotuners (measure verdicts persist across runs)")
    args = ap.parse_args()
    sweep = COMM_CHOICES if args.comm is None else (args.comm,)
    use_compile_cache()

    n = len(jax.devices())
    cols = max(c for c in range(1, int(n ** 0.5) + 1) if n % c == 0)
    mesh = jax.make_mesh((n,), ("fft",))
    mesh2 = jax.make_mesh((n // cols, cols), ("mx", "my"))
    print(f"{n} {jax.devices()[0].platform} devices: slab mesh {n}, "
          f"pencil mesh {n // cols}x{cols}")
    planner = Planner(mode="estimate", backends=("jnp",),
                      wisdom_path=args.wisdom)
    rng = np.random.default_rng(0)

    # ------------------------------------------------------------------
    # the decomposition planner at work: one front-end, every layout
    # ------------------------------------------------------------------
    for shape, kind, m in (((64, 64), "r2c", mesh),
                           ((512, 512), "r2c", mesh),
                           ((32, 64, 128), "c2c", mesh2),
                           ((10, 36), "r2c", mesh)):     # mixed radix
        nd = plan_nd(shape, kind, mesh=m, planner=planner)
        print(f"plan_nd{shape} {kind}: decomp={nd.decomp:7s} "
              f"axes={nd.mesh_axes} comm={nd.comm} "
              f"est={nd.est_cost * 1e6:8.1f}us")

    # 2D r2c through the front-end, per comm spec
    n, m = 512, 512
    x = rng.standard_normal((n, m)).astype(np.float32)
    ref = np.fft.rfft2(x)
    for comm in sweep:
        nd = plan_nd((n, m), "r2c", mesh=mesh, comm=comm, planner=planner,
                     decomp="slab", axes=("fft",))
        fn = jax.jit(lambda a, _p=nd: rfftn(a, mesh=mesh, plan=_p,
                                            planner=planner))
        out = jax.block_until_ready(fn(x))
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(x))
        dt = time.perf_counter() - t0
        z = np.asarray(out[0]) + 1j * np.asarray(out[1])
        err = np.max(np.abs(z - ref)) / np.max(np.abs(ref))
        print(f"rfftn slab comm={comm:10s} t={dt * 1e3:7.1f}ms "
              f"rel_err={err:.2e}")

    # roundtrip through the inverse (the same plan serves both directions)
    nd = plan_nd((n, m), "r2c", mesh=mesh, planner=planner)
    back = irfftn(rfftn(x, mesh=mesh, plan=nd, planner=planner),
                  shape=(n, m), mesh=mesh, plan=nd, planner=planner)
    print("irfftn roundtrip err:", float(np.max(np.abs(np.asarray(back) - x))))

    # 3D pencil decomposition (P3DFFT-style) on the 2D mesh, per comm spec
    xc = (rng.standard_normal((32, 64, 128)).astype(np.float32)
          + 1j * rng.standard_normal((32, 64, 128)).astype(np.float32))
    ref3 = np.fft.fftn(xc)
    for comm in sweep:
        nd3 = plan_nd((32, 64, 128), "c2c", mesh=mesh2, comm=comm,
                      planner=planner, decomp="pencil", axes=("mx", "my"))
        rr, ri = fftn(xc, mesh=mesh2, plan=nd3, planner=planner)
        err3 = np.max(np.abs((np.asarray(rr) + 1j * np.asarray(ri)) - ref3)) \
            / np.max(np.abs(ref3))
        print(f"fftn pencil comm={comm:10s} rel_err={err3:.2e}")
    if args.wisdom:
        from repro.core import comm as comm_mod
        verdicts = {k: planner.wisdom.get(k).get("backend",
                                                 planner.wisdom.get(k))
                    for k in planner.wisdom.keys("comm/")}
        print(f"comm wisdom at {args.wisdom}: {verdicts} "
              f"(timing probes this run: {comm_mod.MEASURE_STATS['timed']})")
        print("dfft wisdom:", list(planner.wisdom.keys("dfft/")))

    # mixed per-axis selection + full c2c roundtrip
    ndp = plan_nd((32, 64, 128), "c2c", mesh=mesh2,
                  comm=("collective", "pipelined"), planner=planner,
                  decomp="pencil", axes=("mx", "my"))
    br, bi = ifftn(fftn(xc, mesh=mesh2, plan=ndp, planner=planner),
                   mesh=mesh2, plan=ndp, planner=planner)
    back3 = np.asarray(br) + 1j * np.asarray(bi)
    print("ifftn roundtrip err:", float(np.max(np.abs(back3 - xc))))

    # 3D r2c/c2r roundtrip with a leading batch dim and a mixed-radix mesh
    # (on 8 devices neither X=6 nor Y=10 divides the 4x2 communicators; the
    # padded bands are planned, carried, and cropped by the NdPlan recipe)
    xr3 = rng.standard_normal((2, 6, 10, 128)).astype(np.float32)
    ndr = plan_nd((6, 10, 128), "r2c", mesh=mesh2, planner=planner,
                  decomp="pencil", axes=("mx", "my"))
    re3, im3 = rfftn(xr3, mesh=mesh2, plan=ndr, planner=planner, ndim=3)
    z3 = np.asarray(re3) + 1j * np.asarray(im3)
    ref_r = np.fft.rfftn(xr3, axes=(-3, -2, -1))
    err_r = np.max(np.abs(z3 - ref_r)) / np.max(np.abs(ref_r))
    back_r = irfftn((re3, im3), shape=(6, 10, 128), mesh=mesh2, plan=ndr,
                    planner=planner)
    print(f"rfftn pencil(batch,mixed-radix) rel_err={err_r:.2e}  "
          "irfftn roundtrip err:",
          float(np.max(np.abs(np.asarray(back_r) - xr3))))


if __name__ == "__main__":
    main()
