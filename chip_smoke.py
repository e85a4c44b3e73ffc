#!/usr/bin/env python3
"""On-chip smoke test of the planned N-D FFT.

Drives the public front-end the way a user calls it: ``plan_nd`` and the
``rfftn``/``irfftn``/``fftn``/``ifftn`` family on a ``jax.make_mesh`` mesh,
eagerly (no ``jit`` of its own), at the paper's sizes.  Every result is
checked against ``numpy.fft`` at full size on the host, and round-tripped
through its inverse.

    python chip_smoke.py             # one chip: 2D r2c 2^14 x 2^14, 3D c2c 512^3
    python chip_smoke.py --chips 4   # four chips: slab r2c 2^14 x 2^14,
                                     # pencil c2c 512^3 on 2x2, factor1d c2c 2^24

Each phase prints its plan, its errors, its compile time, one steady call
time (host clock around ``block_until_ready``: a smoke timing, not a
benchmark) and the device's peak memory.  The last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import (Planner, fftn, hardware_for, ifftn,  # noqa: E402
                        irfftn, plan_nd, rfftn)
from repro.launch.compile_cache import use_compile_cache   # noqa: E402

#: max |result - numpy| / max |numpy| for the forward transforms, and
#: max |roundtrip - x| / max |x| for the round trips.  An f32 transform at
#: these sizes lands near 1e-6; one bf16 matmul pass lands near 1e-3.
TOL = 1e-4

SEED = 0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def _compile_log():
    """Count backend compiles, their seconds, and persistent-cache hits."""
    log = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == _COMPILE_EVENT:
            log["compiles"] += 1
            log["compile_s"] += secs

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            log["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def _rel_err(got, ref, block: int = 1 << 22) -> float:
    """max |got - ref| / max |ref|, in blocks so that the float64
    temporaries stay small at 2^28 elements."""
    got, ref = got.reshape(-1), ref.reshape(-1)
    num = den = 0.0
    for i in range(0, ref.size, block):
        g, r = got[i:i + block], ref[i:i + block]
        num = max(num, float(np.max(np.abs(g - r))))
        den = max(den, float(np.max(np.abs(r))))
    return num / den


def _host_complex(pair) -> np.ndarray:
    re, im = (np.asarray(jax.device_get(a)) for a in pair)
    return re + 1j * im


def _peak_bytes(mesh) -> str:
    stats = mesh.devices.flat[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


def _check(ok: bool, message: str) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not ok:
        raise AssertionError(message)


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def run_phase(name: str, kind: str, shape, mesh, planner: Planner, *,
              decomp=None, in_spec=P(), seed: int = SEED,
              tol: float = TOL) -> dict:
    """Plan, transform, check against numpy, invert, check the round trip.

    ``decomp`` forces a decomposition through ``plan_nd(decomp=...)``; what
    ``plan_nd`` picks on its own is printed beside it.  On a mesh of more
    than one device the spectrum must be sharded over every device of it.
    Raises ``AssertionError`` on any failed check."""
    shape = tuple(shape)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape, dtype=np.float32)
    if kind == "c2c":
        x = x + 1j * rng.standard_normal(shape, dtype=np.float32)
    own = plan_nd(shape, kind, mesh=mesh, planner=planner)
    nd = own if decomp is None else plan_nd(
        shape, kind, mesh=mesh, planner=planner, decomp=decomp)
    print(f"[{name}] shape={shape} kind={kind} decomp={nd.decomp} "
          f"mesh_axes={nd.mesh_axes} comm={nd.comm} "
          f"(plan_nd alone picks decomp={own.decomp} "
          f"mesh_axes={own.mesh_axes} comm={own.comm})", flush=True)

    sharding = NamedSharding(mesh, in_spec)
    if kind == "r2c":
        xd = jax.device_put(x, sharding)
        ref = np.fft.rfftn(x.astype(np.float64))

        def forward(a):
            return rfftn(a, mesh=mesh, plan=nd, planner=planner)

        def inverse(c):
            return irfftn(c, mesh=mesh, plan=nd, planner=planner)
    else:
        xd = tuple(jax.device_put(np.ascontiguousarray(a), sharding)
                   for a in (x.real, x.imag))
        ref = np.fft.fftn(x.astype(np.complex128))

        def forward(a):
            return fftn(a, mesh=mesh, plan=nd, planner=planner)

        def inverse(c):
            return ifftn(c, mesh=mesh, plan=nd, planner=planner)

    with _compile_log() as log:
        y, first_s = _timed(forward, xd)
    y, steady_s = _timed(forward, xd)
    err = _rel_err(_host_complex(y), ref)
    del ref
    back = jax.block_until_ready(inverse(y))
    back = _host_complex(back) if kind == "c2c" else np.asarray(back)
    rt_err = _rel_err(back, x)
    out_sharding = y[0].sharding
    print(f"[{name}] rel_err={err:.3e} roundtrip_err={rt_err:.3e} "
          f"tol={tol:g}", flush=True)
    print(f"[{name}] output sharding: {out_sharding}", flush=True)
    print(f"[{name}] smoke timing (host clock, not a benchmark): "
          f"first call {first_s:.3f} s with {log['compiles']} compiles "
          f"taking {log['compile_s']:.3f} s and {log['cache_hits']} "
          f"persistent-cache hits; steady call {steady_s:.4f} s; "
          f"peak_bytes_in_use={_peak_bytes(mesh)}", flush=True)

    _check(err < tol, f"{name}: forward error {err:.3e} >= {tol:g}")
    _check(rt_err < tol,
           f"{name}: round-trip error {rt_err:.3e} >= {tol:g}")
    if mesh.size > 1:
        _check(out_sharding.device_set == set(mesh.devices.flat)
               and not out_sharding.is_fully_replicated,
               f"{name}: spectrum is not sharded over the mesh: "
               f"{out_sharding}")
    return {"err": err, "roundtrip_err": rt_err, "first_s": first_s,
            "steady_s": steady_s, **log}


def one_chip_phases(mesh, planner, n2d: int = 1 << 14, n3d: int = 512):
    """The planned local transforms at the paper's sizes."""
    run_phase("r2c_2d", "r2c", (n2d, n2d), mesh, planner)
    run_phase("c2c_3d", "c2c", (n3d,) * 3, mesh, planner)


def four_chip_phases(slab_mesh, pencil_mesh, planner, n2d: int = 1 << 14,
                     n3d: int = 512, n1d: int = 1 << 24):
    """The decompositions that exist only across chips."""
    (ax,) = slab_mesh.axis_names
    run_phase("slab_r2c_2d", "r2c", (n2d, n2d), slab_mesh, planner,
              decomp="slab", in_spec=P(ax, None))
    run_phase("pencil_c2c_3d", "c2c", (n3d,) * 3, pencil_mesh, planner,
              decomp="pencil", in_spec=P(*pencil_mesh.axis_names, None))
    run_phase("factor1d_c2c_1d", "c2c", (n1d,), slab_mesh, planner,
              decomp="factor1d", in_spec=P(ax))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the local transforms; 4: slab, pencil and "
                         "factor1d across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{dev.platform!r}); not running on another device")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} chips, JAX sees {len(devices)}")
    hw = hardware_for(dev.device_kind)
    planner = Planner(hardware=hw)
    _check(planner.hw == hw, f"planner peaks {planner.hw} are not {hw}")
    cache = use_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
          f"planner hardware {hw.name}; compile cache {cache}", flush=True)

    if args.chips == 1:
        mesh = jax.make_mesh((1,), ("fft",), devices=devices[:1])
        one_chip_phases(mesh, planner)
    else:
        slab = jax.make_mesh((4,), ("fft",), devices=devices[:4])
        pencil = jax.make_mesh((2, 2), ("px", "py"), devices=devices[:4])
        four_chip_phases(slab, pencil, planner)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
