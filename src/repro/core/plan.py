"""FFTW-style planning for the matmul FFT.

The paper shows FFTW's behaviour is dominated by *planning*: estimated plans
are cheap but can leave >5x performance on the table for threaded backends;
measured plans cost >50x more planning time but rescue scaling (Figs. 3-5).

We reproduce that trade-off natively:

* ``estimate``  — analytic roofline cost model over candidate (factorization,
  backend, layout) tuples, using a ``HardwareSpec``; O(us) planning.
* ``measured``  — compile and time every candidate on the local device (like
  FFTW's MEASURE dynamic programming over codelets) and keep the fastest.
* wisdom       — plans are cached by (n, kind, batch-bucket, mode, backend
  restriction) in-process and optionally persisted to a JSON wisdom file,
  exactly like FFTW wisdom.  The store (:class:`repro.core.wisdom.WisdomStore`)
  is shared with the communication autotuner: ``plan/*`` keys live next to
  the ``comm/*`` verdicts of :func:`repro.core.comm.measure_comm`, and the
  ``export_wisdom`` / ``import_wisdom`` / ``forget_wisdom`` methods mirror
  FFTW's API over the whole unified store.

A ``Plan`` is a pure-data recipe; ``execute`` closes over it.  Plans are
reusable across arrays with the same trailing length (batch size is free),
matching FFTW semantics where a plan is tied to the FFT length.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import algo
from .wisdom import WisdomStore, batch_bucket

# ---------------------------------------------------------------------------
# hardware profiles (roofline constants)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    flops: float          # peak FLOP/s (f32 matmul units)
    hbm_bw: float         # bytes/s main-memory bandwidth
    link_bw: float        # bytes/s per interconnect link
    matmul_dim: int       # native matmul tile (MXU = 128)
    vmem_bytes: int       # fast scratch (VMEM / L2)


TPU_V5E = HardwareSpec("tpu_v5e", flops=197e12 / 2, hbm_bw=819e9, link_bw=50e9,
                       matmul_dim=128, vmem_bytes=128 * 2 ** 20)
# 197 TFLOP/s bf16 and 819 GB/s HBM are the published v5e peaks (Google Cloud
# "TPU v5e").  The DFT matmuls are f32 at Precision.HIGHEST (several bf16
# passes), so flops=bf16/2 and link_bw are guesses until a chip run
# measures them.
CPU_LOCAL = HardwareSpec("cpu_local", flops=5e9, hbm_bw=20e9, link_bw=1e9,
                         matmul_dim=8, vmem_bytes=32 * 2 ** 20)

#: roofline constants keyed by ``jax.Device.device_kind``
HARDWARE_BY_KIND = {"TPU v5 lite": TPU_V5E}


def hardware_for(device_kind: str) -> HardwareSpec:
    """The :class:`HardwareSpec` of a device kind.  A kind that is not in
    :data:`HARDWARE_BY_KIND` is an error, never another chip's peaks."""
    try:
        return HARDWARE_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(f"no HardwareSpec for device kind {device_kind!r} "
                         f"(known: {sorted(HARDWARE_BY_KIND)})") from None


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

BACKENDS = ("jnp", "jnp_karatsuba", "pallas", "pallas_karatsuba", "xla_native")


@dataclasses.dataclass(frozen=True)
class Plan:
    """A 1D FFT recipe (FFTW: one plan per transform length)."""
    n: int
    kind: str                       # "c2c" | "r2c" | "c2r"
    factors: Tuple[int, ...]
    backend: str                    # one of BACKENDS
    permuted: bool = False          # skip digit transpose (conv pipelines)
    est_cost: float = 0.0           # seconds, from the cost model
    measured_cost: float = -1.0     # seconds, if mode == "measured"

    @property
    def karatsuba(self) -> bool:
        return self.backend.endswith("karatsuba")

    def flops(self, batch: int) -> float:
        """Real-MAC flop count for one batched apply."""
        if self.backend == "xla_native":
            return 5.0 * batch * self.n * max(np.log2(self.n), 1)
        n_eff = self.n // 2 if self.kind in ("r2c", "c2r") else self.n
        muls = 3 if self.karatsuba else 4
        return 2.0 * muls * batch * n_eff * sum(self.factors)

    def bytes_moved(self, batch: int) -> float:
        """HBM traffic estimate: each four-step stage reads+writes the array;
        the fused kernel keeps its stages in VMEM and reads+writes it once."""
        n_eff = self.n // 2 if self.kind in ("r2c", "c2r") else self.n
        if self.backend.startswith("pallas"):
            passes = 1
        else:
            passes = max(len(self.factors), 1) + (0 if self.permuted else 1)
        return 2.0 * passes * batch * n_eff * 8.0  # (re, im) f32


def _candidate_factorizations(n: int, max_base: int) -> Sequence[Tuple[int, ...]]:
    """All 1/2/3-way splits with every factor <= max_base (dedup, sorted)."""
    cands = set()
    if n <= max_base:
        cands.add((n,))
    for f1 in range(2, max_base + 1):
        if n % f1:
            continue
        r1 = n // f1
        if r1 <= max_base:
            cands.add(tuple(sorted((f1, r1), reverse=True)))
        for f2 in range(2, max_base + 1):
            if r1 % f2:
                continue
            r2 = r1 // f2
            if r2 <= max_base:
                cands.add(tuple(sorted((f1, f2, r2), reverse=True)))
    return sorted(cands)


def default_backends() -> Tuple[str, ...]:
    """The backends a Planner chooses among unless told: on a TPU the fused
    Pallas kernel for the splits it runs and jnp for the rest; elsewhere
    jnp alone (the kernel would only be interpreted)."""
    return ("pallas", "jnp") if jax.default_backend() == "tpu" else ("jnp",)


class Planner:
    """Creates and caches plans. ``mode``: "estimate" | "measured".
    ``backends=None``: :func:`default_backends` of the platform JAX runs
    on."""

    def __init__(self, hardware: HardwareSpec = TPU_V5E,
                 mode: str = "estimate", max_base: int = 128,
                 wisdom_path: Optional[str] = None,
                 backends: Optional[Sequence[str]] = None,
                 wisdom: Optional[WisdomStore] = None):
        assert mode in ("estimate", "measured")
        self.hw = hardware
        self.mode = mode
        self.max_base = max_base
        self.backends = tuple(backends or default_backends())
        # a shared store may be passed in (e.g. one file for several
        # planners + the comm autotuner); otherwise open/create our own.
        self.wisdom = wisdom if wisdom is not None else WisdomStore(wisdom_path)
        self.wisdom_path = self.wisdom.path
        self.last_plan_seconds: float = 0.0

    # -- FFTW-style wisdom API (unified plan/* + comm/* store) ---------------

    def export_wisdom(self) -> str:
        return self.wisdom.export_wisdom()

    def import_wisdom(self, text: str, replace: bool = False) -> int:
        return self.wisdom.import_wisdom(text, replace=replace)

    def forget_wisdom(self, prefix: str = "") -> int:
        return self.wisdom.forget_wisdom(prefix)

    # -- cost model ---------------------------------------------------------

    def _estimate_seconds(self, plan: Plan, batch: int) -> float:
        hw = self.hw
        t_compute = plan.flops(batch) / hw.flops
        t_mem = plan.bytes_moved(batch) / hw.hbm_bw
        # matmul efficiency penalty: factors far below the MXU tile waste lanes
        if plan.backend != "xla_native" and plan.factors:
            util = min(min(plan.factors) / hw.matmul_dim, 1.0)
            t_compute = t_compute / max(util, 1 / hw.matmul_dim)
        return max(t_compute, t_mem)

    # -- plan construction ---------------------------------------------------

    def _candidates(self, n: int, kind: str, permuted: bool):
        from repro.kernels.dft_matmul.dft_matmul import kernel_factors
        n_eff = n // 2 if kind in ("r2c", "c2r") else n
        for backend in self.backends:
            if backend == "xla_native":
                yield Plan(n, kind, (), backend)
                continue
            for fac in _candidate_factorizations(n_eff, self.max_base):
                if backend.startswith("pallas"):
                    # only splits the kernel runs, in the order it runs them
                    fac = kernel_factors(fac)
                    if fac is None:
                        continue
                if permuted and len(fac) != 2:
                    continue
                yield Plan(n, kind, fac, backend, permuted=permuted)

    def plan(self, n: int, kind: str = "c2c", batch: int = 1,
             permuted: bool = False) -> Plan:
        key = (f"plan/{n}/{kind}/b{batch_bucket(batch)}/{self.mode}/"
               f"{permuted}/{','.join(self.backends)}")
        w = self.wisdom.get(key)
        if w is not None:
            self.last_plan_seconds = 0.0
            return Plan(n, kind, tuple(w["factors"]), w["backend"], permuted,
                        w.get("est", 0.0), w.get("measured", -1.0))
        t0 = time.perf_counter()
        cands = [dataclasses.replace(p, est_cost=self._estimate_seconds(p, batch))
                 for p in self._candidates(n, kind, permuted)]
        if not cands:
            raise ValueError(f"no plan candidates for n={n} ({kind})")
        cands.sort(key=lambda p: p.est_cost)
        if self.mode == "estimate":
            best = cands[0]
        else:
            best = self._measure(cands[: min(len(cands), 12)], n, kind, batch)
        self.last_plan_seconds = time.perf_counter() - t0
        self.wisdom.put(key, {"factors": list(best.factors),
                              "backend": best.backend,
                              "est": best.est_cost,
                              "measured": best.measured_cost})
        return best

    # -- N-D decomposition planning (the guru interface) ----------------------

    def plan_nd(self, shape, kind: str = "c2c", mesh=None, axes=None,
                mode: Optional[str] = None, comm="auto", decomp=None,
                output_layout: str = "natural"):
        """Plan an N-D (possibly distributed) transform with THIS planner's
        hardware profile and wisdom store (delegates to
        :func:`repro.core.api.plan_nd`).  ``mode`` defaults to the
        planner's own mode, so a measured Planner measures decompositions
        too."""
        from .api import plan_nd
        if mode is None:
            mode = "measured" if self.mode == "measured" else "estimate"
        return plan_nd(shape, kind, mesh=mesh, axes=axes, mode=mode,
                       comm=comm, planner=self, decomp=decomp,
                       output_layout=output_layout)

    # -- communication planning (paper §5.3: parcelport choice) ---------------

    def plan_comm(self, n: int, m: int, p: int,
                  overlap_capable: bool = True) -> str:
        """Pick the slab exchange backend for this planner's hardware
        (delegates to :func:`repro.core.comm.plan_comm`)."""
        from .comm import plan_comm
        return plan_comm(n, m, p, hw=self.hw,
                         overlap_capable=overlap_capable)

    def plan_comm_pencil(self, shape, mesh_shape, kind: str = "c2c",
                         overlap_capable: bool = True):
        """Pick per-mesh-axis pencil exchange backends for this planner's
        hardware (delegates to :func:`repro.core.comm.plan_comm_pencil`)."""
        from .comm import plan_comm_pencil
        return plan_comm_pencil(shape, mesh_shape, hw=self.hw,
                                overlap_capable=overlap_capable, kind=kind)

    # -- measured planning (FFTW MEASURE) -------------------------------------

    def _measure(self, cands: Sequence[Plan], n: int, kind: str, batch: int) -> Plan:
        best, best_t = None, float("inf")
        if kind == "c2c":
            probe = (jnp.ones((batch, n), jnp.float32), jnp.zeros((batch, n), jnp.float32))
        else:
            probe = jnp.ones((batch, n), jnp.float32)
        for p in cands:
            fn = jax.jit(lambda a, _p=p: execute(_p, a))
            try:
                out = fn(probe)
            except NotImplementedError:     # unsupported here: skip it
                continue
            jax.block_until_ready(out)
            reps, t0 = 3, time.perf_counter()
            for _ in range(reps):
                out = fn(probe)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / reps
            if dt < best_t:
                best, best_t = p, dt
        if best is None:
            raise RuntimeError(f"no supported plan candidate for n={n} "
                               f"({kind})")
        return dataclasses.replace(best, measured_cost=best_t)


# ---------------------------------------------------------------------------
# plan execution
# ---------------------------------------------------------------------------


def _fused(plan: Plan, sign: int = -1):
    """The plan's c2c stage of length ``n_eff`` as the fused Pallas kernel
    (unscaled)."""
    from repro.kernels.dft_matmul import ops as dft_ops
    return functools.partial(dft_ops.fft_four_step, factors=plan.factors,
                             sign=sign, karatsuba=plan.karatsuba)


def _fused_inverse(plan: Plan):
    fused = _fused(plan, sign=+1)
    n_eff = plan.factors[0] * plan.factors[1]
    return lambda c: algo.cscale(fused(c), 1.0 / n_eff)


def execute(plan: Plan, x):
    """Apply a plan along the last axis. c2c takes/returns an (re, im) pair;
    r2c takes a real array and returns a pair; c2r the reverse.  A
    ``pallas`` plan runs its c2c stage (the inner one of r2c and c2r)
    through the fused kernel; the r2c pack and unpack stay jnp."""
    if plan.backend == "xla_native":
        if plan.kind == "c2c":
            z = jnp.fft.fft(algo.to_complex(x))
            return jnp.real(z), jnp.imag(z)
        if plan.kind == "r2c":
            z = jnp.fft.rfft(x.astype(jnp.float32))
            return jnp.real(z), jnp.imag(z)
        return jnp.fft.irfft(algo.to_complex(x)).astype(jnp.float32)

    if plan.backend.startswith("pallas"):
        if plan.kind == "c2c":
            return _fused(plan)(x, permuted=plan.permuted)
        if plan.kind == "r2c":
            return algo.rfft(x, inner=_fused(plan))
        return algo.irfft(x, inner=_fused_inverse(plan))

    opts = dict(factors=plan.factors or None, karatsuba=plan.karatsuba)
    if plan.kind == "c2c":
        return algo.fft(x, permuted=plan.permuted, **opts)
    if plan.kind == "r2c":
        return algo.rfft(x, **opts)
    if plan.kind == "c2r":
        return algo.irfft(x, **opts)
    raise ValueError(plan.kind)


def execute_inverse(plan: Plan, x):
    """Inverse transform matching ``plan`` (c2c only).  A ``pallas`` plan
    runs the fused kernel with the conjugate tables, unless it is
    permuted: that inverse consumes the permuted order in jnp
    (:func:`repro.core.algo.ifft_from_permuted`)."""
    assert plan.kind == "c2c"
    if plan.backend == "xla_native":
        z = jnp.fft.ifft(algo.to_complex(x))
        return jnp.real(z), jnp.imag(z)
    if plan.permuted:
        return algo.ifft_from_permuted(x, factors=plan.factors,
                                       karatsuba=plan.karatsuba)
    if plan.backend.startswith("pallas"):
        return _fused_inverse(plan)(x)
    return algo.ifft(x, factors=plan.factors or None, karatsuba=plan.karatsuba)
