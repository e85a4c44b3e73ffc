"""One planned N-D transform front-end: ``plan_nd`` + the ``fftn`` family.

The paper's central lesson is that the *plan* — not clever asynchrony —
decides FFT performance.  Our distributed layer used to make the biggest
planning decision (slab vs pencil vs purely local, and how to pad/batch) by
forcing the caller to pick among six shape-specific entry points.  This
module hides that behind FFTW's ``plan_many``/guru idea: one planner that
scores every decomposition the mesh supports and returns a pure-data
:class:`NdPlan` recipe, plus thin ``fftn``/``ifftn``/``rfftn``/``irfftn``
conveniences that execute it.

Decompositions scored (both of the paper's planning modes):

* **local**    — single-device planned execution (no mesh, or the exchange
  cost outweighs the speedup; on a mesh the model charges one gather).
* **slab**     — 1D decomposition over one mesh axis (ndim >= 2), including
  which mesh axis (assignment matters: it sets the padding).
* **pencil**   — P3DFFT-style multi-axis decomposition (ndim >= 3), over
  every ordered tuple of 2..ndim-1 mesh axes: the leading transform axes
  are sharded and one exchange per adjacent pair walks the chain.
* **factor1d** — distributed 1D c2c via the ``fft_conv`` factor-split
  algorithm (the length-N signal viewed as an (n1, n2) matrix, three
  exchanges), whenever ``repro.core.fftconv.factor_split`` finds a split.

The planner also decides the OUTPUT LAYOUT: ``output_layout="transposed"``
asks for the spectrum sharded over the last transform axis instead of the
first, which lets the slab executor skip its second exchange entirely (and
``ifftn`` invert the transposed spectrum with a single exchange, no
re-shuffle).  Values stay at their natural numpy index positions either
way — only the sharding differs — so ``NdPlan.crop`` is unchanged.

``mode="estimate"`` scores candidates with the roofline model extended from
:mod:`repro.core.plan` / :mod:`repro.core.comm` (compute + HBM + wire bytes
+ a per-collective latency charge).  ``mode="measured"`` additionally
compiles and times the finalists on the LIVE mesh — FFTW MEASURE applied to
the decomposition choice — reusing the ``measure_comm_*`` autotuners for
each finalist's exchanges.  Verdicts are cached under the ``dfft/*``
namespace of the unified wisdom store, next to the ``plan/*`` and ``comm/*``
entries, so a given (shape, mesh, kind, mode, comm) decision is made once
per process — and once per *machine* with a wisdom file.

The executors live in :mod:`repro.core.dfft`; this module only plans,
dispatches, and crops (``NdPlan.crop`` recovers the exact transform from
the collective-padded layout, including mixed-radix mesh shapes).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import algo, dfft
from .comm import (_normalize_axis_specs, _time_callable, fac_sum,
                   measure_comm_factor1d, measure_comm_pencil_nd,
                   measure_comm_slab_nd, pad_to, plan_comm_factor1d,
                   plan_comm_pencil_nd, plan_comm_slab_nd)
from .fftconv import factor_split
from .plan import Planner, execute, execute_inverse

Complex = algo.Complex

__all__ = ["NdPlan", "plan_nd", "execute_nd", "execute_nd_inverse",
           "fftn", "ifftn", "rfftn", "irfftn", "PLAN_ND_STATS",
           "COLLECTIVE_LAT"]

DECOMPS = ("local", "slab", "pencil", "factor1d")
OUTPUT_LAYOUTS = ("natural", "transposed")

#: per-collective latency charge in the decomposition roofline (seconds).
#: This is what makes small transforms stay local: two exchanges cost more
#: than the whole FFT until the wire/compute terms dominate.
COLLECTIVE_LAT = 2e-5

#: whole-transform timing probes actually executed by ``mode="measured"``;
#: tests snapshot this to prove wisdom hits re-measure nothing.
PLAN_ND_STATS = {"timed": 0}


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NdPlan:
    """A pure-data recipe for one N-D (possibly distributed) transform.

    ``shape`` is the transform shape (the trailing axes of the input; any
    leading axes are batch).  ``mesh_axes``/``mesh_shape`` name the mesh
    axes the decomposition uses, in decomposition order; ``comm`` holds one
    RESOLVED exchange spec per mesh axis (never ``"auto"``/``"measure"`` —
    those are resolved at planning time).  ``output_layout="transposed"``
    leaves the spectrum sharded over the LAST transform axis (the slab
    executor skips its restore exchange; values keep natural positions).
    ``factors`` is the (n1, n2) split of the ``factor1d`` decomposition.
    """

    shape: Tuple[int, ...]
    kind: str                            # "c2c" | "r2c"
    decomp: str                          # one of DECOMPS
    mesh_axes: Tuple[str, ...] = ()
    mesh_shape: Tuple[int, ...] = ()
    comm: Tuple = ()
    mode: str = "estimate"
    est_cost: float = 0.0
    measured_cost: float = -1.0
    output_layout: str = "natural"       # "natural" | "transposed"
    factors: Tuple[int, ...] = ()        # factor1d: the (n1, n2) split

    # -- padded layout (the shared pad-and-crop convention) -----------------

    @property
    def spectrum_shape(self) -> Tuple[int, ...]:
        """Exact transform output shape (``numpy.fft.fftn``/``rfftn``)."""
        if self.kind == "r2c":
            return self.shape[:-1] + (self.shape[-1] // 2 + 1,)
        return self.shape

    @property
    def padded_spectrum_shape(self) -> Tuple[int, ...]:
        """Spectrum shape with the collective-divisibility padding the
        executors produce (equal to ``spectrum_shape`` for local plans)."""
        s, sp = self.shape, self.spectrum_shape
        if self.decomp == "slab":
            (p,) = self.mesh_shape
            return (pad_to(s[0], p),) + s[1:-1] + (pad_to(sp[-1], p),)
        if self.decomp == "pencil":
            ps, k = self.mesh_shape, len(self.mesh_shape)
            # axis j (0 < j < k) is input-sharded over p_j and
            # exchange-split over p_{j-1}, so its padding must divide both
            # communicators; unsharded middle axes stay unpadded
            return ((pad_to(s[0], ps[0]),)
                    + tuple(pad_to(s[j], math.lcm(ps[j - 1], ps[j]))
                            for j in range(1, k))
                    + s[k:-1] + (pad_to(sp[-1], ps[-1]),))
        return sp

    @property
    def padded_input_shape(self) -> Tuple[int, ...]:
        """Input transform-shape after the executors' zero-padding of the
        sharded axes (the last axis is always fully local going in)."""
        return self.padded_spectrum_shape[:-1] + (self.shape[-1],)

    @property
    def crop(self) -> Tuple[slice, ...]:
        """Slices recovering the exact spectrum from the padded layout:
        ``padded[(..., *plan.crop)] == numpy`` result.  This is THE cropping
        contract — callers never hard-code the padded column count."""
        return tuple(slice(0, n) for n in self.spectrum_shape)

    def crop_pair(self, c: Complex) -> Complex:
        """Apply :attr:`crop` to an (re, im) pair (batch dims untouched)."""
        d = len(self.shape)
        for j, n in enumerate(self.spectrum_shape):
            c = dfft._crop_axis(c, c[0].ndim - d + j, n)
        return c


# ---------------------------------------------------------------------------
# the decomposition roofline (ESTIMATE mode)
# ---------------------------------------------------------------------------


def _estimate_nd(plan: NdPlan, hw, on_mesh: bool) -> float:
    """Roofline seconds for one execution of ``plan`` on ``hw``.

    Extends the 1D model of :class:`repro.core.plan.Planner` and the
    exchange model of :func:`repro.core.comm.plan_comm`: per-device compute
    is max(flops, HBM passes), each redistribution charges its wire bytes
    through one link plus ``COLLECTIVE_LAT``, and a *local* plan on a live
    mesh charges one gather of the whole array (the data is distributed;
    somebody has to move it).  Padding waste is priced in by using the
    padded shapes, which is what makes mesh-axis assignment non-trivial.
    """
    d = len(plan.shape)
    padded = plan.padded_spectrum_shape
    elems = float(np.prod(padded))
    bytes_pair = elems * 8.0                       # (re, im) f32
    if plan.decomp == "factor1d":                  # two planned 1D stages
        stage_macs = fac_sum(plan.factors[0]) + fac_sum(plan.factors[1])
    else:
        stage_macs = sum(fac_sum(n) for n in plan.shape)
    flops = 8.0 * elems * stage_macs
    devices = max(int(np.prod(plan.mesh_shape or (1,))), 1)
    t_comp = max(flops / hw.flops,
                 (d + 1) * bytes_pair / hw.hbm_bw) / devices
    t_comm = 0.0
    if plan.decomp == "local":
        if on_mesh:
            t_comm = bytes_pair / hw.link_bw + COLLECTIVE_LAT
    elif plan.decomp == "slab":
        (p,) = plan.mesh_shape
        wire = (p - 1) / p * (bytes_pair / p)
        # a transposed output layout skips the restore exchange entirely
        n_exchanges = 1.0 if plan.output_layout == "transposed" else 2.0
        t_comm = n_exchanges * (wire / hw.link_bw + COLLECTIVE_LAT)
    elif plan.decomp == "factor1d":
        (p,) = plan.mesh_shape
        wire = (p - 1) / p * (bytes_pair / p)
        # stage A + stage B + the natural-order unpermute
        t_comm = 3.0 * (wire / hw.link_bw + COLLECTIVE_LAT)
    else:                                          # pencil
        for p in plan.mesh_shape:
            if p <= 1:
                continue
            wire = (p - 1) / p * (bytes_pair / devices)
            t_comm += wire / hw.link_bw + COLLECTIVE_LAT
    return t_comp + t_comm


# ---------------------------------------------------------------------------
# candidate enumeration + comm resolution
# ---------------------------------------------------------------------------


def _mesh_axis_sizes(mesh, axes) -> "dict[str, int]":
    """Accepts a live ``jax.sharding.Mesh`` OR an abstract ``{name: size}``
    mapping (estimate-only planning without devices, e.g. in benchmarks)."""
    if mesh is None:
        return {}
    if isinstance(mesh, dict):
        sizes = dict(mesh)
    else:
        sizes = {a: mesh.shape[a] for a in mesh.axis_names}
    if axes is not None:
        sizes = {a: sizes[a] for a in axes}
    return sizes


def _candidates(shape, kind, sizes,
                output_layout: str = "natural"
                ) -> Sequence[Tuple[str, Tuple[str, ...]]]:
    """(decomp, mesh_axes) candidates the shape/mesh combination supports."""
    d = len(shape)
    live = [a for a, p in sizes.items() if p > 1]
    cands = [("local", ())]
    if d >= 2:
        cands += [("slab", (a,)) for a in live]
    if d >= 3:
        # multi-axis pencil: every ordered tuple of 2..ndim-1 mesh axes
        # (the k leading transform axes are sharded, one exchange per
        # adjacent pair of the chain)
        for k in range(2, min(d - 1, len(live)) + 1):
            cands += [("pencil", axes)
                      for axes in itertools.permutations(live, k)]
    if d == 1 and kind == "c2c" and output_layout == "natural":
        # distributed 1D through the fft_conv factor split (three
        # exchanges; output is natural-order, so no transposed layout)
        cands += [("factor1d", (a,)) for a in live
                  if factor_split(shape[0], sizes[a]) is not None]
    return cands


def _plan_factors(decomp: str, shape, mesh_axes, sizes) -> Tuple[int, ...]:
    """The (n1, n2) split a ``factor1d`` candidate executes; () otherwise."""
    if decomp != "factor1d":
        return ()
    split = factor_split(shape[0], sizes[mesh_axes[0]])
    if split is None:
        raise ValueError(
            f"no factor split of n={shape[0]} over p={sizes[mesh_axes[0]]} "
            "(need n divisible by p**2 with factorizable factors)")
    return split


def _resolve_comm(decomp, mesh_axes, shape, kind, comm, mesh, sizes,
                  planner, factors=()) -> Tuple:
    """Turn the user's ``comm`` argument into one concrete spec per mesh
    axis.  ``"auto"`` entries go through the roofline planners,
    ``"measure"`` entries through the on-mesh autotuners (live mesh only);
    explicit names / CommBackend instances / per-axis collections pass
    through as in the historical entry points."""
    if decomp == "local":
        return ()
    live = mesh is not None and not isinstance(mesh, dict)
    specs = list(_normalize_axis_specs(comm, mesh_axes))
    if decomp in ("slab", "factor1d"):
        (a,) = mesh_axes
        if specs[0] == "auto":
            if decomp == "factor1d":
                specs[0] = plan_comm_factor1d(shape[0], factors[0],
                                              factors[1], sizes[a],
                                              hw=planner.hw)
            else:
                specs[0] = plan_comm_slab_nd(shape, sizes[a], hw=planner.hw,
                                             kind=kind)
        elif specs[0] == "measure":
            if not live:
                raise ValueError('comm="measure" needs a live mesh')
            if decomp == "factor1d":
                specs[0] = measure_comm_factor1d(shape[0], tuple(factors),
                                                 mesh, a,
                                                 wisdom=planner.wisdom)
            else:
                specs[0] = measure_comm_slab_nd(shape, mesh, a, kind=kind,
                                                wisdom=planner.wisdom)
        return tuple(specs)
    # pencil: plan/measure per mesh axis, only the axes that ask
    if "auto" in specs:
        ps = tuple(sizes[a] for a in mesh_axes)
        planned = plan_comm_pencil_nd(shape, ps, hw=planner.hw, kind=kind)
        specs = [planned[i] if s == "auto" else s for i, s in enumerate(specs)]
    if "measure" in specs:
        if not live:
            raise ValueError('comm="measure" needs a live mesh')
        measured = measure_comm_pencil_nd(
            tuple(shape), mesh, mesh_axes, kind=kind, wisdom=planner.wisdom,
            which=tuple(s == "measure" for s in specs))
        specs = [measured[i] if s == "measure" else s
                 for i, s in enumerate(specs)]
    return tuple(specs)


def _comm_tag(comm) -> Optional[str]:
    """Stable wisdom-key tag for a comm argument, or None if uncacheable
    (CommBackend instances are process-local objects)."""
    if isinstance(comm, str):
        return comm
    if isinstance(comm, (list, tuple)) and all(isinstance(s, str)
                                               for s in comm):
        return ",".join(comm)
    if isinstance(comm, dict) and all(isinstance(s, str)
                                      for s in comm.values()):
        return ",".join(f"{k}={v}" for k, v in sorted(comm.items()))
    return None


# ---------------------------------------------------------------------------
# plan_nd (the guru interface)
# ---------------------------------------------------------------------------


def plan_nd(shape: Sequence[int], kind: str = "c2c", mesh=None,
            axes: Optional[Sequence[str]] = None, mode: str = "estimate",
            comm="auto", planner: Optional[Planner] = None,
            decomp: Optional[str] = None,
            output_layout: str = "natural") -> NdPlan:
    """Plan one N-D transform: pick the decomposition, the mesh-axis
    assignment, and the exchange backends; return the :class:`NdPlan`.

    ``shape``: transform shape (trailing axes; leading input axes are
    batch).  ``kind``: ``"c2c"`` or ``"r2c"`` (the plan serves the inverse
    too).  ``mesh``: a live ``jax.sharding.Mesh``, an abstract
    ``{axis_name: size}`` mapping (estimate-only), or None for single
    device.  ``axes`` restricts which mesh axes the planner may use.

    ``mode="estimate"`` scores candidates with the roofline model;
    ``mode="measured"`` also times the finalists on the live mesh (FFTW
    MEASURE applied to the decomposition choice).  ``comm`` is any spec the
    historical entry points accepted — a backend name/instance,
    ``"auto"``, ``"measure"``, or a per-mesh-axis collection for pencil.

    ``output_layout="transposed"`` plans for a spectrum sharded over the
    last transform axis (slab saves its restore exchange; the same plan
    passed to ``ifftn`` inverts the transposed spectrum without a
    re-shuffle).  Values keep their natural numpy positions either way.

    ``decomp`` forces a decomposition (the deprecated shims use this); the
    verdict of a free choice is cached under a ``dfft/v2/*`` wisdom key
    (pre-bump ``dfft/*`` entries are migrated on first lookup).
    """
    shape = tuple(int(n) for n in shape)
    assert kind in ("c2c", "r2c"), kind
    assert mode in ("estimate", "measured"), mode
    assert output_layout in OUTPUT_LAYOUTS, output_layout
    planner = planner or Planner()
    sizes = _mesh_axis_sizes(mesh, axes)
    live = mesh is not None and not isinstance(mesh, dict)

    def build(dec, mesh_axes, est=0.0, measured=-1.0, comm_arg=None):
        factors = _plan_factors(dec, shape, mesh_axes, sizes)
        return NdPlan(
            shape, kind, dec, tuple(mesh_axes),
            tuple(sizes[a] for a in mesh_axes),
            _resolve_comm(dec, tuple(mesh_axes), shape, kind,
                          comm if comm_arg is None else comm_arg, mesh,
                          sizes, planner, factors=factors),
            mode, est, measured, output_layout, factors)

    if decomp is not None:              # forced (shims, benchmarks)
        assert decomp in DECOMPS, decomp
        if decomp == "factor1d" and output_layout == "transposed":
            raise ValueError("factor1d output is natural-order only")
        if decomp == "slab" and len(shape) < 2:
            raise ValueError("slab decomposition needs ndim >= 2")
        if decomp == "factor1d" and (len(shape) != 1 or kind != "c2c"):
            raise ValueError("factor1d is the 1D c2c decomposition")
        if decomp == "local":
            mesh_axes = ()
        elif axes is not None:
            mesh_axes = tuple(axes)
        else:
            width = 1 if decomp in ("slab", "factor1d") else \
                min(len(sizes), len(shape) - 1)
            mesh_axes = tuple(list(sizes)[:width])
        if decomp == "pencil" and not 2 <= len(mesh_axes) <= len(shape) - 1:
            raise ValueError(
                f"pencil needs 2..ndim-1 mesh axes, got {mesh_axes} for "
                f"ndim={len(shape)}")
        nd = build(decomp, mesh_axes)
        return dataclasses.replace(
            nd, est_cost=_estimate_nd(nd, planner.hw, on_mesh=bool(sizes)))

    key = None
    tag = _comm_tag(comm)
    if tag is not None:
        mesh_tag = ".".join(f"{a}{p}" for a, p in sizes.items()) or "none"
        key = (f"dfft/v2/{'x'.join(str(n) for n in shape)}/{kind}/"
               f"{mesh_tag}/{mode}/{tag}/{output_layout}")
        hit = planner.wisdom.get(key)
        if hit is not None and not _valid_verdict(hit):
            # corrupt v2 record: re-plan (the fresh verdict overwrites it)
            hit = None
        if hit is None and output_layout == "natural":
            hit = _migrate_v1_verdict(planner, shape, kind, mesh_tag, mode,
                                      tag, key)
        if hit is not None:
            return NdPlan(shape, kind, hit["decomp"],
                          tuple(hit["mesh_axes"]), tuple(hit["mesh_shape"]),
                          tuple(hit["comm"]), mode, hit.get("est", 0.0),
                          hit.get("measured", -1.0),
                          hit.get("output_layout", "natural"),
                          tuple(hit.get("factors", ())))

    scored = []
    for dec, mesh_axes in _candidates(shape, kind, sizes, output_layout):
        nd = NdPlan(shape, kind, dec, mesh_axes,
                    tuple(sizes[a] for a in mesh_axes), (), mode,
                    output_layout=output_layout,
                    factors=_plan_factors(dec, shape, mesh_axes, sizes))
        scored.append((_estimate_nd(nd, planner.hw, on_mesh=bool(sizes)),
                       nd))
    scored.sort(key=lambda t: t[0])

    if mode == "measured" and live and len(scored) > 1:
        # measured mode prices every finalist with its best exchange:
        # "auto" comm upgrades to the on-mesh measure_comm_* autotuners
        m_comm = "measure" if comm == "auto" else comm
        best = _measure_finalists(scored, shape, kind, mesh, planner,
                                  lambda dec, axes_, est: build(
                                      dec, axes_, est=est, comm_arg=m_comm))
    else:
        est, nd = scored[0]
        best = build(nd.decomp, nd.mesh_axes, est=est)

    if key is not None and _comm_tag(best.comm) is not None:
        planner.wisdom.put(key, {
            "decomp": best.decomp, "mesh_axes": list(best.mesh_axes),
            "mesh_shape": list(best.mesh_shape), "comm": list(best.comm),
            "est": best.est_cost, "measured": best.measured_cost,
            "output_layout": best.output_layout,
            "factors": list(best.factors)})
    return best


def _valid_verdict(rec) -> bool:
    """A ``dfft/*`` wisdom record trustworthy enough to reconstruct a plan
    from (truncated/hand-edited records fall through to re-planning — the
    store is a cache, never ground truth)."""
    return (isinstance(rec, dict)
            and rec.get("decomp") in DECOMPS
            and all(isinstance(rec.get(f), list)
                    for f in ("mesh_axes", "mesh_shape", "comm"))
            and (rec["decomp"] != "factor1d"
                 or len(rec.get("factors") or ()) == 2))


def _migrate_v1_verdict(planner, shape, kind, mesh_tag, mode, tag,
                        v2_key) -> Optional[dict]:
    """Adopt a pre-bump ``dfft/*`` (v1) wisdom verdict for a natural-layout
    lookup: the v1 schema had no ``output_layout``/``factors`` fields (and
    no ``factor1d`` decomposition), so a v1 record is exactly a v2
    natural-layout record with the new fields defaulted.  The migrated
    record is re-written under the v2 key so the v1 entry is consulted at
    most once per key."""
    v1_key = (f"dfft/{'x'.join(str(n) for n in shape)}/{kind}/"
              f"{mesh_tag}/{mode}/{tag}")
    old = planner.wisdom.get(v1_key)
    # the v1 schema predates factor1d, so a factor1d decomp marks the
    # record as garbage rather than a migratable verdict
    if (not _valid_verdict(old)
            or old["decomp"] not in ("local", "slab", "pencil")):
        return None        # corrupt/truncated v1 record: re-plan instead
    rec = dict(old)
    rec.setdefault("output_layout", "natural")
    rec.setdefault("factors", [])
    planner.wisdom.put(v2_key, rec)
    return rec


def _measure_finalists(scored, shape, kind, mesh, planner, build) -> NdPlan:
    """FFTW MEASURE over decompositions: execute each finalist's forward
    transform once-compiled on the live mesh and keep the fastest.  Each
    finalist's exchanges resolve through the comm autotuners first (their
    verdicts land in ``comm/*`` wisdom as usual), so the measurement prices
    the decomposition with its best available exchange."""
    rng = np.random.default_rng(0)
    if kind == "r2c":
        probe = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    else:
        probe = tuple(jnp.asarray(
            rng.standard_normal(shape).astype(np.float32)) for _ in range(2))
    best, best_t = None, float("inf")
    # finalists = the roofline's top 3, mirroring how Planner._measure caps
    # its candidate sweep — timing the model's last-ranked candidates buys
    # nothing and each one costs a compile + a comm chunk-sweep
    for est, cand in scored[:3]:
        nd = build(cand.decomp, cand.mesh_axes, est)

        def run(*args):
            a = args[0] if nd.kind == "r2c" else args
            return execute_nd(nd, a, mesh=mesh, planner=planner)

        args = (probe,) if kind == "r2c" else probe
        dt = _time_callable(jax.jit(run), args, reps=3)
        if dt != float("inf"):
            PLAN_ND_STATS["timed"] += 1
        if dt < best_t:
            best, best_t = nd, dt
    if best is None:
        raise RuntimeError(f"no supported decomposition of {shape} ({kind})")
    return dataclasses.replace(best, measured_cost=best_t)


# ---------------------------------------------------------------------------
# execution (dispatch to the shared executors in repro.core.dfft)
# ---------------------------------------------------------------------------


@functools.partial(jax.profiler.annotate_function, name="repro.execute")
def execute_nd(plan: NdPlan, x, mesh=None, planner: Optional[Planner] = None,
               chunks: int = 4, **layout_opts):
    """Run ``plan`` forward.  ``x``: real array for r2c, (re, im) pair for
    c2c (leading batch dims welcome).  Returns the PADDED spectrum pair —
    crop with ``plan.crop`` / ``plan.crop_pair`` for the exact transform.
    The output layout follows ``plan.output_layout`` (transposed slab
    plans skip the restore exchange); ``layout_opts`` are the LEGACY
    2D-slab-only flags ``keep_transposed``/``permuted_cols`` the
    deprecated shims still pass.
    """
    planner = planner or Planner()
    if plan.decomp == "local":
        return _execute_local(plan, x, planner)
    assert mesh is not None, "distributed plans need the live mesh"
    if plan.decomp == "slab":
        return dfft.execute_slab(plan, x, mesh, planner, chunks=chunks,
                                 **layout_opts)
    if plan.decomp == "factor1d":
        return dfft.execute_factor1d(plan, x, mesh, planner, chunks=chunks)
    return dfft.execute_pencil(plan, x, mesh, planner, chunks=chunks)


@functools.partial(jax.profiler.annotate_function, name="repro.execute")
def execute_nd_inverse(plan: NdPlan, c: Complex, mesh=None,
                       planner: Optional[Planner] = None, chunks: int = 4,
                       **layout_opts):
    """Run ``plan`` backward from the PADDED spectrum pair.  Returns a pair
    for c2c, a real array for r2c; sharded axes keep their divisibility
    padding (crop trailing axes to ``plan.shape``)."""
    planner = planner or Planner()
    if plan.decomp == "local":
        return _execute_local_inverse(plan, c, planner)
    assert mesh is not None, "distributed plans need the live mesh"
    if plan.decomp == "slab":
        return dfft.execute_slab_inverse(plan, c, mesh, planner,
                                         chunks=chunks, **layout_opts)
    if plan.decomp == "factor1d":
        return dfft.execute_factor1d_inverse(plan, c, mesh, planner,
                                             chunks=chunks)
    return dfft.execute_pencil_inverse(plan, c, mesh, planner, chunks=chunks)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _execute_local(plan: NdPlan, x, planner: Planner):
    """Single-device N-D transform: planned 1D stages, axis by axis,
    compiled as one program per plan and shape.  Called eagerly, the
    stages ran op by op, every intermediate in device memory: a 2^14 x
    2^14 r2c peaked at 15 GiB of a v5e's 16 GiB."""
    d = len(plan.shape)
    if plan.kind == "r2c":
        y = dfft.rows_rfft(planner, x, plan.shape[-1])
    else:
        y = execute(planner.plan(plan.shape[-1], kind="c2c"), x)
    for k in range(d - 2, -1, -1):
        y = dfft._fft_axis(planner.plan(plan.shape[k], kind="c2c"), y,
                           y[0].ndim - d + k)
    return y


@functools.partial(jax.jit, static_argnums=(0, 2))
def _execute_local_inverse(plan: NdPlan, c: Complex, planner: Planner):
    d = len(plan.shape)
    y = c
    for k in range(d - 1):
        y = dfft._fft_axis(planner.plan(plan.shape[k], kind="c2c"), y,
                           y[0].ndim - d + k, inverse=True)
    if plan.kind == "r2c":
        return dfft.rows_irfft(planner, y, plan.shape[-1])
    return execute_inverse(planner.plan(plan.shape[-1], kind="c2c"), y)


# ---------------------------------------------------------------------------
# the fftn family (numpy-shaped conveniences over plan_nd)
# ---------------------------------------------------------------------------


def _as_pair(x) -> Complex:
    if isinstance(x, (tuple, list)):
        return tuple(x)
    if jnp.iscomplexobj(x):
        return algo.to_pair(x)
    x = jnp.asarray(x)
    return x.astype(jnp.float32), jnp.zeros_like(x, jnp.float32)


def _transform_ndim(x, ndim, plan) -> int:
    if plan is not None:
        return len(plan.shape)
    arr = x[0] if isinstance(x, (tuple, list)) else x
    return arr.ndim if ndim is None else ndim


def _pad_spectrum(c: Complex, plan: NdPlan) -> Complex:
    """Zero-pad an exact spectrum pair back to the executor's padded layout
    (the padded bands are zero by construction, so this is lossless)."""
    d = len(plan.shape)
    for ax_off, (true, padded) in enumerate(zip(plan.spectrum_shape,
                                                plan.padded_spectrum_shape)):
        if true != padded:
            c = dfft._pad_axis(c, c[0].ndim - d + ax_off, padded)
    return c


def _crop_spatial(y, plan: NdPlan, pair: bool):
    """Crop the inverse executors' output back to ``plan.shape``."""
    d = len(plan.shape)
    for j, n in enumerate(plan.shape):
        if pair:
            y = dfft._crop_axis(y, y[0].ndim - d + j, n)
        else:
            y = dfft.crop_to(y, y.ndim - d + j, n)
    return y


@functools.partial(jax.profiler.annotate_function, name="repro.fftn")
def fftn(x, mesh=None, axes=None, planner: Optional[Planner] = None,
         comm="auto", mode: str = "estimate", ndim: Optional[int] = None,
         plan: Optional[NdPlan] = None, chunks: int = 4,
         output_layout: str = "natural") -> Complex:
    """N-D c2c FFT matching ``numpy.fft.fftn`` over the trailing ``ndim``
    axes (default: all).  ``x``: complex array or (re, im) pair; leading
    axes beyond ``ndim`` are batch.  Decomposition, mesh-axis assignment,
    exchange backends and output layout come from :func:`plan_nd` (or pass
    ``plan=``).  Returns an (re, im) pair with the exact numpy shape (with
    ``output_layout="transposed"`` the values are identical but the
    spectrum stays sharded over the last transform axis)."""
    if isinstance(mesh, int):   # legacy repro.core.fftn(pair, ndim) call
        import warnings
        warnings.warn(
            "fftn(x, ndim) is the old repro.core.algo.fftn signature; "
            "repro.core.fftn is now the planned front-end — pass ndim=... "
            "(or call repro.core.algo.fftn directly)",
            DeprecationWarning, stacklevel=3)   # past the host span
        mesh, ndim = None, mesh
    c = _as_pair(x)
    d = _transform_ndim(c, ndim, plan)
    plan = plan or plan_nd(c[0].shape[c[0].ndim - d:], "c2c", mesh=mesh,
                           axes=axes, mode=mode, comm=comm, planner=planner,
                           output_layout=output_layout)
    out = execute_nd(plan, c, mesh=mesh, planner=planner, chunks=chunks)
    return plan.crop_pair(out)


@functools.partial(jax.profiler.annotate_function, name="repro.ifftn")
def ifftn(x, mesh=None, axes=None, planner: Optional[Planner] = None,
          comm="auto", mode: str = "estimate", ndim: Optional[int] = None,
          plan: Optional[NdPlan] = None, chunks: int = 4,
          output_layout: str = "natural") -> Complex:
    """Inverse of :func:`fftn` (matches ``numpy.fft.ifftn``).  Accepts the
    exact spectrum (array or pair); re-pads internally for the collective
    layout.  With a transposed plan (``plan.output_layout="transposed"``
    or ``output_layout=`` here) the transposed spectrum inverts without a
    re-shuffle: the slab inverse skips its first exchange."""
    c = _as_pair(x)
    d = _transform_ndim(c, ndim, plan)
    plan = plan or plan_nd(c[0].shape[c[0].ndim - d:], "c2c", mesh=mesh,
                           axes=axes, mode=mode, comm=comm, planner=planner,
                           output_layout=output_layout)
    c = _pad_spectrum(c, plan)
    y = execute_nd_inverse(plan, c, mesh=mesh, planner=planner,
                           chunks=chunks)
    return _crop_spatial(y, plan, pair=True)


@functools.partial(jax.profiler.annotate_function, name="repro.rfftn")
def rfftn(x: jax.Array, mesh=None, axes=None,
          planner: Optional[Planner] = None, comm="auto",
          mode: str = "estimate", ndim: Optional[int] = None,
          plan: Optional[NdPlan] = None, chunks: int = 4,
          output_layout: str = "natural") -> Complex:
    """N-D r2c FFT matching ``numpy.fft.rfftn`` over the trailing ``ndim``
    axes of a real array (odd last-axis lengths included).  Returns the
    exact half-spectrum pair."""
    x = jnp.asarray(x)
    d = _transform_ndim(x, ndim, plan)
    plan = plan or plan_nd(x.shape[x.ndim - d:], "r2c", mesh=mesh,
                           axes=axes, mode=mode, comm=comm, planner=planner,
                           output_layout=output_layout)
    out = execute_nd(plan, x.astype(jnp.float32), mesh=mesh, planner=planner,
                     chunks=chunks)
    return plan.crop_pair(out)


@functools.partial(jax.profiler.annotate_function, name="repro.irfftn")
def irfftn(x, shape: Optional[Sequence[int]] = None, mesh=None, axes=None,
           planner: Optional[Planner] = None, comm="auto",
           mode: str = "estimate", plan: Optional[NdPlan] = None,
           chunks: int = 4, output_layout: str = "natural") -> jax.Array:
    """Inverse of :func:`rfftn` back to a real array (matches
    ``numpy.fft.irfftn``).  ``shape`` is the spatial transform shape; when
    omitted the last axis is assumed even (``2 * (mh - 1)``), exactly
    numpy's convention."""
    c = _as_pair(x)
    if plan is None:
        if shape is None:       # no batch dims: every input axis transforms
            shape = c[0].shape[:-1] + (2 * (c[0].shape[-1] - 1),)
        shape = tuple(int(n) for n in shape)
        plan = plan_nd(shape, "r2c", mesh=mesh, axes=axes, mode=mode,
                       comm=comm, planner=planner,
                       output_layout=output_layout)
    c = _pad_spectrum(c, plan)
    y = execute_nd_inverse(plan, c, mesh=mesh, planner=planner,
                           chunks=chunks)
    return _crop_spatial(y, plan, pair=False)
