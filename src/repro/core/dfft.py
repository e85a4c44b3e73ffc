"""Distributed multidimensional FFT on a device mesh (the paper's §3.2/§5.3).

Slab decomposition over one mesh axis, pencil decomposition over an
ordered chain of 2..ndim-1 mesh axes, and the factor-split distributed 1D
transform.  All data movement is EXPLICIT collectives inside ``shard_map``
— the paper's
central design decision ("relying on the implicit communication HPX allows
with AGAS does not make sense; instead we use the HPX equivalents of the MPI
collective operations").

This module holds the *executors*: given an :class:`repro.core.api.NdPlan`
(the pure-data recipe produced by :func:`repro.core.api.plan_nd`), the
``execute_slab`` / ``execute_pencil`` pairs run the decomposed transform on
a live mesh.  The planning — which decomposition, which mesh-axis
assignment, which exchange backend — lives in :mod:`repro.core.api`; the
exchange strategies themselves live in :mod:`repro.core.comm`.

One shared pad-and-crop layer serves every path:

* r2c half spectra are zero-padded to the collective-divisible width
  (``padded_half``), the convention the 2D slab path always had;
* **mixed-radix mesh shapes** — transform axes not divisible by their
  communicator — are handled by zero-padding the axis up to the next
  multiple, cropping to the true length just before the axis is transformed,
  and re-padding after, so the padded band stays exactly zero through every
  exchange and is cropped once at the end (``NdPlan.crop``);
* **leading batch dims** ride through every executor via the batched
  shard_map spec helper (:func:`batched_spec`) shared
  with :func:`repro.core.fftconv.fft_conv_seq_sharded`.

Algorithm (slab, 2D r2c, row-major N x M, P devices; paper's five steps):

  1. local r2c FFTs along contiguous rows          (N/P, Mh)
  2. COMMUNICATE: all_to_all column slabs          -> (N, Mh/P)  [rearrange
     = split into N_locs parts + concat, fused into the tiled collective]
  3. transpose AFTER communication (paper's choice) -> (Mh/P, N)
  4. local c2c FFTs along (now contiguous) columns
  5. COMMUNICATE back + rearrange to original layout (N/P, Mh)

Pencil decomposition (P3DFFT-style, k mesh axes) has full parity with
slab, and the ``factor1d`` executor distributes a single long axis via the
``fft_conv`` factor split (three 1/P exchanges instead of one full
gather).

The historical shape-specific entry points — ``fft2_slab``/``ifft2_slab``
and the four ``*_pencil`` functions — remain as thin DEPRECATED shims that
build an ``NdPlan`` internally and call the shared executors; new code
should go through :func:`repro.core.api.plan_nd` and the ``fftn`` family.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import algo
from .comm import (COMM_BACKENDS, CommBackend, CommSpec, get_backend,
                   measure_comm_pencil, measure_comm_slab, pad_to,
                   padded_half, plan_comm, plan_comm_pencil,
                   resolve_axis_backends)
from .plan import Plan, Planner, execute, execute_inverse

Complex = algo.Complex

__all__ = [
    "COMM_BACKENDS", "padded_half", "pad_to", "plan_comm", "plan_comm_pencil",
    "measure_comm_slab", "measure_comm_pencil",
    "rows_rfft", "rows_irfft", "hermitian_extend_last",
    "execute_slab", "execute_slab_inverse",
    "execute_pencil", "execute_pencil_inverse",
    "execute_factor1d", "execute_factor1d_inverse",
    "fft2_slab", "ifft2_slab",
    "fft3_pencil", "ifft3_pencil", "rfft3_pencil", "irfft3_pencil",
    "distribute", "collect",
]


# ---------------------------------------------------------------------------
# shared pad-and-crop layer (every decomposition path goes through these)
# ---------------------------------------------------------------------------


def _pad_axis(c: Complex, axis: int, target: int) -> Complex:
    """Zero-pad one axis of an (re, im) pair up to ``target`` entries."""
    pad = target - c[0].shape[axis]
    if pad <= 0:
        return c
    widths = [(0, 0)] * c[0].ndim
    widths[axis] = (0, pad)
    return jnp.pad(c[0], widths), jnp.pad(c[1], widths)


def crop_to(a: jax.Array, axis: int, n: int) -> jax.Array:
    """Crop one axis of an array to its first ``n`` entries.

    A mesh with ``Explicit`` axes (``jax.make_mesh``'s default) keeps every
    sharded axis evenly divided, so an axis whose mesh axes do not divide
    ``n`` is gathered over them first; on ``Auto`` meshes and inside
    ``shard_map`` blocks the type carries no such axis and this is a plain
    slice."""
    axis %= a.ndim
    if a.shape[axis] == n:
        return a
    sharding = jax.typeof(a).sharding
    spec = tuple(sharding.spec) + (None,) * (a.ndim - len(sharding.spec))
    names = spec[axis]
    if names is not None:
        names = names if isinstance(names, tuple) else (names,)
        if n % math.prod(sharding.mesh.shape[m] for m in names):
            spec = spec[:axis] + (None,) + spec[axis + 1:]
            a = jax.sharding.reshard(
                a, jax.sharding.NamedSharding(sharding.mesh, P(*spec)))
    return jax.lax.slice_in_dim(a, 0, n, axis=axis)


def _crop_axis(c: Complex, axis: int, n: int) -> Complex:
    """Crop one axis of a pair back to its true length ``n``."""
    return crop_to(c[0], axis, n), crop_to(c[1], axis, n)


def _fft_axis(plan: Plan, c: Complex, axis: int, inverse: bool = False
              ) -> Complex:
    """c2c transform along one (fully local) axis of a pair."""
    if axis == c[0].ndim - 1 or axis == -1:
        return execute_inverse(plan, c) if inverse else execute(plan, c)
    ct = (jnp.moveaxis(c[0], axis, -1), jnp.moveaxis(c[1], axis, -1))
    zt = execute_inverse(plan, ct) if inverse else execute(plan, ct)
    return jnp.moveaxis(zt[0], -1, axis), jnp.moveaxis(zt[1], -1, axis)


def hermitian_extend_last(c: Complex, n: int) -> Complex:
    """Rebuild the full length-``n`` spectrum from the half spectrum of a
    real signal along the last axis: ``F[k] = conj(F[n-k])`` for k > n//2.
    Valid whenever every other axis is already in its real/spatial form."""
    mh = n // 2 + 1
    idx = np.arange(n - mh, 0, -1)          # tail k = mh..n-1  <-  n-k
    return (jnp.concatenate([c[0], c[0][..., idx]], -1),
            jnp.concatenate([c[1], -c[1][..., idx]], -1))


def rows_rfft(planner: Planner, x: jax.Array, n: int) -> Complex:
    """r2c FFT along the last axis for ANY length: even lengths use the
    packed real codelet path, odd lengths fall back to a c2c transform of
    the real signal cropped to the half spectrum."""
    if n % 2 == 0:
        return execute(planner.plan(n, kind="r2c"), x)
    with jax.named_scope(algo.R2C_SCOPE):
        z = (x, jnp.zeros_like(x))
    re, im = execute(planner.plan(n, kind="c2c"), z)
    with jax.named_scope(algo.R2C_SCOPE):
        return re[..., : n // 2 + 1], im[..., : n // 2 + 1]


def rows_irfft(planner: Planner, c: Complex, n: int) -> jax.Array:
    """c2r inverse of :func:`rows_rfft` (input ``(..., n//2+1)``)."""
    if n % 2 == 0:
        return execute(planner.plan(n, kind="c2r"), c)
    with jax.named_scope(algo.R2C_SCOPE):
        full = hermitian_extend_last(c, n)
    return execute_inverse(planner.plan(n, kind="c2c"), full)[0]


def _warm_rows_plan(planner: Planner, n: int, inverse: bool = False) -> None:
    """Pre-plan the 1D stage :func:`rows_rfft` / :func:`rows_irfft` will
    request, outside the ``shard_map`` body — its trace-time lookups then
    hit the planner's wisdom cache without triggering a wisdom write."""
    if n % 2 == 0:
        planner.plan(n, kind="c2r" if inverse else "r2c")
    else:
        planner.plan(n, kind="c2c")


def _compiled(*static_names: str):
    """Run an executor as one compiled program per (plan, mesh, planner,
    options, input shapes).

    Called eagerly, ``shard_map`` runs its body primitive by primitive and
    compiles each primitive anew on every call; the constants made in the
    body (DFT matrices, twiddles) are concrete single-device arrays, which
    a mesh with ``Explicit`` axes refuses.  Under ``jit`` the body is
    traced once and compiled once, and a call from inside a caller's own
    ``jit`` is inlined there."""
    return functools.partial(jax.jit, static_argnums=(0, 2, 3),
                             static_argnames=("chunks",) + static_names)


def batched_spec(spec, batch_ndim: int) -> P:
    """Prepend ``batch_ndim`` replicated (None) dims to a PartitionSpec.

    The one batching convention for every shard_map'd transform: leading
    batch axes are never sharded by the FFT layer, so a spec written for the
    unbatched layout extends to any batch rank.  Shared by the executors
    here and :func:`repro.core.fftconv.fft_conv_seq_sharded`."""
    if batch_ndim <= 0:
        return spec
    return P(*((None,) * batch_ndim + tuple(spec)))


def _slab_backend(nd, chunks: int) -> CommBackend:
    return get_backend(nd.comm[0] if nd.comm else "collective", chunks=chunks)


def _pencil_backends(nd, chunks: int) -> Tuple[CommBackend, ...]:
    return resolve_axis_backends(nd.comm, nd.mesh_axes, chunks=chunks)


def _pencil_spectrum_spec(axs, k: int, d: int) -> P:
    """The pencil SPECTRUM sharding (forward output == inverse input):
    transform axis j+1 over mesh axis j for j < k-1, the last axis over
    mesh axis k-1, everything else replicated.  One definition so the two
    executors can never desynchronize."""
    spec = [None] * d
    for j in range(k - 1):
        spec[j + 1] = axs[j]
    spec[d - 1] = axs[k - 1]
    return P(*spec)


# ---------------------------------------------------------------------------
# slab executor (1 mesh axis, ndim >= 2, leading batch dims, mixed radix)
# ---------------------------------------------------------------------------
#
# Layout (forward, transform shape (n0, ..., nlast), P devices over `ax`):
#
#   input   (b..., n0p/P, ..., nlast)   last-axis FFT (r2c or c2c) local,
#                                       then every middle axis, then pad the
#                                       spectrum's last axis to lp
#   xchg    split last, concat first -> (b..., n0p, ..., lp/P)
#   ax0 FFT crop n0p -> n0, transform, re-pad to n0p
#   xchg    split first, concat last -> (b..., n0p/P, ..., lp)
#
# n0p = pad_to(n0, P); lp = padded_half(nlast, P) for r2c, pad_to(nlast, P)
# for c2c.  The padded bands are exactly zero throughout (zero columns stay
# zero under FFTs along other axes), so `NdPlan.crop` recovers the exact
# spectrum.


@_compiled("keep_transposed", "permuted_cols")
def execute_slab(nd, x, mesh: jax.sharding.Mesh, planner: Planner, *,
                 chunks: int = 4, keep_transposed: bool = False,
                 permuted_cols: bool = False):
    """Forward slab transform of an :class:`~repro.core.api.NdPlan`.

    ``x``: real array for ``kind="r2c"``, (re, im) pair for ``"c2c"``, with
    any number of leading batch dims.  Returns the PADDED spectrum pair
    (global trailing shape ``nd.padded_spectrum_shape``), sharded over the
    first transform axis — crop with ``nd.crop`` for the exact transform.

    A plan with ``output_layout="transposed"`` skips the second exchange
    entirely: the values stay at their natural (numpy) index positions but
    the output is sharded over the LAST axis instead of the first (any
    ndim, mixed radix included) — ``execute_slab_inverse`` consumes that
    layout with a single exchange, so a transposed round trip saves two.

    ``keep_transposed`` / ``permuted_cols`` are the historical 2D-only
    layout flags of ``fft2_slab`` (folded transposed layout / skip the
    column digit transpose); new code plans the layout instead.
    """
    d = len(nd.shape)
    assert nd.decomp == "slab" and len(nd.mesh_axes) == 1
    transposed_out = getattr(nd, "output_layout", "natural") == "transposed"
    if keep_transposed or permuted_cols:
        assert d == 2, "transposed/permuted layouts are 2D-only"
        assert not transposed_out, \
            "legacy keep_transposed flag on an already-transposed plan"
    ax, p = nd.mesh_axes[0], nd.mesh_shape[0]
    pair_in = nd.kind == "c2c"
    xr = x[0] if pair_in else x
    bnd = xr.ndim - d
    i0, il = bnd, bnd + d - 1
    n0, nlast = nd.shape[0], nd.shape[-1]
    n0p = pad_to(n0, p)
    lp = nd.padded_spectrum_shape[-1]
    backend = _slab_backend(nd, chunks)

    if keep_transposed and n0p != n0:
        raise ValueError("keep_transposed requires shape[0] divisible by "
                         "the mesh axis (mixed radix keeps both exchanges)")
    row_plan = planner.plan(nlast, kind="c2c") if pair_in else None
    if not pair_in:
        _warm_rows_plan(planner, nlast)
    mid_plans = [planner.plan(nd.shape[k], kind="c2c")
                 for k in range(1, d - 1)]
    col_plan = planner.plan(n0, kind="c2c", permuted=permuted_cols)

    if n0p != n0:                       # mixed radix: zero-pad sharded axis
        widths = [(0, 0)] * xr.ndim
        widths[i0] = (0, n0p - n0)
        x = ((jnp.pad(x[0], widths), jnp.pad(x[1], widths)) if pair_in
             else jnp.pad(x, widths))

    def local(*args):
        if pair_in:
            y = execute(row_plan, args)                     # c2c last axis
            y = _pad_axis(y, il, lp)
        else:
            y = rows_rfft(planner, args[0], nlast)          # r2c last axis
            y = _pad_axis(y, il, lp)
        for k, mp in enumerate(mid_plans):                  # middle axes
            y = _fft_axis(mp, y, i0 + 1 + k)
        y = backend.exchange(y, ax, split=il, concat=i0, p=p)
        y = _crop_axis(y, i0, n0)                           # mixed radix
        y = _fft_axis(col_plan, y, i0)                      # first axis
        if keep_transposed:     # 2D: hand back the transposed local layout
            return jnp.swapaxes(y[0], i0, il), jnp.swapaxes(y[1], i0, il)
        y = _pad_axis(y, i0, n0p)
        if transposed_out:      # planned layout: skip the second exchange
            return y
        return backend.exchange(y, ax, split=i0, concat=il, p=p)

    spec_in = batched_spec(P(ax, *(None,) * (d - 1)), bnd)
    if keep_transposed:
        spec_out = batched_spec(P(None, ax), bnd)
    elif transposed_out:
        spec_out = batched_spec(P(*(None,) * (d - 1), ax), bnd)
    else:
        spec_out = batched_spec(P(ax, *(None,) * (d - 1)), bnd)
    in_specs = (spec_in, spec_in) if pair_in else (spec_in,)
    args = x if pair_in else (x,)
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=(spec_out, spec_out))(*args)


@_compiled("from_transposed", "permuted_cols")
def execute_slab_inverse(nd, c: Complex, mesh: jax.sharding.Mesh,
                         planner: Planner, *, chunks: int = 4,
                         from_transposed: bool = False,
                         permuted_cols: bool = False):
    """Inverse slab transform: consumes the PADDED spectrum pair produced by
    :func:`execute_slab` (zero padded bands) and returns the spatial array —
    real for ``kind="r2c"``, a pair for ``"c2c"`` — with the first transform
    axis still padded to ``pad_to(n0, p)`` (crop with ``nd.shape[0]``).

    A plan with ``output_layout="transposed"`` consumes the last-axis-
    sharded layout :func:`execute_slab` produced for it and needs only ONE
    exchange; the legacy 2D ``from_transposed`` flag consumes the
    historical folded layout instead."""
    d = len(nd.shape)
    assert nd.decomp == "slab" and len(nd.mesh_axes) == 1
    transposed_in = getattr(nd, "output_layout", "natural") == "transposed"
    if from_transposed or permuted_cols:
        assert d == 2, "transposed/permuted layouts are 2D-only"
        assert not transposed_in, \
            "legacy from_transposed flag on an already-transposed plan"
    ax, p = nd.mesh_axes[0], nd.mesh_shape[0]
    bnd = c[0].ndim - d
    i0, il = bnd, bnd + d - 1
    n0, nlast = nd.shape[0], nd.shape[-1]
    n0p = pad_to(n0, p)
    lp = nd.padded_spectrum_shape[-1]
    ltrue = nd.spectrum_shape[-1]       # mh for r2c, nlast for c2c
    backend = _slab_backend(nd, chunks)
    col_plan = planner.plan(n0, kind="c2c", permuted=permuted_cols)
    mid_plans = [planner.plan(nd.shape[k], kind="c2c")
                 for k in range(1, d - 1)]
    row_plan = planner.plan(nlast, kind="c2c") if nd.kind == "c2c" else None
    if nd.kind == "r2c":
        _warm_rows_plan(planner, nlast, inverse=True)

    if from_transposed and n0p != n0:
        raise ValueError("from_transposed requires shape[0] divisible by "
                         "the mesh axis")

    def local(cr: jax.Array, ci: jax.Array):
        z = (cr, ci)
        if from_transposed:
            # first-axis inverse: in the folded layout the axis is last
            z = execute_inverse(col_plan, z)                # (lp/p, n0)
            z = (jnp.swapaxes(z[0], i0, il), jnp.swapaxes(z[1], i0, il))
        elif transposed_in:
            # planned transposed input is already in the post-exchange-1
            # layout (first axis full, last sharded): no exchange needed
            z = _crop_axis(z, i0, n0)
            z = _fft_axis(col_plan, z, i0, inverse=True)
            z = _pad_axis(z, i0, n0p)
        else:
            z = backend.exchange(z, ax, split=il, concat=i0, p=p)
            z = _crop_axis(z, i0, n0)
            z = _fft_axis(col_plan, z, i0, inverse=True)
            z = _pad_axis(z, i0, n0p)
        z = backend.exchange(z, ax, split=i0, concat=il, p=p)
        z = _crop_axis(z, il, ltrue)                        # drop padding
        for k, mp in reversed(list(enumerate(mid_plans))):  # middle axes
            z = _fft_axis(mp, z, i0 + 1 + k, inverse=True)
        if nd.kind == "c2c":
            return execute_inverse(row_plan, z)
        return rows_irfft(planner, z, nlast)                # c2r last axis

    spec_std = batched_spec(P(ax, *(None,) * (d - 1)), bnd)
    if from_transposed:
        spec_in = batched_spec(P(None, ax), bnd)
    elif transposed_in:
        spec_in = batched_spec(P(*(None,) * (d - 1), ax), bnd)
    else:
        spec_in = spec_std
    out_specs = spec_std if nd.kind == "r2c" else (spec_std, spec_std)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec_in, spec_in),
                         out_specs=out_specs)(c[0], c[1])


# ---------------------------------------------------------------------------
# pencil executor (P3DFFT-style, k mesh axes, ndim >= k+1, batch dims,
# mixed radix)
# ---------------------------------------------------------------------------
#
# Layout convention (forward direction), mesh axes (a0..a_{k-1}) of sizes
# (p0..p_{k-1}) sharding the FIRST k transform axes; 3D/2-axis shown:
#
#   input   (b..., Xp/p0, Yp/p1, Z)    Z-FFT local, pad Z -> Zp (or zh_pad)
#   xchg 1  over a1 (row communicator):   split Z, concat Y
#           (b..., Xp/p0, Yp, Zp/p1)   crop Y, Y-FFT local, re-pad
#   xchg 2  over a0 (column communicator): split Y, concat X
#           (b..., Xp,  Yp/p0, Zp/p1)  crop X, X-FFT local, re-pad
#
# For k > 2 (ndim > 3) the chain continues axis by axis: one exchange per
# adjacent pair of sharded axes, each inside its own communicator, the
# just-transformed axis donating its locality to the next.  Axis paddings:
# axis 0 -> pad_to(., p0); axis j (0 < j < k) -> pad_to(., lcm(p_{j-1},
# p_j)) (input-sharded over p_j, exchange-split over p_{j-1}); non-sharded
# middle axes unpadded; last axis pad_to(., p_{k-1}) (padded_half for r2c).
# Communication stays within row/column(/plane) communicators — the P3DFFT
# advantage the paper cites over slab decomposition.  The inverses retrace
# the same exchanges backwards, so each mesh axis keeps its chosen comm
# backend both ways.


@_compiled()
def execute_pencil(nd, x, mesh: jax.sharding.Mesh, planner: Planner, *,
                   chunks: int = 4):
    """Forward pencil transform of an :class:`~repro.core.api.NdPlan`
    (``kind="c2c"``: (re, im) pair in, ``"r2c"``: real array in; any number
    of leading batch dims).  The input's first ``k = len(nd.mesh_axes)``
    transform axes are sharded over the mesh axes in order.  Returns the
    PADDED spectrum pair, global trailing shape ``nd.padded_spectrum_shape``
    sharded ``(None, a0, .., a_{k-2})`` on the leading axes and ``a_{k-1}``
    on the last — crop with ``nd.crop`` for the exact transform."""
    d = len(nd.shape)
    k = len(nd.mesh_axes)
    assert nd.decomp == "pencil" and 2 <= k <= d - 1, (nd.decomp, k, d)
    axs, ps = nd.mesh_axes, nd.mesh_shape
    pair_in = nd.kind == "c2c"
    xr = x[0] if pair_in else x
    bnd = xr.ndim - d
    il = bnd + d - 1
    padded = nd.padded_spectrum_shape
    backends = _pencil_backends(nd, chunks)
    plans = [planner.plan(nd.shape[j], kind="c2c") for j in range(d - 1)]
    plan_last = planner.plan(nd.shape[-1], kind="c2c") if pair_in else None
    if not pair_in:
        _warm_rows_plan(planner, nd.shape[-1])

    pads = [(0, 0)] * xr.ndim
    for j in range(k):                      # mixed radix: pad sharded axes
        pads[bnd + j] = (0, padded[j] - nd.shape[j])
    if any(p != (0, 0) for p in pads):
        x = ((jnp.pad(x[0], pads), jnp.pad(x[1], pads)) if pair_in
             else jnp.pad(x, pads))

    def local(*args):
        if pair_in:
            z = execute(plan_last, args)                    # FFT last axis
        else:
            z = rows_rfft(planner, args[0], nd.shape[-1])   # r2c last axis
        z = _pad_axis(z, il, padded[-1])
        for j in range(k, d - 1):           # unsharded middle axes: local
            z = _fft_axis(plans[j], z, bnd + j)
        donor = il
        for j in range(k - 1, -1, -1):      # the exchange chain
            z = backends[j].exchange(z, axs[j], split=donor, concat=bnd + j,
                                     p=ps[j])
            z = _crop_axis(z, bnd + j, nd.shape[j])
            z = _fft_axis(plans[j], z, bnd + j)             # FFT along j
            z = _pad_axis(z, bnd + j, padded[j])
            donor = bnd + j
        return z

    spec_in = batched_spec(P(*axs, *(None,) * (d - k)), bnd)
    spec_out = batched_spec(_pencil_spectrum_spec(axs, k, d), bnd)
    in_specs = (spec_in, spec_in) if pair_in else (spec_in,)
    args = x if pair_in else (x,)
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=(spec_out, spec_out))(*args)


@_compiled()
def execute_pencil_inverse(nd, c: Complex, mesh: jax.sharding.Mesh,
                           planner: Planner, *, chunks: int = 4):
    """Inverse pencil transform: PADDED spectrum pair in (zero padded
    bands), spatial data out — a pair for ``kind="c2c"``, a real array for
    ``"r2c"`` — with the sharded axes still padded to their communicator
    multiples (crop with ``nd.shape``)."""
    d = len(nd.shape)
    k = len(nd.mesh_axes)
    assert nd.decomp == "pencil" and 2 <= k <= d - 1, (nd.decomp, k, d)
    axs, ps = nd.mesh_axes, nd.mesh_shape
    bnd = c[0].ndim - d
    il = bnd + d - 1
    padded = nd.padded_spectrum_shape
    ltrue = nd.spectrum_shape[-1]           # half width for r2c
    backends = _pencil_backends(nd, chunks)
    plans = [planner.plan(nd.shape[j], kind="c2c") for j in range(d - 1)]
    plan_last = planner.plan(nd.shape[-1], kind="c2c") \
        if nd.kind == "c2c" else None
    if nd.kind == "r2c":
        _warm_rows_plan(planner, nd.shape[-1], inverse=True)

    def local(cr: jax.Array, ci: jax.Array):
        z = (cr, ci)
        for j in range(k):                  # retrace the chain backwards
            z = _crop_axis(z, bnd + j, nd.shape[j])
            z = _fft_axis(plans[j], z, bnd + j, inverse=True)
            z = _pad_axis(z, bnd + j, padded[j])
            donor = bnd + j + 1 if j < k - 1 else il
            z = backends[j].exchange(z, axs[j], split=bnd + j, concat=donor,
                                     p=ps[j])
        z = _crop_axis(z, il, ltrue)                        # drop padding
        for j in range(d - 2, k - 1, -1):   # unsharded middle axes
            z = _fft_axis(plans[j], z, bnd + j, inverse=True)
        if nd.kind == "c2c":
            return execute_inverse(plan_last, z)            # inverse last
        return rows_irfft(planner, z, nd.shape[-1])         # c2r last axis

    spec_in = batched_spec(_pencil_spectrum_spec(axs, k, d), bnd)
    spec_out = batched_spec(P(*axs, *(None,) * (d - k)), bnd)
    out_specs = spec_out if nd.kind == "r2c" else (spec_out, spec_out)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec_in, spec_in),
                         out_specs=out_specs)(c[0], c[1])


# ---------------------------------------------------------------------------
# factor1d executor (distributed 1D c2c via the fft_conv factor split)
# ---------------------------------------------------------------------------
#
# The length-N signal is viewed as an (n1, n2) row-major matrix sharded
# over n1 (nd.factors = (n1, n2), both divisible by p — see
# repro.core.fftconv.factor_split).  The paper's own 2D framing of the
# distributed 1D problem:
#
#   stage A: all_to_all -> columns local; DFT along n1; twiddle T[k1, n2]
#   stage B: all_to_all -> rows local;    DFT along n2   => C[k1, k2]
#   unpermute: all_to_all + local transpose => X[n1*k2 + k1], row-sharded
#
# Three exchanges each way.  fft_conv_seq_sharded keeps its own copy of
# stages A/B *without* the unpermute (pointwise products commute with the
# digit permutation, so the convolution skips both transposes); the planned
# front-end needs numpy-exact natural order, hence the third exchange.


def _factor1d_twiddle_block(n1: int, n2: int, axis_name: str, p: int,
                            sign: int, chunk_axis: int) -> Complex:
    """This device's block of ``T[k1, j2] = exp(sign*2*pi*i*k1*j2/(n1*n2))``,
    computed in-graph from ``axis_index`` (O(N/p) per device) rather than
    sliced out of a full O(N) host constant — at the large N where the
    planner picks factor1d over gather-local, a replicated full twiddle
    would cost as much memory as the gather the decomposition avoids.
    ``chunk_axis=1``: all k1, this device's j2 columns (forward);
    ``chunk_axis=0``: this device's k1 rows, all j2 (inverse)."""
    me = jax.lax.axis_index(axis_name)
    if chunk_axis == 1:
        w = n2 // p
        k1 = jax.lax.iota(jnp.float32, n1)[:, None]
        j2 = (me * w + jax.lax.iota(jnp.int32, w)).astype(jnp.float32)[None]
    else:
        w = n1 // p
        k1 = (me * w + jax.lax.iota(jnp.int32, w)) \
            .astype(jnp.float32)[:, None]
        j2 = jax.lax.iota(jnp.float32, n2)[None, :]
    # k1*j2 < N stays exactly representable in f32 for any practical N
    ang = (sign * 2.0 * np.pi / (n1 * n2)) * (k1 * j2)
    return jnp.cos(ang), jnp.sin(ang)


@_compiled()
def execute_factor1d(nd, x, mesh: jax.sharding.Mesh, planner: Planner, *,
                     chunks: int = 4) -> Complex:
    """Forward distributed 1D c2c transform of an
    :class:`~repro.core.api.NdPlan` with ``decomp="factor1d"`` ((re, im)
    pair in, sharded over the transform axis; leading batch dims ride
    through).  Returns the natural-order spectrum pair, still sharded over
    the mesh axis."""
    assert nd.decomp == "factor1d" and len(nd.mesh_axes) == 1
    assert nd.kind == "c2c", "factor1d is c2c-only (r2c 1D stays local)"
    ax, p = nd.mesh_axes[0], nd.mesh_shape[0]
    n1, n2 = nd.factors
    assert n1 * n2 == nd.shape[0] and n1 % p == 0 and n2 % p == 0, nd
    bnd = x[0].ndim - 1
    backend = _slab_backend(nd, chunks)
    plan1 = planner.plan(n1, kind="c2c")
    plan2 = planner.plan(n2, kind="c2c")

    def local(xr: jax.Array, xi: jax.Array):
        shape = xr.shape[:-1] + (n1 // p, n2)
        z = (xr.reshape(shape), xi.reshape(shape))
        i1, i2 = z[0].ndim - 2, z[0].ndim - 1
        # stage A: columns local
        z = backend.exchange(z, ax, split=i2, concat=i1, p=p)  # (n1, n2/p)
        z = _fft_axis(plan1, z, i1)                         # DFT along n1
        z = algo.cmul(z, _factor1d_twiddle_block(n1, n2, ax, p, -1,
                                                 chunk_axis=1))
        # stage B: rows local
        z = backend.exchange(z, ax, split=i1, concat=i2, p=p)  # (n1/p, n2)
        z = _fft_axis(plan2, z, i2)                         # DFT along n2
        # unpermute C[k1, k2] -> X[n1*k2 + k1] (natural order, row-sharded)
        z = backend.exchange(z, ax, split=i2, concat=i1, p=p)  # (n1, n2/p)
        z = (jnp.swapaxes(z[0], i1, i2), jnp.swapaxes(z[1], i1, i2))
        flat = z[0].shape[:-2] + (n1 * n2 // p,)
        return z[0].reshape(flat), z[1].reshape(flat)

    spec = batched_spec(P(ax), bnd)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec))(x[0], x[1])


@_compiled()
def execute_factor1d_inverse(nd, c: Complex, mesh: jax.sharding.Mesh,
                             planner: Planner, *,
                             chunks: int = 4) -> Complex:
    """Inverse of :func:`execute_factor1d`: natural-order spectrum pair in,
    spatial pair out (both sharded over the mesh axis)."""
    assert nd.decomp == "factor1d" and len(nd.mesh_axes) == 1
    ax, p = nd.mesh_axes[0], nd.mesh_shape[0]
    n1, n2 = nd.factors
    bnd = c[0].ndim - 1
    backend = _slab_backend(nd, chunks)
    plan1 = planner.plan(n1, kind="c2c")
    plan2 = planner.plan(n2, kind="c2c")

    def local(cr: jax.Array, ci: jax.Array):
        shape = cr.shape[:-1] + (n2 // p, n1)
        z = (cr.reshape(shape), ci.reshape(shape))
        i1, i2 = z[0].ndim - 2, z[0].ndim - 1
        # re-permute X[n1*k2 + k1] -> C[k1, k2] (rows local)
        z = (jnp.swapaxes(z[0], i1, i2), jnp.swapaxes(z[1], i1, i2))
        z = backend.exchange(z, ax, split=i1, concat=i2, p=p)  # (n1/p, n2)
        # inverse DFT along k2 (normalized: 1/n2)
        z = _fft_axis(plan2, z, i2, inverse=True)
        # conjugate twiddle T[k1-block, n2]
        z = algo.cmul(z, _factor1d_twiddle_block(n1, n2, ax, p, +1,
                                                 chunk_axis=0))
        # columns local; inverse DFT along k1 (normalized: 1/n1)
        z = backend.exchange(z, ax, split=i2, concat=i1, p=p)  # (n1, n2/p)
        z = _fft_axis(plan1, z, i1, inverse=True)
        # back to the row-sharded natural layout
        z = backend.exchange(z, ax, split=i1, concat=i2, p=p)  # (n1/p, n2)
        flat = z[0].shape[:-2] + (n1 * n2 // p,)
        return z[0].reshape(flat), z[1].reshape(flat)

    spec = batched_spec(P(ax), bnd)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec))(c[0], c[1])


# ---------------------------------------------------------------------------
# deprecated shape-specific shims (build an NdPlan, run the shared executor)
# ---------------------------------------------------------------------------

_DEPRECATED_EMITTED = set()


def _warn_deprecated(name: str) -> None:
    """One DeprecationWarning per entry point per process."""
    if name in _DEPRECATED_EMITTED:
        return
    _DEPRECATED_EMITTED.add(name)
    warnings.warn(
        f"repro.core.dfft.{name} is deprecated; use repro.core.api.plan_nd "
        "and the fftn/ifftn/rfftn/irfftn front-end instead",
        DeprecationWarning, stacklevel=3)


def _shim_plan(shape, kind, mesh, mesh_axes, comm, planner, decomp):
    from .api import plan_nd
    return plan_nd(tuple(shape), kind, mesh=mesh, axes=tuple(mesh_axes),
                   comm=comm, planner=planner, decomp=decomp)


def fft2_slab(x: jax.Array, mesh: jax.sharding.Mesh, axis: str,
              planner: Optional[Planner] = None,
              comm: CommSpec = "collective", chunks: int = 4,
              keep_transposed: bool = False,
              permuted_cols: bool = False):
    """DEPRECATED: distributed 2D r2c FFT (use ``plan_nd`` + ``rfftn``).

    x: real (N, M), sharded (P(axis), None).  Returns (re, im) of shape
    (N, mh_pad) sharded the same way (crop to M//2+1 for the exact rfft2),
    or the transposed (mh_pad/P, N*P) folded layout if ``keep_transposed``
    (saves the whole second communication step when the consumer accepts
    transposed layout).  ``permuted_cols`` skips the column FFT's digit
    transpose (pair with ``ifft2_slab(..., permuted_cols=True)``).
    """
    _warn_deprecated("fft2_slab")
    planner = planner or Planner(backends=("jnp",))
    nd = _shim_plan(x.shape, "r2c", mesh, (axis,), comm, planner, "slab")
    return execute_slab(nd, x, mesh, planner, chunks=chunks,
                        keep_transposed=keep_transposed,
                        permuted_cols=permuted_cols)


def ifft2_slab(c: Complex, mesh: jax.sharding.Mesh, axis: str, m: int,
               planner: Optional[Planner] = None,
               comm: CommSpec = "collective", chunks: int = 4,
               from_transposed: bool = False,
               permuted_cols: bool = False) -> jax.Array:
    """DEPRECATED: inverse of :func:`fft2_slab` back to a real (N, M) array
    (use ``plan_nd`` + ``irfftn``)."""
    _warn_deprecated("ifft2_slab")
    planner = planner or Planner(backends=("jnp",))
    p = mesh.shape[axis]
    n = c[0].shape[1] // p if from_transposed else c[0].shape[0]
    nd = _shim_plan((n, m), "r2c", mesh, (axis,), comm, planner, "slab")
    return execute_slab_inverse(nd, c, mesh, planner, chunks=chunks,
                                from_transposed=from_transposed,
                                permuted_cols=permuted_cols)


def fft3_pencil(x: Complex, mesh: jax.sharding.Mesh, axes: Tuple[str, str],
                planner: Optional[Planner] = None,
                comm: CommSpec = "collective", chunks: int = 4) -> Complex:
    """DEPRECATED: 3D c2c pencil FFT of (X, Y, Z) sharded
    (P(ax0), P(ax1), None) (use ``plan_nd`` + ``fftn``).  Output sharded
    (None, P(ax0), P(ax1)).  ``comm`` may be one spec for both
    communicators, a per-axis pair/dict, ``"auto"`` or ``"measure"``."""
    _warn_deprecated("fft3_pencil")
    planner = planner or Planner(backends=("jnp",))
    nd = _shim_plan(x[0].shape, "c2c", mesh, axes, comm, planner, "pencil")
    return execute_pencil(nd, x, mesh, planner, chunks=chunks)


def ifft3_pencil(c: Complex, mesh: jax.sharding.Mesh, axes: Tuple[str, str],
                 planner: Optional[Planner] = None,
                 comm: CommSpec = "collective", chunks: int = 4) -> Complex:
    """DEPRECATED: inverse of :func:`fft3_pencil` (use ``plan_nd`` +
    ``ifftn``)."""
    _warn_deprecated("ifft3_pencil")
    planner = planner or Planner(backends=("jnp",))
    nd = _shim_plan(c[0].shape, "c2c", mesh, axes, comm, planner, "pencil")
    return execute_pencil_inverse(nd, c, mesh, planner, chunks=chunks)


def rfft3_pencil(x: jax.Array, mesh: jax.sharding.Mesh, axes: Tuple[str, str],
                 planner: Optional[Planner] = None,
                 comm: CommSpec = "collective", chunks: int = 4) -> Complex:
    """DEPRECATED: 3D r2c pencil FFT of a real (X, Y, Z) array (use
    ``plan_nd`` + ``rfftn``).  Output: (re, im) of global shape
    (X, Y, zh_pad) sharded (None, P(ax0), P(ax1)) — crop the last axis to
    Z//2+1 for the exact ``numpy.fft.rfftn``."""
    _warn_deprecated("rfft3_pencil")
    planner = planner or Planner(backends=("jnp",))
    nd = _shim_plan(x.shape, "r2c", mesh, axes, comm, planner, "pencil")
    return execute_pencil(nd, x, mesh, planner, chunks=chunks)


def irfft3_pencil(c: Complex, mesh: jax.sharding.Mesh, axes: Tuple[str, str],
                  nz: int, planner: Optional[Planner] = None,
                  comm: CommSpec = "collective",
                  chunks: int = 4) -> jax.Array:
    """DEPRECATED: inverse of :func:`rfft3_pencil` back to a real (X, Y, Z)
    array (use ``plan_nd`` + ``irfftn``).  Takes the *uncropped* padded
    spectrum plus the original Z length ``nz``."""
    _warn_deprecated("irfft3_pencil")
    planner = planner or Planner(backends=("jnp",))
    nx, ny = c[0].shape[0], c[0].shape[1]
    nd = _shim_plan((nx, ny, nz), "r2c", mesh, axes, comm, planner, "pencil")
    return execute_pencil_inverse(nd, c, mesh, planner, chunks=chunks)


# ---------------------------------------------------------------------------
# distribute / collect (the paper's `scatter` collective setup step)
# ---------------------------------------------------------------------------


def distribute(x: jax.Array, mesh: jax.sharding.Mesh, axis: str) -> jax.Array:
    """Scatter a host/global matrix into row slabs over ``axis`` (the paper's
    hpx scatter collective before the FFT)."""
    from jax.sharding import NamedSharding
    return jax.device_put(x, NamedSharding(mesh, P(axis, None)))


def collect(x, plan=None) -> np.ndarray:
    """Gather slabs back to a single host array (paper: gather/concat).

    With an :class:`~repro.core.api.NdPlan` the padded collective bands are
    cropped away (``plan.crop``), so callers get the exact transform instead
    of having to know the padded column count.  Pairs are cropped per
    member."""
    if isinstance(x, tuple):
        return tuple(collect(a, plan) for a in x)
    out = np.asarray(jax.device_get(x))
    if plan is not None:
        out = out[(Ellipsis,) + plan.crop]
    return out
