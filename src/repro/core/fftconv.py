"""FFT-based long convolution — the paper's distributed FFT as an LM mixer.

Hyena/S4-style token mixing is a length-L causal convolution, computed as
  y = ifft( fft(pad(u)) * fft(pad(k)) )[:L]
which is exactly the workload the paper studies: batched 1D FFTs plus a
global data redistribution when the sequence is sharded across devices.

Two beyond-paper TPU optimizations are first-class here:

* **Transpose elision** (permuted frequency order): the pointwise product
  commutes with the four-step digit permutation, so both the forward digit
  transpose and the inverse's un-permute are skipped (`permuted=True` plans).
  For the *distributed* path this removes the global transpose entirely —
  only the two all_to_all exchanges of the paper's algorithm remain, fwd and
  bwd (4 total), versus 6 exchanges for an order-preserving pipeline.

* **Overlap-ready chunked exchanges** (`comm="pipelined"`), via the shared
  exchange layer in :mod:`repro.core.comm` — the same swappable backends the
  slab/pencil paths in :mod:`repro.core.dfft` use.

The distributed 1D FFT views the length-L signal as an (N1, N2) matrix
(row-major), sharded over n1 — the paper's own 2D framing of the problem:

  stage A: all_to_all -> columns local; DFT along n1; twiddle T[k1, n2]
  stage B: all_to_all -> rows local;   DFT along n2
  output C[k1, k2] row-sharded, permuted order.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import algo
from .comm import (CommBackend, CommSpec, get_backend, measure_comm_conv,
                   plan_comm_conv)
from .dfft import batched_spec
from .plan import Planner

Complex = algo.Complex


def next_fft_len(n: int) -> int:
    """Smallest power of two >= n (all assigned seq lens are powers of two)."""
    m = 1
    while m < n:
        m *= 2
    return m


def factor_split(n: int, p: int) -> Optional[Tuple[int, int]]:
    """Factor a 1D transform length for the distributed factor-split FFT:
    ``n = n1 * n2`` with both factors divisible by ``p`` (every exchange is
    a tiled all_to_all over ``p`` participants) and as close to ``sqrt(n)``
    as the divisors allow.  Returns ``None`` when no such split exists
    (``n`` not a multiple of ``p**2``, or a factor would be an
    unfactorizable prime) — the caller falls back to a local transform.

    Shared by :func:`fft_conv_seq_sharded` and the ``factor1d``
    decomposition of :func:`repro.core.api.plan_nd`.
    """
    if p < 1 or n % (p * p):
        return None
    r = n // (p * p)
    best = None
    for a in range(1, int(np.sqrt(r)) + 1):
        if r % a == 0:
            best = a                    # largest divisor <= sqrt(r)
    n1, n2 = p * best, p * (r // best)
    try:                                # both stages must be plannable
        algo.default_factorization(n1)
        algo.default_factorization(n2)
    except ValueError:
        return None
    return n1, n2


# ---------------------------------------------------------------------------
# implicit filter parameterization (Hyena-lite): tiny param count at any L
# ---------------------------------------------------------------------------


def filter_basis(length: int, rank: int, dtype=jnp.float32) -> jax.Array:
    """(rank, length) damped-oscillator basis, generated in-graph via iota so
    a 500k-length filter costs no parameter memory."""
    t = jax.lax.iota(jnp.float32, length)[None, :] / max(length, 1)
    r = jax.lax.iota(jnp.float32, rank)[:, None]
    decay = jnp.exp(-jnp.exp(0.5 * r) * t)
    phase = jnp.cos(2.0 * np.pi * (r + 1.0) * t)
    return (decay * phase).astype(dtype)


def materialize_filter(weights: jax.Array, length: int) -> jax.Array:
    """weights (D, rank) -> causal filters (D, length)."""
    basis = filter_basis(length, weights.shape[-1], weights.dtype)
    return weights @ basis


# ---------------------------------------------------------------------------
# single-device FFT convolution
# ---------------------------------------------------------------------------


def fft_conv(u: jax.Array, k: jax.Array, planner: Optional[Planner] = None,
             permuted: bool = True) -> jax.Array:
    """Causal convolution via FFT.

    u: (B, L, D) real activations; k: (D, L) real causal filters.
    Returns (B, L, D).  Uses c2c on the real signal (imag = 0) so the
    permuted-order transpose elision applies end to end.
    """
    b, slen, d = u.shape
    nf = next_fft_len(2 * slen)
    planner = planner or Planner(backends=("jnp",))
    plan = planner.plan(nf, kind="c2c", permuted=permuted)

    ut = jnp.moveaxis(u, 1, 2).astype(jnp.float32)              # (B, D, L)
    up = jnp.pad(ut, ((0, 0), (0, 0), (0, nf - slen)))
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, nf - slen)))

    from .plan import execute, execute_inverse
    uf = execute(plan, (up, jnp.zeros_like(up)))
    kf = execute(plan, (kp, jnp.zeros_like(kp)))
    prod = algo.cmul(uf, kf)
    y = execute_inverse(plan, prod)[0]                          # real part
    return jnp.moveaxis(y[..., :slen], 2, 1).astype(u.dtype)


# ---------------------------------------------------------------------------
# sequence-sharded distributed FFT convolution (shard_map)
# ---------------------------------------------------------------------------


def _dist_fft_permuted(x: Complex, axis: str, p: int, n1: int, n2: int,
                       sign: int, planner: Planner,
                       backend: Optional[CommBackend] = None) -> Complex:
    """Distributed c2c FFT along axis 1 of local (B, Lloc, D) blocks.

    Global length N = n1 * n2, row-major (n1, n2), sharded over n1.
    Returns C[k1, k2] (permuted order), k1-sharded: local (B, Lloc, D).
    """
    from .plan import execute
    backend = backend or get_backend("collective")
    bsz, lloc, d = x[0].shape
    n1loc = n1 // p
    assert lloc == n1loc * n2, (lloc, n1, n2, p)
    plan1 = planner.plan(n1, kind="c2c")
    plan2 = planner.plan(n2, kind="c2c")

    def r4(a):  # (B, n1loc, n2, D) view
        return a.reshape(bsz, n1loc, n2, d)

    a = (r4(x[0]), r4(x[1]))
    # stage A: columns local
    a = backend.exchange(a, axis, split=2, concat=1, p=p)       # (B, n1, n2/p, D)
    at = (jnp.moveaxis(a[0], 1, -1), jnp.moveaxis(a[1], 1, -1))  # n1 last
    bt = execute(plan1, at) if sign < 0 else _inv_exec(plan1, at)
    bm = (jnp.moveaxis(bt[0], -1, 1), jnp.moveaxis(bt[1], -1, 1))
    # twiddle T[k1, n2-block], sliced to this device's n2 columns
    tw = algo.twiddle_factors(n1, n2, sign)
    me = jax.lax.axis_index(axis)
    w = n2 // p
    twr = jax.lax.dynamic_slice_in_dim(tw[0], me * w, w, 1)     # (n1, n2/p)
    twi = jax.lax.dynamic_slice_in_dim(tw[1], me * w, w, 1)
    btw = algo.cmul(bm, (twr[None, :, :, None], twi[None, :, :, None]))
    # stage B: rows local
    c = backend.exchange(btw, axis, split=1, concat=2, p=p)     # (B, n1/p, n2, D)
    ct = (jnp.moveaxis(c[0], 2, -1), jnp.moveaxis(c[1], 2, -1))  # n2 last
    dt = execute(plan2, ct) if sign < 0 else _inv_exec(plan2, ct)
    dm = (jnp.moveaxis(dt[0], -1, 2), jnp.moveaxis(dt[1], -1, 2))
    return dm[0].reshape(bsz, lloc, d), dm[1].reshape(bsz, lloc, d)


def _inv_exec(plan, x):
    """Unnormalized inverse (sign=+1) transform with the plan's recipe."""
    return algo.fft(x, sign=+1, factors=plan.factors or None,
                    karatsuba=plan.karatsuba)


def _dist_ifft_permuted(x: Complex, axis: str, p: int, n1: int, n2: int,
                        planner: Planner,
                        backend: Optional[CommBackend] = None) -> Complex:
    """Inverse of :func:`_dist_fft_permuted` (consumes permuted order)."""
    from .plan import execute
    backend = backend or get_backend("collective")
    bsz, lloc, d = x[0].shape
    n1loc = n1 // p
    n = n1 * n2
    plan1 = planner.plan(n1, kind="c2c")
    plan2 = planner.plan(n2, kind="c2c")

    c = (x[0].reshape(bsz, n1loc, n2, d), x[1].reshape(bsz, n1loc, n2, d))
    # inverse DFT along k2 (rows are local)
    ct = (jnp.moveaxis(c[0], 2, -1), jnp.moveaxis(c[1], 2, -1))
    bt = _inv_exec(plan2, ct)
    b = (jnp.moveaxis(bt[0], -1, 2), jnp.moveaxis(bt[1], -1, 2))
    # conjugate twiddle T[k1-block, n2]
    tw = algo.twiddle_factors(n1, n2, +1)
    me = jax.lax.axis_index(axis)
    twr = jax.lax.dynamic_slice_in_dim(tw[0], me * n1loc, n1loc, 0)
    twi = jax.lax.dynamic_slice_in_dim(tw[1], me * n1loc, n1loc, 0)
    b = algo.cmul(b, (twr[None, :, :, None], twi[None, :, :, None]))
    # all_to_all -> columns local; inverse DFT along k1
    a = backend.exchange(b, axis, split=2, concat=1, p=p)       # (B, n1, n2/p, D)
    at = (jnp.moveaxis(a[0], 1, -1), jnp.moveaxis(a[1], 1, -1))
    ot = _inv_exec(plan1, at)
    o = (jnp.moveaxis(ot[0], -1, 1), jnp.moveaxis(ot[1], -1, 1))
    # back to row-sharded layout
    o = backend.exchange(o, axis, split=1, concat=2, p=p)       # (B, n1/p, n2, D)
    scale = 1.0 / n
    return (o[0].reshape(bsz, lloc, d) * scale,
            o[1].reshape(bsz, lloc, d) * scale)


def fft_conv_seq_sharded(u: jax.Array, k: jax.Array,
                         mesh: jax.sharding.Mesh, axis: str,
                         planner: Optional[Planner] = None,
                         comm: CommSpec = "collective",
                         chunks: int = 4) -> jax.Array:
    """Causal FFT convolution with the sequence sharded over ``axis``.

    u: (B, L, D) with L sharded; k: (D, L_full) replicated filters.
    The paper's distributed algorithm, transposed-order end to end.
    ``comm`` picks the exchange backend (see :mod:`repro.core.comm`);
    ``"auto"`` plans it from the roofline model, ``"measure"`` times the
    backends on the live mesh (verdict cached in the planner's wisdom).
    """
    planner = planner or Planner(backends=("jnp",))
    b, slen, d = u.shape
    p = mesh.shape[axis]
    nf = next_fft_len(2 * slen)
    # both factors near sqrt(nf), each divisible by p (stage-A AND stage-B
    # exchanges are tiled all_to_alls) — the same split the factor1d
    # decomposition of plan_nd uses
    split = factor_split(nf, p)
    assert split is not None, f"sequence too short for mesh: nf={nf}, p={p}"
    n1, n2 = split
    if comm == "auto":
        comm = plan_comm_conv(b, d, n1, n2, p, hw=planner.hw)
    elif comm == "measure":
        comm = measure_comm_conv(b, d, n1, n2, mesh, axis,
                                 wisdom=planner.wisdom)
    return _conv_seq_sharded(u, k, mesh, axis, planner, comm, chunks, n1, n2)


@functools.partial(jax.jit, static_argnums=tuple(range(2, 9)))
def _conv_seq_sharded(u: jax.Array, k: jax.Array, mesh, axis: str,
                      planner: Planner, comm: CommSpec, chunks: int,
                      n1: int, n2: int) -> jax.Array:
    """The compiled body of :func:`fft_conv_seq_sharded`: one program per
    mesh, resolved exchange spec and shapes (an eager ``shard_map`` would
    compile every primitive of its body on every call; see
    :func:`repro.core.dfft._compiled`)."""
    slen = u.shape[1]
    p = mesh.shape[axis]
    nf = n1 * n2
    backend = get_backend(comm, chunks=chunks)

    # global zero-padding to the FFT length (outside shard_map: the tail
    # zeros live on the trailing devices of the sequence axis)
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (0, nf - slen), (0, 0)))
    kp = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, nf - slen)))

    def local(ul: jax.Array, kl: jax.Array) -> jax.Array:
        klt = kl.T[None]                                        # (1, nf/p, D)
        uf = _dist_fft_permuted((ul, jnp.zeros_like(ul)), axis, p, n1, n2,
                                -1, planner, backend)
        kf = _dist_fft_permuted((klt, jnp.zeros_like(klt)), axis, p, n1, n2,
                                -1, planner, backend)
        prod = algo.cmul(uf, kf)
        return _dist_ifft_permuted(prod, axis, p, n1, n2, planner, backend)[0]

    # the (B, L, D) activations and (D, L) filters share the batched-spec
    # convention of the dfft executors: one leading replicated batch dim
    # prepended to the sharded-sequence spec
    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(batched_spec(P(axis, None), 1), batched_spec(P(axis), 1)),
        out_specs=batched_spec(P(axis, None), 1),
    )(up, kp)
    return y[:, :slen, :].astype(u.dtype)
