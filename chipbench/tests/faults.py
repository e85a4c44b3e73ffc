"""The control and the faults that the output check has to catch, planted
in the program underneath the benchmark (``monkeypatch``; the executors
are compiled, so the caller clears JAX's caches around a plant).

* ``control``: the DFT matmuls at three bf16 passes (``Precision.HIGH``,
  written out so that the CPU computes it the same way) instead of the
  program's ``HIGHEST``: the step a later change would be tempted by.
* ``answer_altered``: one value of the spectrum zeroed where it is made.
* ``half_left_out``: the transform of only the first half of the rows.
* ``exchange_left_out``: every all-to-all keeps each chip's own block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("answer_altered", "half_left_out", "exchange_left_out")


def _mm_three_bf16_passes(a, b):
    """``a @ b`` as a TPU computes it at ``Precision.HIGH``: each f32
    operand split into a bf16 head and a bf16 tail, and the three
    products that matter summed in f32.  The split rounds with
    ``reduce_precision``, which the compiler keeps; a round trip through
    a bf16 array it may drop (XLA allows excess precision), and the tail
    then vanishes."""
    dims = (((a.ndim - 1,), (0,)), ((), ()))

    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def split(v):
        head = bf16(v)
        return head, bf16(v - head)

    def dot(u, w):
        # both operands hold bf16 values: one pass is exact on them
        return jax.lax.dot_general(u, w, dims,
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)

    (ah, at), (bh, bt) = split(a), split(b)
    return dot(ah, bh) + (dot(ah, bt) + dot(at, bh))


def _local_a2a(c, axis_name, split, concat):
    """The shape of a tiled all-to-all with no exchange: each chip tiles
    its own first block."""
    def one(a):
        p = jax.lax.axis_size(axis_name)
        block = jax.lax.slice_in_dim(a, 0, a.shape[split] // p, axis=split)
        return jnp.concatenate([block] * p, axis=concat)
    return one(c[0]), one(c[1])


def plant(monkeypatch, name: str) -> None:
    """Plant the control or one fault for the rest of the test."""
    import repro.core.algo as algo
    import repro.core.api as api
    import repro.core.comm as comm

    if name == "control":
        monkeypatch.setattr(algo, "_mm", _mm_three_bf16_passes)
    elif name == "answer_altered":
        execute_nd = api.execute_nd

        def altered(plan, x, *args, **kw):
            re, im = execute_nd(plan, x, *args, **kw)
            idx = (0,) * (re.ndim - 1) + (1,)
            return re.at[idx].set(0.0), im.at[idx].set(0.0)
        monkeypatch.setattr(api, "execute_nd", altered)
    elif name == "half_left_out":
        execute_nd = api.execute_nd

        def half(plan, x, *args, **kw):
            def cut(a):
                n0 = a.shape[a.ndim - len(plan.shape)]
                rows = jnp.arange(n0) < n0 // 2
                shape = (n0,) + (1,) * (len(plan.shape) - 1)
                return jnp.where(rows.reshape(shape), a, 0)
            x = tuple(map(cut, x)) if isinstance(x, tuple) else cut(x)
            return execute_nd(plan, x, *args, **kw)
        monkeypatch.setattr(api, "execute_nd", half)
    elif name == "exchange_left_out":
        monkeypatch.setattr(comm, "a2a_pair", _local_a2a)
    else:
        raise ValueError(f"unknown fault {name!r}")
