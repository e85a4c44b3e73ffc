"""Real-size compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX, and compiles for a topology that
is described but not attached.  These tests compile the main path at the
paper's sizes and check what only that compiler can refuse: a program that
does not fit the chip's memory, and a Pallas kernel that Mosaic does not
accept.  Nothing runs, so they say nothing about results or times.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import Planner, fftn, plan_nd, rfftn
from repro.kernels.dft_matmul.dft_matmul import fft_four_step_pallas

HBM_BYTES = 16 * 2 ** 30            # one v5e chip
N2D = 1 << 14                       # the paper's 2D r2c size
N3D = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total <= HBM_BYTES, (mem, total)
    return compiled


def test_local_rfftn_2d_compiles(one_chip):
    planner = Planner()
    nd = plan_nd((N2D, N2D), "r2c", planner=planner)
    x = jax.ShapeDtypeStruct((N2D, N2D), jnp.float32, sharding=one_chip)
    _compile(lambda a: rfftn(a, plan=nd, planner=planner), x)


def test_local_fftn_3d_compiles(one_chip):
    planner = Planner()
    nd = plan_nd((N3D,) * 3, "c2c", planner=planner)
    x = jax.ShapeDtypeStruct((N3D,) * 3, jnp.float32, sharding=one_chip)
    _compile(lambda a, b: fftn((a, b), plan=nd, planner=planner), x, x)


def test_slab_rfftn_2d_on_four_chips_compiles(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("fft",),
                axis_types=(AxisType.Explicit,))
    planner = Planner()
    nd = plan_nd((N2D, N2D), "r2c", mesh=mesh, planner=planner,
                 decomp="slab", comm="collective")
    x = jax.ShapeDtypeStruct((N2D, N2D), jnp.float32,
                             sharding=NamedSharding(mesh, P("fft", None)))
    compiled = _compile(
        lambda a: rfftn(a, mesh=mesh, plan=nd, planner=planner), x)
    assert "all-to-all" in compiled.as_text()


def test_dft_matmul_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 128 * 128), jnp.float32,
                             sharding=one_chip)
    compiled = _compile(lambda a, b: fft_four_step_pallas(
        (a, b), (128, 128), interpret=False), x, x)
    assert "tpu_custom_call" in compiled.as_text()
