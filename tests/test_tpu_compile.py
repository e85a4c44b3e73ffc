"""Real-size compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX, and compiles for a topology that
is described but not attached.  These tests compile the main path at the
paper's sizes and check what only that compiler can refuse: a program that
does not fit the chip's memory, and a Pallas kernel that Mosaic does not
accept.  Nothing runs, so they say nothing about results or times.

The planner's kernel calls decide interpret mode from the platform JAX
runs on, which here is the CPU; the tests that compile them through the
planner make it read as a TPU (``kernel_on_chip``).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import Planner, fftn, plan_nd, rfftn
from repro.kernels.dft_matmul.dft_matmul import SPLITS, fft_four_step_pallas

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


@pytest.fixture
def kernel_on_chip(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


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


KERNEL_CASES = [
    (4096, (128, 128)),
    (16384, (64, 128)),        # the local cell's rows: inner c2c 8192
    (8200, (128, 128)),        # its columns: 8193 padded to 8200
]


@pytest.mark.parametrize("rows,factors", KERNEL_CASES)
def test_dft_matmul_kernel_compiles(one_chip, rows, factors):
    x = jax.ShapeDtypeStruct((rows, factors[0] * factors[1]), jnp.float32,
                             sharding=one_chip)
    compiled = _compile(lambda a, b: fft_four_step_pallas(
        (a, b), factors, interpret=False), x, x)
    assert "tpu_custom_call" in compiled.as_text()


def test_every_split_the_planner_gives_the_kernel_is_compiled_here():
    assert {factors for _, factors in KERNEL_CASES} == set(SPLITS)


FUSED = ("pallas", "jnp")


def test_local_rfftn_2d_through_the_kernel_compiles(one_chip, kernel_on_chip):
    planner = Planner(backends=FUSED)
    nd = plan_nd((N2D, N2D), "r2c", planner=planner)
    x = jax.ShapeDtypeStruct((N2D, N2D), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda a: rfftn(a, plan=nd, planner=planner), x)
    assert "tpu_custom_call" in compiled.as_text()


def test_slab_rfftn_2d_through_the_kernel_on_four_chips_compiles(
        topo, kernel_on_chip):
    mesh = Mesh(np.array(topo.devices[:4]), ("fft",),
                axis_types=(AxisType.Explicit,))
    planner = Planner(backends=FUSED)
    nd = plan_nd((N2D, N2D), "r2c", mesh=mesh, planner=planner,
                 decomp="slab", comm="collective")
    x = jax.ShapeDtypeStruct((N2D, N2D), jnp.float32,
                             sharding=NamedSharding(mesh, P("fft", None)))
    compiled = _compile(
        lambda a: rfftn(a, mesh=mesh, plan=nd, planner=planner), x)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text
