"""The program's own tracing: the named scopes its compiled programs carry
in their ops' ``op_name`` metadata, and the host spans its front end
writes into the profiler's trace.  The benchmark's per-layer readers
(``chipbench/scopes.py``) read both."""

import contextlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import Planner, api, irfftn, plan_nd, rfftn

SRC = Path(__file__).resolve().parents[1] / "src"
SCOPES = ("repro_r2c", "repro_exchange")


def _scopes_in(hlo_text: str) -> set:
    """The ``repro_*`` scopes named in the ops' ``op_name`` metadata."""
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {part for n in names for part in n.split("/")
            if part.startswith("repro_")}


@pytest.mark.parametrize("shape", [(16, 32), (16, 15)],
                         ids=["even", "odd"])
def test_local_rfftn_carries_the_r2c_scope(shape):
    planner = Planner()
    nd = plan_nd(shape, "r2c", planner=planner)
    x = jnp.ones(shape, jnp.float32)
    hlo = api._execute_local.lower(nd, x, planner).as_text(
        dialect="hlo", debug_info=True)
    assert _scopes_in(hlo) == {"repro_r2c"}
    compiled = api._execute_local.lower(nd, x, planner).compile().as_text()
    assert "repro_r2c" in _scopes_in(compiled)


@pytest.mark.parametrize("shape", [(16, 32), (16, 15)],
                         ids=["even", "odd"])
def test_local_irfftn_carries_the_r2c_scope(shape):
    planner = Planner()
    nd = plan_nd(shape, "r2c", planner=planner)
    c = (jnp.ones(nd.spectrum_shape, jnp.float32),) * 2
    hlo = api._execute_local_inverse.lower(nd, c, planner).as_text(
        dialect="hlo", debug_info=True)
    assert _scopes_in(hlo) == {"repro_r2c"}


def test_the_inner_c2c_fft_stays_outside_the_r2c_scope():
    """The DFT matmuls of an r2c transform carry no ``repro_*`` scope."""
    planner = Planner()
    nd = plan_nd((16, 32), "r2c", planner=planner)
    x = jnp.ones((16, 32), jnp.float32)
    hlo = api._execute_local.lower(nd, x, planner).as_text(
        dialect="hlo", debug_info=True)
    dots = [ln for ln in hlo.splitlines() if " dot(" in ln]
    assert dots and not any("repro_" in ln for ln in dots)


def _stripped(compiled_text: str) -> str:
    """Compiled HLO without its op metadata and the source-location tables
    (``FileNames`` to ``StackFrames``) that the metadata refers to."""
    program = compiled_text.split("\nFileNames\n", 1)[0]
    return re.sub(r", metadata=\{[^}]*\}", "", program)


@pytest.mark.parametrize("shape", [(16, 32), (16, 15)],
                         ids=["even", "odd"])
@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_scopes_leave_the_compiled_program_unchanged(shape, direction,
                                                     monkeypatch):
    """The named scopes change op metadata only: the local executors
    compile op for op as they do with every scope a no-op."""
    planner = Planner()
    nd = plan_nd(shape, "r2c", planner=planner)
    if direction == "forward":
        fn, arg = api._execute_local, jnp.ones(shape, jnp.float32)
    else:
        fn = api._execute_local_inverse
        arg = (jnp.ones(nd.spectrum_shape, jnp.float32),) * 2

    def compiled():
        jax.clear_caches()
        return fn.lower(nd, arg, planner).compile().as_text()

    scoped = compiled()
    assert "repro_r2c" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "repro_" not in bare
    assert _stripped(scoped) == _stripped(bare)


_SLAB = """
import json, re, sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import Planner, dfft, plan_nd
assert len(jax.devices()) == 4, jax.devices()
mesh = jax.make_mesh((4,), ("fft",))
planner = Planner()
x = jax.device_put(jnp.ones((64, 64), jnp.float32),
                   NamedSharding(mesh, P("fft", None)))
out = {}
for comm in ("collective", "pipelined", "agas"):
    nd = plan_nd((64, 64), "r2c", mesh=mesh, planner=planner,
                 decomp="slab", comm=comm)
    hlo = dfft.execute_slab.lower(nd, x, mesh, planner).as_text(
        dialect="hlo", debug_info=True)
    out[comm] = sorted({p for n in re.findall(r'op_name="([^"]*)"', hlo)
                        for p in n.split("/") if p.startswith("repro_")})
print(json.dumps(out))
"""


def test_slab_on_four_devices_carries_the_exchange_scope():
    """Every exchange backend runs inside ``repro_exchange`` (4 virtual
    devices, set before JAX starts, so in a child process)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in [env.get("PYTHONPATH")] if p])
    proc = subprocess.run([sys.executable, "-c", _SLAB], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    assert found == {comm: sorted(SCOPES)
                     for comm in ("collective", "pipelined", "agas")}


def _host_events(trace_dir: Path) -> list:
    (path,) = trace_dir.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_front_end_spans_in_the_profilers_trace(tmp_path):
    planner = Planner()
    x = jnp.ones((16, 32), jnp.float32)
    nd = plan_nd((16, 32), "r2c", planner=planner)
    jax.block_until_ready(irfftn(rfftn(x, plan=nd, planner=planner),
                                 plan=nd, planner=planner))   # compiled
    with jax.profiler.trace(str(tmp_path)):
        c = rfftn(x, plan=nd, planner=planner)
        jax.block_until_ready(irfftn(c, plan=nd, planner=planner))
    events = _host_events(tmp_path)
    names = [name for name, _, _ in events]
    assert names.count("repro.rfftn") == names.count("repro.irfftn") == 1
    assert names.count("repro.execute") == 2
    for front in ("repro.rfftn", "repro.irfftn"):
        (lo, hi) = [(a, b) for n, a, b in events if n == front][0]
        inside = [n for n, a, b in events
                  if n == "repro.execute" and lo <= a and b <= hi]
        assert inside == ["repro.execute"], (front, events)
