"""One run of one cell: set-up, the measured (or traced) window, and the
check of what the window produced against the float64 reference.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is data found by name under the checkout:

* ``BENCHMARK.json``: the cell (configuration, mix, chips) and the
  metrics that it reports;
* ``chipbench/configs/<config>.json``: shape, kind, dtype, mesh layout,
  and the limit of the output check;
* ``chipbench/traffic/<mix>.json``: the calls of one step, the planning
  mode, the warm-up, the traced steps and the steps the check samples from;
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import work, xplane, yardstick

BENCH_DIR = "chipbench"
CALLS = ("forward", "inverse")


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and mix."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def shape(self) -> tuple:
        return tuple(self.config["shape"])

    @property
    def kind(self) -> str:
        return self.config["kind"]

    @property
    def calls(self) -> List[str]:
        return list(self.mix["calls"])


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """Find a cell, its configuration and its mix by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in "
                         f"BENCHMARK.json (known: {sorted(cells)})")
    w = cells[name]
    (cfg_entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix_path = root / BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    mix = json.loads(mix_path.read_text())
    bad = [c for c in mix["calls"] if c not in CALLS]
    if bad or not mix["calls"] or mix["calls"][0] != "forward":
        raise ValueError(f"{mix_path.name}: calls must start with "
                         f"'forward' and be among {CALLS}: {mix['calls']}")
    mesh_size = int(np.prod(config["mesh"]["shape"]))
    if mesh_size != w["chips"]:
        raise ValueError(f"{name}: config {w['config']} lays its mesh over "
                         f"{mesh_size} chips, the cell asks for {w['chips']}")
    return Cell(name, int(w["chips"]), config, mix,
                [m for m in bench["end_to_end"] if _reported_in(m, name)],
                [m for m in bench["per_layer"] if _reported_in(m, name)],
                root)


def require_accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices, or exit: a run on another
    platform, or on fewer chips than the cell asks for, prints no
    result."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU found (JAX sees "
                         f"{devices[0].platform!r}); not running on another "
                         f"device")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devices)}")
    return devices[:chips]


def compile_cache_dir(root: Path) -> Path:
    """The persistent compilation cache: a fixed directory inside the
    checkout, so that every run after the first in that checkout finds
    its programs (the path is part of the cache's key)."""
    return root / BENCH_DIR / ".jax_cache"


def enable_compile_cache(root: Path) -> Path:
    path = compile_cache_dir(root)
    jax.config.update("jax_compilation_cache_dir", str(path))
    # every compile is kept: the eager front end compiles small programs
    # that never reach JAX's default one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def seed_key(seed: int):
    """A PRNG key from all the bits of ``seed`` (``jax.random.key`` alone
    keeps only the low 32 of a larger Python int)."""
    seed %= 1 << 64
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_mesh(config: dict, devices: list):
    m = config["mesh"]
    return jax.make_mesh(tuple(m["shape"]), tuple(m["axes"]),
                         devices=devices)


def make_input(cell: Cell, mesh, seed: int):
    """The input, made on the devices in one compiled call from the seed:
    a real f32 array (r2c) or an (re, im) f32 pair (c2c)."""
    if cell.config["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {cell.config['dtype']!r}")
    sharding = NamedSharding(mesh, P(*cell.config["mesh"]["input_spec"]))
    shape = cell.shape

    if cell.kind == "r2c":
        def gen(key):
            return jax.random.normal(key, shape, np.float32)
        out_shardings = sharding
    else:
        def gen(key):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(k1, shape, np.float32),
                    jax.random.normal(k2, shape, np.float32))
        out_shardings = (sharding, sharding)
    return jax.block_until_ready(
        jax.jit(gen, out_shardings=out_shardings)(seed_key(seed)))


def make_step(cell: Cell, mesh, device_kind: str):
    """Plan once (``plan_nd``, the planner's own choice), and return the
    plan and one step of the mix: the public eager front end, called as a
    user calls it."""
    from repro.core import (Planner, fftn, hardware_for, ifftn, irfftn,
                            plan_nd, rfftn)
    front = {("r2c", "forward"): rfftn, ("r2c", "inverse"): irfftn,
             ("c2c", "forward"): fftn, ("c2c", "inverse"): ifftn}
    planner = Planner(hardware=hardware_for(device_kind))
    nd = plan_nd(cell.shape, cell.kind, mesh=mesh, planner=planner,
                 mode=cell.mix["plan"]["mode"])
    fns = [front[(cell.kind, c)] for c in cell.calls]

    def step(x) -> list:
        outs = []
        for fn in fns:
            x = fn(x, mesh=mesh, plan=nd, planner=planner)
            outs.append(x)
        return outs

    return nd, step


def _sample_step(seed: int, mix: dict, steps_at_least: int) -> int:
    """The window step whose answers the check keeps, drawn from the
    seed among the first ``check_sample_steps``."""
    hi = max(1, min(int(mix["check_sample_steps"]), steps_at_least))
    return int(np.random.default_rng(seed).integers(0, hi))


@dataclasses.dataclass
class Window:
    steps: int
    window_s: float
    step_s: List[float]
    dispatch_s: List[float]
    kept: Dict[str, list]          # "step<k>" / "last" -> outputs
    compiles: dict


def run_window(step: Callable, x, *, seconds: Optional[float] = None,
               steps: Optional[int] = None, sample: int = 0,
               spans: bool = False) -> Window:
    """A closed loop of one caller: call, wait until ready, call again.

    Runs until ``seconds`` have passed (the step that crosses the end
    completes and counts) or for ``steps`` steps.  Each step is timed from
    its call until its result is ready; the dispatch is the time until
    the front-end calls return.  With ``spans`` every step carries the
    host spans that the trace reduction reads."""
    step_s, dispatch_s = [], []
    kept = {}
    outs = None
    with yardstick.compile_log() as log:
        t_start = time.perf_counter()
        end = t_start + seconds if seconds is not None else None
        i = 0
        while True:
            outs = None         # the last step's result is not kept alive
            if spans:
                with jax.profiler.TraceAnnotation(xplane.STEP):
                    t0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation(xplane.DISPATCH):
                        outs = step(x)
                    t1 = time.perf_counter()
                    with jax.profiler.TraceAnnotation(xplane.BLOCK):
                        jax.block_until_ready(outs)
                    t2 = time.perf_counter()
            else:
                t0 = time.perf_counter()
                outs = step(x)
                t1 = time.perf_counter()
                jax.block_until_ready(outs)
                t2 = time.perf_counter()
            step_s.append(t2 - t0)
            dispatch_s.append(t1 - t0)
            if i == sample:
                kept[f"step{i}"] = outs
            i += 1
            if (end is not None and t2 >= end) or (steps is not None
                                                   and i >= steps):
                break
        window_s = t2 - t_start
    kept["last"] = outs
    return Window(i, window_s, step_s, dispatch_s, kept, dict(log))


def p95(values: List[float]) -> float:
    """The 95th percentile (Python's ``statistics.quantiles``)."""
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20)[18]


def check_outputs(cell: Cell, mesh, x, kept: Dict[str, list]) -> dict:
    """Compare every kept answer with the float64 reference.

    Returns ``{name: {"value": v, "limit": l}}``; an answer is correct when
    every value is at most its limit.  The device arrays are copied to the
    host and freed before the reference runs."""
    limits = cell.config["check"]["max_rel_err"]
    checks = {}
    devices = set(mesh.devices.flat)
    unsharded = 0
    for outs in kept.values():
        for out in outs:
            for a in (out if isinstance(out, (tuple, list)) else (out,)):
                sh = a.sharding
                if sh.device_set != devices or (
                        mesh.size > 1 and sh.is_fully_replicated):
                    unsharded += 1
    checks["outputs_not_over_mesh"] = {"value": unsharded, "limit": 0}

    x_host = yardstick.host_value(x)
    if isinstance(x_host, tuple):
        x_host = x_host[0] + 1j * x_host[1]
    got = {name: [yardstick.host_value(o) for o in outs]
           for name, outs in kept.items()}
    for a in jax.tree_util.tree_leaves((x, kept)):
        a.delete()
    ref = x_host
    for i, call in enumerate(cell.calls):
        ref = yardstick.reference(cell.kind, call, ref)
        for name, outs in got.items():
            out = outs[i]
            shape = (out[0] if isinstance(out, tuple) else out).shape
            if shape != ref.shape:
                checks[f"shape_mismatch.{name}.{call}"] = {"value": 1,
                                                           "limit": 0}
                continue
            checks[f"rel_err.{name}.{call}"] = {
                "value": yardstick.rel_err(out, ref),
                "limit": limits[call]}
    return checks


def _load_reader(root: Path, metric: str):
    path = root / BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, trace: xplane.Trace, ctx: dict) -> dict:
    """Run each per-layer reader of the cell over the trace.  A reader
    that finds nothing to read returns None, and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = _load_reader(cell.root, m["name"])(trace, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_window(cell: Cell, step, x, sample: int):
    """A short traced window of ``trace_steps`` steps; returns the window
    and the reduced trace."""
    trace_dir = cell.root / BENCH_DIR / "traces" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=options):
        win = run_window(step, x, steps=int(cell.mix["trace_steps"]),
                         sample=sample, spans=True)
    return win, xplane.load(xplane.find_xplane(trace_dir))


def run_cell(cell: Cell, devices: list, seed: int, seconds: float,
             trace: bool, t_process: float) -> dict:
    """One run of a cell: the result object the benchmark prints."""
    dev = devices[0]
    mesh = make_mesh(cell.config, devices)
    with yardstick.compile_log() as setup_log:
        x = make_input(cell, mesh, seed)
        nd, step = make_step(cell, mesh, dev.device_kind)
        print(f"[{cell.name}] plan: decomp={nd.decomp} "
              f"mesh_axes={nd.mesh_axes} comm={nd.comm} "
              f"shape={nd.shape} kind={nd.kind}", flush=True)
        for _ in range(int(cell.mix["warmup_steps"])):
            jax.block_until_ready(step(x))
    setup_s = time.perf_counter() - t_process
    print(f"[{cell.name}] set-up {setup_s:.3f} s: {setup_log['compiles']} "
          f"compiles taking {setup_log['compile_s']:.3f} s, "
          f"{setup_log['cache_hits']} persistent-cache hits", flush=True)

    peaks = work.peaks_for(dev.device_kind)
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}}
    if trace:
        sample = _sample_step(seed, cell.mix, int(cell.mix["trace_steps"]))
        win, tr = traced_window(cell, step, x, sample)
        if not any(tr.device_ops(d) for d in tr.ops):
            raise SystemExit("chipbench: the trace holds no device "
                             "operation in the window")
        ctx = {"dispatch_s": win.dispatch_s, "peaks": peaks,
               "work": work.step_work(cell.shape, cell.kind, cell.calls,
                                      cell.chips)}
        result["metrics"] = read_per_layer(cell, tr, ctx)
        busy = [xplane.busy_ns(tr, d) for d in tr.ops]
        result["device"]["busy_s"] = sum(busy) / len(busy) / 1e9
        result["device"]["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = xplane.breakdown(tr)
    else:
        sample = _sample_step(seed, cell.mix, 1 << 30)
        win = run_window(step, x, seconds=seconds, sample=sample)
        values = {
            "transform_ms": win.window_s / win.steps * 1e3,
            "transform_p95_ms": p95(win.step_s) * 1e3,
            "setup_s": setup_s,
        }
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end
                             if m["name"] in values}
    peak = yardstick.peak_bytes(devices)
    result["device"]["memory_peak_bytes"] = peak
    if not trace and any(m["name"] == "peak_hbm_gib"
                         for m in cell.end_to_end):
        result["metrics"]["peak_hbm_gib"] = {"value": peak / 2 ** 30,
                                             "unit": "GiB"}
    q = statistics.quantiles(win.step_s, n=4) if win.steps > 1 else [0] * 3
    print(f"[{cell.name}] window: {win.steps} steps in {win.window_s:.3f} s;"
          f" {win.compiles['compiles']} compiles and "
          f"{win.compiles['cache_hits']} cache hits inside it; "
          f"peak_bytes_in_use {peak}; step ms min {min(win.step_s) * 1e3:.3f}"
          f" quartiles {[round(v * 1e3, 3) for v in q]} max "
          f"{max(win.step_s) * 1e3:.3f}; dispatch ms mean "
          f"{sum(win.dispatch_s) / win.steps * 1e3:.3f}", flush=True)

    checks = check_outputs(cell, mesh, x, win.kept)
    checks["compiles_in_window"] = {"value": win.compiles["compiles"],
                                    "limit": 0}
    bad = [k for k, c in checks.items() if not c["value"] <= c["limit"]]
    result["correct"] = not bad
    result["attempted"] = win.steps
    result["failed"] = len(bad)
    result["checks"] = checks
    return result


def report_checks(checks: dict, stream=None) -> None:
    """Each number compared, beside its limit, one line each."""
    stream = stream or sys.stderr
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=stream, flush=True)
