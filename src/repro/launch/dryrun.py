import os
if __name__ == "__main__":      # run as a program: fake 512 host devices
    os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                               or "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

Run as a program, the lines above set the device count before ANY other
import (jax locks it on first init); imported as a module (for
``parse_collectives`` and friends) it leaves ``XLA_FLAGS`` alone.
Everything below is ordinary code.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCH_IDS, ALIASES, get_config          # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402
from repro.launch.specs import build_cell                        # noqa: E402
from repro.models.config import SHAPES                           # noqa: E402

# ---------------------------------------------------------------------------
# hardware constants (TPU v5e)
# ---------------------------------------------------------------------------

PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
LINK_BW = 50e9                    # bytes/s per ICI link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\)|[a-z0-9]+\[[\d,]*\])\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}|replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(typestr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(typestr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return 1
    if m.group(1) is not None:
        first = m.group(1).split("}")[0]
        return max(len([x for x in first.split(",") if x.strip() != ""]), 1)
    return int(m.group(3))


def parse_collectives(hlo_text: str, with_wire: bool = False):
    """Per-device operand bytes of every collective, bucketed by op kind.

    ``with_wire`` additionally returns per-device BYTES ON THE LINK, which is
    what distinguishes e.g. AGAS-style all-gather (receives (P-1)/P of the
    full result) from an all_to_all moving the same operand:
      all-gather:      result - operand          (ring receive)
      all-reduce:      2 * operand * (P-1)/P     (reduce-scatter + gather)
      reduce-scatter:  operand * (P-1)/P
      all-to-all:      operand * (P-1)/P
      collective-permute: operand
    """
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    wire = dict.fromkeys(out, 0.0)
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        if "-done" in line.split("=")[-1][:60]:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        typestr, op = m.group(1), m.group(2)
        result_bytes = _shape_bytes(typestr)
        g = max(_group_size(line), 1)
        frac = (g - 1) / g
        if op == "all-gather":
            operand = result_bytes / g
            w = result_bytes - operand
        elif op == "reduce-scatter":
            operand = result_bytes * g
            w = operand * frac
        elif op == "all-reduce":
            operand = result_bytes
            w = 2 * operand * frac
        elif op == "all-to-all":
            operand = result_bytes
            w = operand * frac
        else:                       # collective-permute
            operand = result_bytes
            w = operand
        out[op] += operand
        wire[op] += w
        counts[op] += 1
    if with_wire:
        return out, counts, wire
    return out, counts


def inner_scan_flops_correction(cfg, shape) -> float:
    """Flops hidden from HloCostAnalysis by ROLLED inner scans (flash-attn KV
    blocks, chunked-GLA chunks, sLSTM time steps), added analytically.

    REPRO_SCAN_UNROLL only unrolls the LAYER loop; inner loops stay rolled so
    cost analysis sees 1/n_iters of their flops.  We add the missing
    (n-1)/n portion.  Train steps multiply by 4 (forward + remat-recompute +
    ~2x backward); prefill by 1.  Decode paths have no inner scans.
    Residual error after correction: <1% (chunk boundary terms).
    """
    if shape.kind == "decode":
        return 0.0
    b, s = shape.global_batch, shape.seq_len
    mult = 4.0 if shape.kind == "train" else 1.0
    total = 0.0
    for kind, count in cfg.resolved_segments():
        if kind in ("attn_mlp", "attn_moe", "shared_attn"):
            block_kv = min(1024, s)
            nkv = max(s // block_kv, 1)
            fwd = 4.0 * b * s * s * cfg.num_heads * cfg.hd   # qk + pv MACs*2
            total += count * fwd * (nkv - 1) / nkv
        elif kind in ("mamba2", "mlstm"):
            q = 128
            nc = max(s // q, 1)
            if kind == "mamba2":
                di = cfg.ssm_expand * cfg.d_model
                h = di // cfg.ssm_head_dim
                dk, dv = cfg.ssm_state, cfg.ssm_head_dim
            else:
                h = cfg.num_heads
                dk = 2 * cfg.d_model // h
                dv = dk + 1
            # intra-chunk scores+out (2 MACs->flops each) + state update/carry
            fwd = 2.0 * b * s * h * (q * (dk + dv) + 2.0 * dk * dv)
            total += count * fwd * (nc - 1) / nc
        elif kind == "slstm":
            dh = cfg.d_model // cfg.slstm_heads
            fwd = 2.0 * b * s * 4.0 * cfg.slstm_heads * dh * dh
            total += count * fwd * (s - 1) / s
    return total * mult


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode counts one token/seq."""
    from repro.models import lm as _lm
    from repro.models.params import param_count
    total = param_count(_lm.model_meta(cfg))
    if cfg.num_experts:
        # non-active experts don't contribute: scale expert params by k/E
        from repro.models import blocks as _b
        active = total
        expert_fraction = (cfg.num_experts - cfg.top_k) / cfg.num_experts
        # expert params = 3 * d * ff * E per layer
        ep = 3 * cfg.d_model * cfg.d_ff * cfg.num_experts * cfg.num_layers
        active = total - ep * expert_fraction
        total = active
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * total * tokens


def _fwd_calibration(arch: str, shape_name: str, mesh):
    """Layer-body flop/collective calibration for train cells whose FULLY
    unrolled backward graph is too expensive to compile (deep recurrent
    stacks: 48-layer xLSTM, 81-layer zamba2).

    Compiles the rolled and the unrolled FORWARD pass (no autodiff — cheap),
    takes the delta (= per-layer body costs hidden by the rolled loop) and
    scales it: x4 for flops (fwd + remat recompute + ~2x bwd), x3 for
    collective bytes (FSDP gather in fwd, re-gather in remat, grad
    reduce-scatter).  Returned deltas are ADDED to the rolled train-step
    measurement.  Documented in EXPERIMENTS.md accounting notes.
    """
    from repro.configs import get_config
    from repro.models import lm as _lm
    from repro.models.params import abstract_tree, sharding_rules
    from repro.parallel import logical_shardings, make_rules
    from repro.launch.specs import batch_abstract
    from repro.data.pipeline import batch_specs
    from repro.parallel import sanitized_shardings
    from repro.models.config import SHAPES_BY_NAME

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    rules = make_rules(mesh)
    meta = _lm.model_meta(cfg)
    pspecs = logical_shardings(mesh, meta, rules)
    params_abs = abstract_tree(meta)
    batch_abs = batch_abstract(cfg, shape)
    raw_bspecs = {k: batch_specs(cfg, shape, rules)[k] for k in batch_abs}
    bspecs = sanitized_shardings(mesh, batch_abs, raw_bspecs)

    def fwd(params, batch):
        with sharding_rules(mesh, rules):
            return _lm.loss_fn(params, cfg, batch)[0]

    out = {}
    for mode in ("0", "1"):
        os.environ["REPRO_SCAN_UNROLL"] = mode
        with mesh:
            comp = jax.jit(fwd, in_shardings=(pspecs, bspecs)).lower(
                params_abs, batch_abs).compile()
        cost = comp.cost_analysis() or {}
        coll, _, wire = parse_collectives(comp.as_text(), with_wire=True)
        out[mode] = (float(cost.get("flops", 0.0)),
                     float(cost.get("bytes accessed", 0.0)),
                     coll, wire)
    os.environ["REPRO_SCAN_UNROLL"] = "0"
    d_flops = max(out["1"][0] - out["0"][0], 0.0)
    d_bytes = max(out["1"][1] - out["0"][1], 0.0)
    d_coll = {k: max(out["1"][2][k] - out["0"][2][k], 0.0) for k in out["1"][2]}
    d_wire = {k: max(out["1"][3][k] - out["0"][3][k], 0.0) for k in out["1"][3]}
    return d_flops, d_bytes, d_coll, d_wire


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             pipeline: bool = False, unroll_mode: str = "env"):
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cell = build_cell(arch, shape_name, mesh, pipeline=pipeline)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "chips": chips, "kind": cell.kind}
    if cell.skip_reason:
        rec["status"] = "skip"
        rec["reason"] = cell.skip_reason
        return rec

    calib = None
    prev_env = os.environ.get("REPRO_SCAN_UNROLL")
    if unroll_mode == "fwd" and cell.kind == "train":
        calib = _fwd_calibration(arch, shape_name, mesh)
        os.environ["REPRO_SCAN_UNROLL"] = "0"   # rolled full train step

    t0 = time.perf_counter()
    try:
        with mesh:
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
    finally:
        if prev_env is None:
            os.environ.pop("REPRO_SCAN_UNROLL", None)
        else:
            os.environ["REPRO_SCAN_UNROLL"] = prev_env
    rec["compile_seconds"] = round(time.perf_counter() - t0, 2)

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    cost = compiled.cost_analysis() or {}
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    rec["hlo_flops_per_device"] = flops_dev
    rec["hlo_bytes_per_device"] = bytes_dev

    text = compiled.as_text()
    coll, counts, wire = parse_collectives(text, with_wire=True)
    if calib is not None:
        d_flops, d_bytes, d_coll, d_wire = calib
        rec["fwd_calibration"] = {"d_flops": d_flops, "d_bytes": d_bytes}
        flops_dev += 4.0 * d_flops
        bytes_dev += 4.0 * d_bytes
        rec["hlo_flops_per_device"] = flops_dev
        rec["hlo_bytes_per_device"] = bytes_dev
        coll = {k: coll[k] + 3.0 * d_coll[k] for k in coll}
        wire = {k: wire[k] + 3.0 * d_wire[k] for k in wire}
    rec["collective_bytes_per_device"] = coll
    rec["collective_counts"] = counts
    rec["collective_wire_bytes_per_device"] = wire
    rec["t_collective_wire"] = sum(wire.values()) / LINK_BW
    coll_total = sum(coll.values())

    corr = inner_scan_flops_correction(cell.arch, cell.shape) / chips
    rec["inner_scan_flops_correction_per_device"] = corr
    flops_dev += corr

    # roofline terms (seconds)
    peak = PEAK_FLOPS_BF16
    rec["t_compute"] = flops_dev / peak
    rec["t_memory"] = bytes_dev / HBM_BW
    rec["t_collective"] = coll_total / LINK_BW
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
             "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)

    mf = model_flops(cell.arch, cell.shape)
    rec["model_flops_total"] = mf
    rec["model_flops_per_device"] = mf / chips
    rec["useful_flops_ratio"] = (mf / chips) / flops_dev if flops_dev else 0.0
    rec["status"] = "ok"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="pod-axis pipeline parallelism (multi-pod only)")
    ap.add_argument("--unroll-mode", choices=["env", "fwd"], default="env",
                    help="'fwd': rolled train step + forward-unroll flop "
                         "calibration (deep recurrent stacks)")
    ap.add_argument("--out", default=None, help="JSON output directory")
    args = ap.parse_args()

    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = [s.name for s in SHAPES] if args.all or not args.shape \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, mp, pipeline=args.pipeline,
                                   unroll_mode=args.unroll_mode)
                except Exception:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error",
                           "error": traceback.format_exc(limit=20)}
                results.append(rec)
                if rec["status"] == "ok":
                    print(f"[ok]   {tag}: compile={rec['compile_seconds']}s "
                          f"flops/dev={rec['hlo_flops_per_device']:.3e} "
                          f"coll/dev={sum(rec['collective_bytes_per_device'].values()):.3e}B "
                          f"bottleneck={rec['bottleneck']}", flush=True)
                elif rec["status"] == "skip":
                    print(f"[skip] {tag}: {rec['reason']}", flush=True)
                else:
                    print(f"[ERR]  {tag}:\n{rec['error']}", flush=True)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    fn = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                    fn += "_pipeline" if args.pipeline else ""
                    with open(os.path.join(args.out, fn + ".json"), "w") as f:
                        json.dump(rec, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} documented skips, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
