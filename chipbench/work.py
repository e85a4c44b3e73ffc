"""The transform's algorithmic work, and the table of peaks it is held to.

The work is the transform's, not the implementation's: benchFFT's
convention for the operations (5 N log2 N for a c2c transform of N points,
2.5 N log2 N for r2c, per call), and for the bytes each input element read
once and each output element written once.  A later change of algorithm
therefore leaves the roofline's meaning where it was.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

#: bytes of one element: a real f32, or a complex value as an f32 pair
_REAL, _COMPLEX = 4, 8


def peaks_for(device_kind: str, path: Path = PEAKS) -> dict:
    """The peaks of one chip of ``device_kind``.  A kind that is not in
    the table is an error, never another chip's peaks."""
    table = json.loads(path.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name} (known: {sorted(table)})")
    return table[device_kind]


def spectrum_shape(shape, kind: str) -> tuple:
    shape = tuple(shape)
    if kind == "r2c":
        return shape[:-1] + (shape[-1] // 2 + 1,)
    return shape


def call_work(shape, kind: str, call: str) -> dict:
    """Operations and least HBM bytes of one front-end call over the whole
    transform (all chips together)."""
    n = math.prod(shape)
    ops = (2.5 if kind == "r2c" else 5.0) * n * math.log2(n)
    spatial = n * (_REAL if kind == "r2c" else _COMPLEX)
    spectral = math.prod(spectrum_shape(shape, kind)) * _COMPLEX
    if call == "forward":
        nbytes = spatial + spectral
    elif call == "inverse":
        nbytes = spectral + spatial
    else:
        raise ValueError(f"unknown call {call!r}")
    return {"ops": ops, "bytes": float(nbytes)}


def step_work(shape, kind: str, calls, chips: int) -> dict:
    """Operations and bytes of one step of a mix, per chip."""
    total = {"ops": 0.0, "bytes": 0.0}
    for call in calls:
        w = call_work(shape, kind, call)
        total["ops"] += w["ops"]
        total["bytes"] += w["bytes"]
    return {k: v / chips for k, v in total.items()}


def least_step_seconds(work: dict, peaks: dict) -> tuple:
    """The least time one chip could take for ``work``, and which bound
    sets it ("bytes" or "ops")."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = work["ops"] / peaks["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
