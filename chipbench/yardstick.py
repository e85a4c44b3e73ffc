"""The benchmark's own yardstick: compile counting, peak memory, the plain
float64 reference and the error it is compared by.

``compile_log``, ``rel_err`` and ``peak_bytes`` are copies of the helpers
in ``chip_smoke.py``, kept here so that no change to the program can move
what the benchmark measures with (``rel_err`` also takes an (re, im)
pair and spreads its blocks over threads; ``peak_bytes`` takes the
fullest of several devices).  The reference imports nothing of the
program: it is ``scipy.fft`` in float64 on the host, applied in blocks of
rows and of columns so that its temporaries stay small at 2^30 elements.
"""

from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import scipy.fft

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: rows (or columns) per block of the reference and of the comparison
BLOCK = 1 << 11


@contextlib.contextmanager
def compile_log():
    """Count backend compiles, their seconds, and persistent-cache hits."""
    log = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == _COMPILE_EVENT:
            log["compiles"] += 1
            log["compile_s"] += secs

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            log["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise RuntimeError(f"{d} reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def rel_err(got, ref, block: int = 1 << 22) -> float:
    """max |got - ref| / max |ref|, in blocks so that the float64
    temporaries stay small at 2^30 elements, the blocks spread over the
    host's cores.  ``got`` is an array, or an (re, im) pair of arrays
    standing for a complex one."""
    pair = isinstance(got, tuple)
    parts = got if pair else (got,)
    if any(p.shape != ref.shape for p in parts):
        raise ValueError(f"shape {parts[0].shape} is not {ref.shape}")
    parts = [p.reshape(-1) for p in parts]
    ref = ref.reshape(-1)

    def one(i):
        r = ref[i:i + block]
        if pair:
            d = np.hypot(parts[0][i:i + block] - r.real,
                         parts[1][i:i + block] - r.imag)
        else:
            d = np.abs(parts[0][i:i + block] - r)
        return float(np.max(d)), float(np.max(np.abs(r)))

    with ThreadPoolExecutor(_workers()) as pool:
        found = list(pool.map(one, range(0, ref.size, block)))
    return max(n for n, _ in found) / max(d for _, d in found)


def _workers() -> int:
    return os.cpu_count() or 1


def _fft_axis_blocked(a: np.ndarray, axis: int, inverse: bool) -> None:
    """In-place c2c transform of complex128 ``a`` along ``axis``, a block
    of the other axes at a time (2D arrays)."""
    f = scipy.fft.ifft if inverse else scipy.fft.fft
    other = 1 - axis
    for i in range(0, a.shape[other], BLOCK):
        sl = [slice(None), slice(None)]
        sl[other] = slice(i, i + BLOCK)
        a[tuple(sl)] = f(a[tuple(sl)], axis=axis, workers=_workers())


def reference(kind: str, call: str, x):
    """numpy's answer, in float64/complex128, to one call of the front end.

    ``kind`` is "r2c" or "c2c"; ``call`` is "forward" (``rfftn``/``fftn``)
    or "inverse" (``irfftn``/``ifftn``).  ``x`` is a real array (r2c
    forward) or a complex array.  2D transforms run in blocks; other ranks
    in one call."""
    shape = x.shape
    if len(shape) != 2:
        if call == "forward":
            f = scipy.fft.rfftn if kind == "r2c" else scipy.fft.fftn
            return f(x.astype(np.float64 if kind == "r2c" else np.complex128),
                     workers=_workers())
        if kind == "r2c":
            n_last = 2 * (shape[-1] - 1)
            return scipy.fft.irfftn(x, s=shape[:-1] + (n_last,),
                                    workers=_workers())
        return scipy.fft.ifftn(x, workers=_workers())

    n0, n1 = shape
    if call == "forward":
        out_cols = n1 // 2 + 1 if kind == "r2c" else n1
        y = np.empty((n0, out_cols), np.complex128)
        row_fft = scipy.fft.rfft if kind == "r2c" else scipy.fft.fft
        cast = np.float64 if kind == "r2c" else np.complex128
        for i in range(0, n0, BLOCK):
            y[i:i + BLOCK] = row_fft(x[i:i + BLOCK].astype(cast), axis=1,
                                     workers=_workers())
        _fft_axis_blocked(y, 0, inverse=False)
        return y
    y = x.astype(np.complex128)
    _fft_axis_blocked(y, 0, inverse=True)
    if kind == "r2c":
        n_last = 2 * (n1 - 1)
        out = np.empty((n0, n_last), np.float64)
        for i in range(0, n0, BLOCK):
            out[i:i + BLOCK] = scipy.fft.irfft(y[i:i + BLOCK], n=n_last,
                                               axis=1, workers=_workers())
        return out
    _fft_axis_blocked(y, 1, inverse=True)
    return y


def host_value(out):
    """A front-end result on the host: an array, or an (re, im) pair of
    arrays."""
    if isinstance(out, (tuple, list)):
        return tuple(np.asarray(a) for a in jax.device_get(tuple(out)))
    return np.asarray(jax.device_get(out))
