"""Reduce a profiler trace (``.xplane.pb``) of the traced window to what
the per-layer readers in ``metrics/`` read.

The benchmark brackets every traced step with host spans of its own
(``jax.profiler.TraceAnnotation``): ``chipbench.step`` around the whole
step, ``chipbench.dispatch`` around the front-end calls until they return
and ``chipbench.block`` around the wait for the result.  The device side
comes from the TPU planes: one event per HLO operation on the ``XLA Ops``
line and one event per program on the ``XLA Modules`` line.

JAX's reader (``jax.profiler.ProfileData``) gives each event its name,
times and own stats, but not the stats of its metadata, where the TPU
profiler keeps the op's ``hlo_category``.  ``_op_categories`` reads those
from the protobuf wire format directly (the ``XSpace`` message of
``xplane.proto``).

The host's and the device's clocks in one trace disagree by about a
millisecond.  The device's times are shifted onto the host's by the
midpoint of what the spans allow: the first op cannot start before the
first dispatch starts, and the last op must end before the last wait ends.
"""

from __future__ import annotations

import dataclasses
import heapq
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
STEP, DISPATCH, BLOCK = (SPAN_PREFIX + s for s in ("step", "dispatch",
                                                   "block"))

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Op:
    """One HLO operation as it ran on one device."""
    name: str
    start_ns: float
    end_ns: float
    category: str
    module: str

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """The traced window: device operations and the benchmark's host
    spans, on the profiler's one clock."""
    ops: Dict[str, List[Op]]                     # device plane -> ops
    spans: List[Tuple[str, float, float]]        # (name, start, end)

    @property
    def window(self) -> Tuple[float, float]:
        steps = [s for s in self.spans if s[0] == STEP]
        if not steps:
            raise ValueError("the trace holds no chipbench.step span")
        return min(s[1] for s in steps), max(s[2] for s in steps)

    @property
    def window_ns(self) -> float:
        lo, hi = self.window
        return hi - lo

    @property
    def steps(self) -> int:
        return sum(1 for s in self.spans if s[0] == STEP)

    def device_ops(self, device: str) -> List[Op]:
        """The ops of one device that lie in the window (clipped)."""
        lo, hi = self.window
        return [dataclasses.replace(o, start_ns=max(o.start_ns, lo),
                                    end_ns=min(o.end_ns, hi))
                for o in self.ops[device]
                if o.end_ns > lo and o.start_ns < hi]


# -- the protobuf wire format, as far as the categories need it ---------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """(field number, value) of each field of one message; a
    length-delimited value is returned as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _map_values(entry: bytes) -> bytes:
    return dict(_fields(entry)).get(2, b"")


def _op_categories(raw: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each event name's ``hlo_category``, read from
    the event metadata.  XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 and .stat_metadata = 5 (maps, value = 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(raw):
        if num != 1:
            continue
        name, event_md, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                event_md.append(_map_values(v))
            elif f == 5:
                md = dict(_fields(_map_values(v)))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        cat_id = [k for k, v in stat_names.items() if v == "hlo_category"]
        cats = {}
        for md in event_md:
            ev_name, cat = "", ""
            for f, v in _fields(md):
                if f == 2:
                    ev_name = v.decode(errors="replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    if cat_id and stat.get(1) == cat_id[0]:
                        if 5 in stat:
                            cat = stat[5].decode()
                        elif 7 in stat:
                            cat = stat_names.get(stat[7], "")
            if cat:
                cats[ev_name] = cat
        out[name] = cats
    return out


def _clock_shift(ops: Dict[str, List[Op]], spans) -> float:
    """Nanoseconds to add to device times to put them on the host's
    clock: the midpoint of the shifts the first dispatch and the last
    wait allow."""
    firsts = [o[0].start_ns for o in ops.values() if o]
    lasts = [o[-1].end_ns for o in ops.values() if o]
    dispatches = [s[1] for s in spans if s[0] == DISPATCH]
    blocks = [s[2] for s in spans if s[0] == BLOCK]
    if not firsts or not dispatches or not blocks:
        return 0.0
    lo = min(dispatches) - min(firsts)
    hi = max(blocks) - max(lasts)
    return (lo + hi) / 2 if lo <= hi else lo


def _module_of(start: float, modules: List[Tuple[float, float, str]]
               ) -> str:
    for lo, hi, mod in modules:          # the program the op ran inside
        if lo <= start < hi:
            return mod
    return ""


def load(path) -> Trace:
    """Read one ``.xplane.pb``: events with JAX's own reader, the ops'
    categories from their metadata."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    categories = _op_categories(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = sorted(
                (e.start_ns, e.end_ns, e.name)
                for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            cats = categories.get(plane.name, {})
            ops[plane.name] = sorted(
                (Op(e.name, e.start_ns, e.end_ns, cats.get(e.name, ""),
                    _module_of(e.start_ns, modules))
                 for e in lines[OPS_LINE].events),
                key=lambda o: o.start_ns)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    spans.sort(key=lambda s: s[1])
    shift = _clock_shift(ops, spans)
    ops = {d: [dataclasses.replace(o, start_ns=o.start_ns + shift,
                                   end_ns=o.end_ns + shift) for o in dev]
           for d, dev in ops.items()}
    return Trace(ops, spans)


def find_xplane(trace_dir) -> Path:
    """The one ``.xplane.pb`` a trace directory holds."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


# -- interval arithmetic ------------------------------------------------------


def merge(intervals) -> List[Tuple[float, float]]:
    """The union of ``(start, end)`` intervals, as disjoint intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def length(intervals) -> float:
    return sum(hi - lo for lo, hi in merge(intervals))


def subtract(intervals, holes) -> List[Tuple[float, float]]:
    """``intervals`` minus ``holes`` (both any set of intervals)."""
    holes = merge(holes)
    out = []
    for lo, hi in merge(intervals):
        cur = lo
        for hlo, hhi in holes:
            if hhi <= cur or hlo >= hi:
                continue
            if hlo > cur:
                out.append((cur, hlo))
            cur = max(cur, hhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


# -- what several readers share -------------------------------------------------


def busy_ns(trace: Trace, device: str) -> float:
    """Time in the window during which some op ran on ``device``."""
    return length((o.start_ns, o.end_ns) for o in trace.device_ops(device))


def per_step_ms(trace: Trace, device_ns: Dict[str, float],
                how: str = "mean") -> Optional[float]:
    """A per-device time in ns, as milliseconds per step: the mean over
    the devices, or the ``max`` (the busiest device)."""
    if not device_ns or trace.steps == 0:
        return None
    vals = list(device_ns.values())
    total = max(vals) if how == "max" else sum(vals) / len(vals)
    return total / trace.steps / 1e6


def category_ns(trace: Trace, categories) -> Dict[str, float]:
    """Per device, the summed time of ops in any of ``categories``."""
    out = {}
    for dev in trace.ops:
        out[dev] = sum(o.dur_ns for o in trace.device_ops(dev)
                       if o.category in categories)
    return out


def idle_gaps(trace: Trace, device: str) -> List[Tuple[float, float]]:
    """The stretches of the window in which no op ran on ``device``."""
    lo, hi = trace.window
    return subtract([(lo, hi)], [(o.start_ns, o.end_ns)
                                 for o in trace.device_ops(device)])


def host_activity(trace: Trace, t: float) -> str:
    """Which benchmark host span held the time ``t``: the innermost of
    dispatch and block, else "step" (between them) or "outside"."""
    label = "outside"
    for name, lo, hi in trace.spans:
        if lo <= t < hi:
            if name in (DISPATCH, BLOCK):
                return name[len(SPAN_PREFIX):]
            label = name[len(SPAN_PREFIX):]
    return label


def short_name(key: str) -> str:
    """``%fusion.5 = f32[...] fusion(...), ...`` with its category becomes
    ``fusion.5 [loop fusion]``."""
    name, _, category = key.partition("\0")
    name = name.split(" = ", 1)[0].lstrip("%")
    return f"{name} [{category}]" if category else name


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (HLO name, seconds per device,
    over the window) and the longest idle gaps of the devices, each named
    by what the host was doing."""
    per_name: Dict[str, float] = defaultdict(float)
    gaps = []
    for dev in trace.ops:
        for o in trace.device_ops(dev):
            per_name[f"{o.name}\0{o.category}"] += o.dur_ns
        for lo, hi in idle_gaps(trace, dev):
            gaps.append((hi - lo, host_activity(trace, (lo + hi) / 2)))
    n = max(len(trace.ops), 1)
    ops = heapq.nlargest(top, per_name.items(), key=lambda kv: kv[1])
    return {"device_ops": [[short_name(k), v / n / 1e9] for k, v in ops],
            "idle_gaps": [[label, ns / 1e9]
                          for ns, label in heapq.nlargest(top, gaps)]}


# -- what the compiler's op categories mean to this benchmark's layers --------
#
# The TPU trace gives every op an ``hlo_category``.  These sets name the
# categories of each layer; PERF.md lists the categories the chip's traces
# hold.

#: the local 1D stages' DFT matmuls (XLA lowers a dot to a convolution)
MATMUL = frozenset({"convolution", "convolution fusion"})
#: copies and transposes
RELAYOUT = frozenset({"data formatting"})
#: the exchanges between chips
EXCHANGE = frozenset({"all-to-all", "all-gather", "collective-permute",
                      "all-reduce"})


def exchange_intervals(trace: Trace, device: str):
    return [(o.start_ns, o.end_ns) for o in trace.device_ops(device)
            if o.category in EXCHANGE]
