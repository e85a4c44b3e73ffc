"""The program's own named scopes and host spans, read from the trace the
harness just wrote.

The program names parts of its compiled programs with ``jax.named_scope``
(``repro_r2c``, ``repro_exchange``) and wraps
each public front-end call in a host span (``repro.fftn``, ``repro.ifftn``,
``repro.rfftn``, ``repro.irfftn``) with the executor's dispatch inside it
(``repro.execute``).  ``xplane.load`` keeps neither, and a reader is handed
only its ``xplane.Trace``.  So this module finds the ``.xplane.pb`` that
``harness.traced_window`` wrote (the newest under ``chipbench/traces/*/``,
found from this file's own place), checks that it holds the trace the
reader was handed, and reads from it:

* each device op's scope path: the ``tf_op`` stat of the op's metadata,
  which holds the HLO ``op_name``;
* the host events whose names start with ``repro.``.

Attribution: an op belongs to the innermost ``repro_*`` scope of its
``op_name``.  XLA gives a fusion the metadata of its root, so a fusion
counts by its root's scope.  A program without these scopes and spans (an
older one) reads as absent, never as zero.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

from chipbench import xplane

#: where ``harness.traced_window`` writes a cell's trace when the harness
#: runs from this module's own checkout (``run.main``'s default root); a
#: harness run with another root writes elsewhere, and ``for_trace`` then
#: fails on finding no trace or the wrong one
TRACES = Path(__file__).resolve().parent / "traces"

R2C, EXCHANGE = "repro_r2c", "repro_exchange"
SPAN_PREFIX = "repro."
#: the front-end spans; ``repro.execute`` is the child inside each
FRONT = frozenset(SPAN_PREFIX + f for f in ("fftn", "ifftn", "rfftn",
                                            "irfftn"))

#: the device op stat that carries the HLO ``op_name``
OP_NAME_STAT = "tf_op"

#: a ``repro_*`` component of an ``op_name`` path; the trace's ``tf_op``
#: ends the path with ``:<op type>``, often empty
_SCOPE = re.compile(r"(?:^|/)(repro_[A-Za-z0-9_]+)(?=[/:]|$)")


@dataclasses.dataclass(frozen=True)
class Span:
    """One host span of the program, on the host thread that emitted it."""
    name: str
    start_ns: float
    end_ns: float
    thread: int


@dataclasses.dataclass(frozen=True)
class Scopes:
    """What the program's instrumentation left in one trace."""
    scope: Dict[str, Dict[str, str]]     # device plane -> op name -> scope
    spans: Tuple[Span, ...]              # host spans named repro.*

    @property
    def instrumented(self) -> bool:
        return bool(self.spans) or any(any(s.values())
                                       for s in self.scope.values())

    def scope_of(self, device: str, op: xplane.Op) -> str:
        return self.scope.get(device, {}).get(op.name, "")


def innermost_scope(op_name: str) -> str:
    """The innermost ``repro_*`` component of an ``op_name`` path, or ""."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def _op_scopes(raw: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each op name's innermost ``repro_*`` scope, from
    the ``tf_op`` stat of the event metadata, a string or a reference to
    one (field numbers as in ``xplane._op_categories``)."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in xplane._fields(raw):
        if num != 1:
            continue
        name, event_md, stat_names = "", [], {}
        for f, v in xplane._fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                event_md.append(xplane._map_values(v))
            elif f == 5:
                md = dict(xplane._fields(xplane._map_values(v)))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        if not name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        ids = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        scopes = {}
        for md in event_md:
            ev_name, scope = "", ""
            for f, v in xplane._fields(md):
                if f == 2:
                    ev_name = v.decode(errors="replace")
                elif f == 5:
                    stat = dict(xplane._fields(v))
                    if stat.get(1) not in ids:
                        continue
                    if 5 in stat:
                        scope = innermost_scope(stat[5].decode(
                            errors="replace"))
                    elif 7 in stat:
                        scope = innermost_scope(stat_names.get(stat[7], ""))
            scopes[ev_name] = scope
        out[name] = scopes
    return out


def _host_spans(raw: bytes) -> Tuple[Span, ...]:
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, ln in enumerate(plane.lines):
            spans += [Span(e.name, e.start_ns, e.end_ns, thread)
                      for e in ln.events if e.name.startswith(SPAN_PREFIX)]
    return tuple(sorted(spans, key=lambda s: s.start_ns))


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime_ns: int) -> Tuple[xplane.Trace, Scopes]:
    raw = Path(path).read_bytes()
    return xplane.load(path), Scopes(_op_scopes(raw), _host_spans(raw))


def newest_xplane(traces: Path) -> Path:
    """The newest ``.xplane.pb`` under ``<traces>/<cell>/``."""
    found = list(Path(traces).glob("*/**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {traces}/*/")
    return max(found, key=lambda p: p.stat().st_mtime_ns)


def _same_trace(a: xplane.Trace, b: xplane.Trace) -> bool:
    """The same device ops per plane and the same window, device times
    shifted onto the host clock as ``xplane.load`` shifts them."""
    if ({d: len(o) for d, o in a.ops.items()}
            != {d: len(o) for d, o in b.ops.items()}):
        return False
    if a.window != b.window:
        return False
    return all((a.ops[d][0].start_ns, a.ops[d][-1].end_ns)
               == (b.ops[d][0].start_ns, b.ops[d][-1].end_ns)
               for d in a.ops if a.ops[d])


def for_trace(trace: xplane.Trace) -> Scopes:
    """The scopes and spans of the trace file that holds ``trace``;
    raises if the newest trace file is another trace."""
    path = newest_xplane(TRACES)
    loaded, scopes = _parse(str(path), path.stat().st_mtime_ns)
    if not _same_trace(loaded, trace):
        raise ValueError(f"{path} is not the trace the reader was handed")
    return scopes


# -- what the readers compute -------------------------------------------------


def scope_ns(trace: xplane.Trace, scopes: Scopes, scope: str,
             skip_categories: Iterable[str] = ()) -> Dict[str, float]:
    """Per device, the summed time of the window's ops in ``scope``."""
    skip = frozenset(skip_categories)
    return {d: sum(o.dur_ns for o in trace.device_ops(d)
                   if scopes.scope_of(d, o) == scope
                   and o.category not in skip)
            for d in trace.ops}


def front_spans(trace: xplane.Trace, scopes: Scopes) -> List[Span]:
    """The front-end spans that lie in the window."""
    lo, hi = trace.window
    return [s for s in scopes.spans
            if s.name in FRONT and s.end_ns > lo and s.start_ns < hi]


def self_ns(span: Span, scopes: Scopes) -> float:
    """A span's duration less what its child spans (``repro.*`` spans of
    its thread inside it) cover."""
    children = [(c.start_ns, c.end_ns) for c in scopes.spans
                if c is not span and c.thread == span.thread
                and span.start_ns <= c.start_ns and c.end_ns <= span.end_ns]
    return span.end_ns - span.start_ns - xplane.length(children)
