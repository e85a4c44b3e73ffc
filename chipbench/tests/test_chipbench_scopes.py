"""The program's scopes and spans, read from a trace: on a trace written
out by hand, on the recorded traces without scopes, and on a trace of the
instrumented program recorded on the chip.

``data/r2c2d_16384.local.scopes.xplane.pb`` is the first three steps of a
traced run of that cell on one TPU v5e (``TPU v5 lite``), trimmed as
``r2c2d_16384.local.xplane.pb`` was, keeping each op's ``tf_op`` stat and
the program's ``repro.*`` host spans.  Its expected numbers were checked
by hand with a plain sum over the events (``jax.profiler.ProfileData``
and the ``tf_op`` strings), not with the code under test."""

import json
import os
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, work, xplane
from chipbench.tests import helpers
from chipbench.tests.test_chipbench_xplane import _hand_trace

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "r2c2d_16384.local.xplane.pb"
RECORDED_SCOPES = DATA / "r2c2d_16384.local.scopes.xplane.pb"
NEW = ("r2c_pack_unpack_ms", "exchange_pack_ms", "frontend_self_ms",
       "frontend_idle_ms")
CTX = {"dispatch_s": [8e-6, 8e-6]}


def _read(metric, trace):
    return harness._load_reader(helpers.REPO, metric)(trace, CTX)


def _place(traces: Path, cell: str, raw: bytes, mtime: int = 0) -> Path:
    """Write ``raw`` where ``harness.traced_window`` writes a cell's
    trace; return the path."""
    path = traces / cell / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(raw)
    if mtime:
        os.utime(path, ns=(mtime, mtime))
    return path


@pytest.fixture
def traces(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACES", tmp_path / "traces")
    return tmp_path / "traces"


# -- a trace written out by hand ----------------------------------------------

#: op metadata: HLO name, category, ``tf_op`` (None: no such stat)
OPS = {
    1: ("%slice.1 = f32[8]{0} slice(f32[16]{0} %x)", "slice",
        "jit(_execute_local)/repro_r2c/slice"),
    2: ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %slice.1)",
        "convolution fusion", "jit(_execute_local)/dot_general"),
    # a scope that no reader reads
    3: ("%copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.2)", "data formatting",
        "jit(_execute_local)/repro_other/transpose"),
    4: ("%all-to-all.4 = f32[8]{0} all-to-all(f32[8]{0} %copy.3)",
        "all-to-all",
        "jit(execute_slab)/shard_map/repro_exchange/all_to_all"),
    5: ("%copy.5 = f32[8]{0} copy(f32[8]{0} %all-to-all.4)",
        "data formatting",
        "jit(execute_slab)/shard_map/repro_exchange/concatenate"),
    # a fusion of exchange packing and an r2c unpack whose root is the
    # unpack's add: it carries its root's op_name and counts as r2c
    6: ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %copy.5, f32[8]{0} "
        "%fusion.2)", "loop fusion",
        "jit(execute_slab)/repro_exchange/repro_r2c/add"),
    7: ("%copy.7 = f32[8]{0} copy(f32[8]{0} %fusion.6)", "data formatting",
        None),
}
#: per chip, (metadata id, start, end) in microseconds from a step's start
CHIP_OPS = {
    0: [(1, 4, 6), (2, 10, 40), (3, 40, 45), (4, 50, 60), (5, 60, 64),
        (6, 64, 70), (7, 70, 72)],
    1: [(1, 4, 6), (2, 10, 40), (3, 40, 45), (4, 50, 60), (5, 60, 68),
        (6, 68, 70), (7, 70, 72)],
}


def _ev(mid, lo, hi):
    return (f"events {{ metadata_id: {mid} offset_ps: {lo * 10**6} "
            f"duration_ps: {(hi - lo) * 10**6} }}")


def _scoped_trace() -> bytes:
    """Two steps at 0 and 100 us on two chips, with the program's scopes
    and spans.

    Host, per step at s: step [s, s+100), dispatch [s, s+8), block
    [s+8, s+76); ``repro.rfftn`` [s+1, s+7) with ``repro.execute``
    [s+2, s+5) inside it, on the caller's thread.  The first op starts at
    4 us and the last ends at 172, so the clock shift is 0 (it lies in
    [-4, +4] us).  Chip ops as ``CHIP_OPS``, each step the same.
    """
    meta = []
    for k, (name, cat, tf_op) in OPS.items():
        stats = f'stats {{ metadata_id: 1 str_value: "{cat}" }}'
        if tf_op is not None:
            stats += f' stats {{ metadata_id: 2 str_value: "{tf_op}" }}'
        meta.append(f'event_metadata {{ key: {k} value {{ id: {k} '
                    f'name: "{name}" {stats} }} }}')
    meta.append('event_metadata { key: 10 value { id: 10 name: '
                '"jit_execute_slab(1)" } }')
    planes = []
    for chip, ops in CHIP_OPS.items():
        evs = [_ev(m, s + lo, s + hi) for s in (0, 100) for m, lo, hi in ops]
        mods = [_ev(10, s + 4, s + 72) for s in (0, 100)]
        planes.append(
            f'planes {{ id: {chip + 1} name: "/device:TPU:{chip}"\n'
            'lines { id: 1 name: "XLA Modules" timestamp_ns: 0\n'
            + "\n".join(mods) + '}\n'
            'lines { id: 2 name: "XLA Ops" timestamp_ns: 0\n'
            + "\n".join(evs) + '}\n' + "\n".join(meta) +
            '\nstat_metadata { key: 1 value { id: 1 name: "hlo_category" } }'
            '\nstat_metadata { key: 2 value { id: 2 name: "tf_op" } }\n}')
    spans = {21: "chipbench.step", 22: "chipbench.dispatch",
             23: "chipbench.block", 24: "repro.rfftn", 25: "repro.execute"}
    host = ['planes { id: 9 name: "/host:CPU" lines { id: 1 name: "main" '
            'timestamp_ns: 0']
    for s in (0, 100):
        host += [_ev(21, s, s + 100), _ev(22, s, s + 8),
                 _ev(23, s + 8, s + 76), _ev(24, s + 1, s + 7),
                 _ev(25, s + 2, s + 5)]
    host.append("}")
    host += [f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
             for k, n in spans.items()]
    host.append("}")
    return ProfileData.text_proto_to_serialized_xspace(
        "\n".join(planes + host))


@pytest.fixture
def hand(traces):
    path = _place(traces, "hand.cell", _scoped_trace())
    return xplane.load(path)


def test_hand_trace_scopes_and_spans(hand):
    s = scopes.for_trace(hand)
    assert s.instrumented
    d0 = "/device:TPU:0"
    assert [s.scope_of(d0, o) for o in hand.ops[d0][:7]] == [
        "repro_r2c", "", "repro_other", "repro_exchange",
        "repro_exchange", "repro_r2c", ""]
    assert [(x.name, x.start_ns, x.end_ns) for x in s.spans[:2]] == [
        ("repro.rfftn", 1e3, 7e3), ("repro.execute", 2e3, 5e3)]
    assert hand.ops[d0][0].start_ns == 4e3          # no clock shift


@pytest.mark.parametrize("metric,expected", [
    # chip 0: slice 2 + fusion.6 6 us a step; chip 1: 2 + 2; the mean
    ("r2c_pack_unpack_ms", 6e-3),
    # the copy in repro_exchange, not its all-to-all, nor fusion.6 (its
    # root is in repro_r2c); the busiest chip's: chip 1, 8 us
    ("exchange_pack_ms", 8e-3),
    # rfftn 6 us less its execute child 3 us
    ("frontend_self_ms", 3e-3),
    # idle inside rfftn [s+1, s+7): [s+1, s+4) and [s+6, s+7) on each chip
    ("frontend_idle_ms", 4e-3),
])
def test_each_reader_on_the_hand_trace(hand, metric, expected):
    assert _read(metric, hand) == pytest.approx(expected, rel=1e-9)


def test_a_fusion_counts_by_its_roots_scope(hand):
    s = scopes.for_trace(hand)
    r2c = scopes.scope_ns(hand, s, scopes.R2C)
    exchange = scopes.scope_ns(hand, s, scopes.EXCHANGE)
    # fusion.6 (6 us a step on chip 0) is in r2c only, whole
    assert r2c["/device:TPU:0"] == 2 * (2e3 + 6e3)
    assert exchange["/device:TPU:0"] == 2 * (10e3 + 4e3)


def test_a_scope_no_reader_reads_counts_in_none(hand):
    """copy.3 (5 us a step) is in ``repro_other``: in neither r2c nor
    exchange, and not among the ops no scope covers."""
    s = scopes.for_trace(hand)
    d0 = "/device:TPU:0"
    split = {sc: scopes.scope_ns(hand, s, sc)[d0]
             for sc in ("", "repro_other", scopes.R2C, scopes.EXCHANGE)}
    assert split["repro_other"] == 2 * 5e3
    assert split[""] == 2 * (30e3 + 2e3)          # fusion.2 and copy.7
    assert sum(split.values()) == sum(o.dur_ns for o in hand.ops[d0])


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/repro_r2c/add", "repro_r2c"),
    ("jit(_execute_local)/repro_r2c/add:", "repro_r2c"),     # as tf_op has it
    ("jit(f)/repro_exchange:", "repro_exchange"),
    ("jit(f)/repro_exchange/repro_r2c/add", "repro_r2c"),
    ("jit(f)/shard_map/repro_exchange/all_to_all", "repro_exchange"),
    ("jit(f)/repro_exchange", "repro_exchange"),
    ("jit(f)/dot_general", ""),
    ("jit(f)/not_repro_r2c/add", ""),
    ("", ""),
])
def test_innermost_scope(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


# -- traces without scopes: every reader reads absent -------------------------


@pytest.mark.parametrize("metric", NEW)
def test_readers_on_the_recorded_trace_without_scopes(traces, metric):
    _place(traces, "r2c2d_16384.local", RECORDED.read_bytes())
    assert _read(metric, xplane.load(RECORDED)) is None


@pytest.mark.parametrize("metric", NEW)
def test_readers_on_a_hand_trace_without_scopes(traces, metric):
    path = _place(traces, "hand.cell", _hand_trace())
    assert _read(metric, xplane.load(path)) is None


# -- finding the trace --------------------------------------------------------


def test_the_newest_trace_is_read(traces):
    old = _place(traces, "a.cell", RECORDED.read_bytes(), mtime=10 ** 18)
    new = _place(traces, "b.cell", _scoped_trace(), mtime=2 * 10 ** 18)
    assert scopes.newest_xplane(traces) == new
    assert scopes.for_trace(xplane.load(new)).instrumented
    with pytest.raises(ValueError, match="not the trace"):
        scopes.for_trace(xplane.load(old))


def test_a_trace_that_does_not_match_is_refused(traces, hand):
    # the same file, rewritten with another trace after the reader's was
    # loaded
    _place(traces, "hand.cell", _hand_trace())
    with pytest.raises(ValueError, match="not the trace"):
        scopes.for_trace(hand)


def test_no_trace_file_is_refused(traces):
    with pytest.raises(FileNotFoundError):
        scopes.newest_xplane(traces)


def test_traces_are_where_the_harness_writes_them():
    assert scopes.TRACES == helpers.BENCH / "traces"


def test_a_file_is_parsed_once(traces):
    path = _place(traces, "a.cell", _scoped_trace())
    trace = xplane.load(path)
    scopes._parse.cache_clear()
    for metric in NEW:
        _read(metric, trace)
    assert scopes._parse.cache_info().misses == 1


def test_benchmark_lists_the_new_metrics():
    bench = json.loads((helpers.REPO / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    both = ["r2c2d_16384.local", "r2c2d_32768.slab4"]
    for name in NEW:
        m = per_layer[name]
        assert (m["moves"], m["better"], m["unit"]) == (
            "transform_ms", "lower", "ms")
        assert m["workloads"] == (["r2c2d_32768.slab4"]
                                  if name == "exchange_pack_ms" else both)


# -- the instrumented local cell, recorded on the chip ------------------------

# by hand, from the recorded trace (3 steps; clock shift 1221145.5 ns)
REC_STEPS = 3
REC_WINDOW_NS = 225172330.0
REC_BUSY_NS = 220234045.0
REC_R2C_NS = 34057036.0        # 36 ops whose tf_op holds repro_r2c
REC_SELF_NS = 500179.0         # 3 repro.rfftn spans less their repro.execute
REC_IDLE_IN_FRONT_NS = 1855859.0


@pytest.fixture
def recorded(traces):
    path = _place(traces, "r2c2d_16384.local", RECORDED_SCOPES.read_bytes())
    return xplane.load(path)


def test_recorded_scopes_trace_shape(recorded):
    assert recorded.steps == REC_STEPS
    assert recorded.window_ns == REC_WINDOW_NS
    assert len(recorded.device_ops("/device:TPU:0")) == 285
    assert xplane.busy_ns(recorded, "/device:TPU:0") == REC_BUSY_NS
    s = scopes.for_trace(recorded)
    assert [x.name for x in s.spans] == ["repro.rfftn", "repro.execute"] * 3
    found = {v for v in s.scope["/device:TPU:0"].values()}
    assert found == {"", "repro_r2c"}
    assert sum(s.scope_of("/device:TPU:0", o) == "repro_r2c"
               for o in recorded.device_ops("/device:TPU:0")) == 36


def test_recorded_busy_time_no_scope_covers(recorded):
    """The scope split of PERF.md's breakdown: every busy ns of the chip
    is either in ``repro_r2c`` or in no scope (one chip: no exchange)."""
    s = scopes.for_trace(recorded)
    d0 = "/device:TPU:0"
    unscoped = scopes.scope_ns(recorded, s, "")[d0]
    assert unscoped == REC_BUSY_NS - REC_R2C_NS
    assert round(unscoped / REC_STEPS / 1e6, 2) == 62.06
    assert round(100 * unscoped / REC_BUSY_NS, 1) == 84.5


@pytest.mark.parametrize("metric,expected", [
    ("r2c_pack_unpack_ms", REC_R2C_NS / REC_STEPS / 1e6),
    ("exchange_pack_ms", None),             # no exchange on one chip
    ("frontend_self_ms", REC_SELF_NS / REC_STEPS / 1e6),
    ("frontend_idle_ms", REC_IDLE_IN_FRONT_NS / REC_STEPS / 1e6),
])
def test_each_reader_on_the_recorded_scopes_trace(recorded, metric,
                                                  expected):
    value = _read(metric, recorded)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("cell_name", ["r2c2d_16384.local",
                                       "r2c2d_32768.slab4"])
def test_the_harness_reads_the_new_metrics(recorded, cell_name):
    """Through ``harness.read_per_layer``, as a traced run reads them: each
    new metric the cell lists is reported as its reader reads it; the
    one-chip trace has no exchange, so ``exchange_pack_ms`` is left out."""
    cell = harness.load_cell(helpers.REPO, cell_name)
    ctx = dict(CTX, peaks=work.peaks_for("TPU v5 lite"),
               work=work.step_work(cell.shape, cell.kind, cell.calls,
                                   cell.chips))
    out = harness.read_per_layer(cell, recorded, ctx)
    for metric in ("r2c_pack_unpack_ms", "frontend_self_ms",
                   "frontend_idle_ms"):
        assert out[metric] == {"value": _read(metric, recorded),
                               "unit": "ms"}
    assert "exchange_pack_ms" not in out
    assert out["dft_matmul_ms"]["value"] == pytest.approx(36.273, abs=1e-3)


def test_recorded_scopes_numbers_as_read(recorded):
    """The same numbers, as rounded for PERF.md."""
    read = {m: _read(m, recorded) for m in NEW if m != "exchange_pack_ms"}
    assert round(read["r2c_pack_unpack_ms"], 3) == 11.352
    assert round(read["frontend_self_ms"], 3) == 0.167
    assert round(read["frontend_idle_ms"], 3) == 0.619
    # the scope-free readers read the instrumented trace as before
    assert round(_read("dft_matmul_ms", recorded), 3) == 36.273
