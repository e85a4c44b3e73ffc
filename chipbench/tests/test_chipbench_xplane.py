"""The trace reduction, on a trace recorded on the chip and on a small
trace written out by hand.

``data/r2c2d_16384.local.xplane.pb`` is the first three steps of a traced
run of that cell on one TPU v5e (``TPU v5 lite``): the device's ``XLA
Modules`` and ``XLA Ops`` lines with each op's ``hlo_category``, and the
benchmark's host spans.  The expected numbers were checked by hand with a
plain sum over the events (``jax.profiler.ProfileData``), not with the
code under test."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import harness, work, xplane
from chipbench.tests import helpers

DATA = Path(__file__).parent / "data" / "r2c2d_16384.local.xplane.pb"
DEV = "/device:TPU:0"

# by hand, from the recorded trace
STEPS = 3
WINDOW_NS = 224963536.0
BUSY_NS = 220235067.0
MATMUL_NS = 108802037.0            # 48 "convolution fusion" ops
RELAYOUT_NS = 67536719.0           # 39 "data formatting" ops
DISPATCH_S = [608630e-9, 426920e-9, 580260e-9]


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(DATA)


@pytest.fixture(scope="module")
def ctx():
    cell_work = work.step_work((16384, 16384), "r2c", ["forward"], 1)
    return {"dispatch_s": DISPATCH_S, "work": cell_work,
            "peaks": work.peaks_for("TPU v5 lite")}


def test_recorded_trace_shape(recorded):
    assert list(recorded.ops) == [DEV]
    assert recorded.steps == STEPS
    assert recorded.window_ns == WINDOW_NS
    ops = recorded.device_ops(DEV)
    assert len(ops) == 285
    assert {o.module for o in ops} == {
        "jit__execute_local(11344105262163368039)"}
    cats = {o.category for o in ops}
    assert cats == {"convolution fusion", "data formatting", "loop fusion",
                    "custom fusion", "slice", "copy-start", "copy-done"}


def test_clock_shift_puts_ops_inside_the_window(recorded):
    lo, hi = recorded.window
    ops = recorded.ops[DEV]
    assert lo <= ops[0].start_ns and ops[-1].end_ns <= hi
    assert xplane.busy_ns(recorded, DEV) == BUSY_NS


@pytest.mark.parametrize("metric,expected", [
    ("frontend_dispatch_ms", sum(DISPATCH_S) / 3 * 1e3),
    ("dft_matmul_ms", MATMUL_NS / STEPS / 1e6),
    ("relayout_ms", RELAYOUT_NS / STEPS / 1e6),
    ("device_idle_share", 100 * (1 - BUSY_NS / WINDOW_NS)),
    # bytes bound: 2^28 f32 read + 2^14 x 8193 complex written, 819 GB/s
    ("hbm_roofline_share",
     100 * ((2 ** 28 * 4 + 16384 * 8193 * 8) / 819e9)
     / (BUSY_NS / STEPS / 1e9)),
    # the local plan runs no program besides its executor, no exchange
    ("frontend_device_ms", None),
    ("exchange_ms", None),
    ("exchange_exposed_ms", None),
])
def test_each_reader_on_the_recorded_trace(recorded, ctx, metric, expected):
    value = harness._load_reader(helpers.REPO, metric)(recorded, ctx)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected, rel=1e-9)


def test_recorded_numbers_as_read(recorded, ctx):
    """The same numbers, as rounded for PERF.md."""
    read = {m: harness._load_reader(helpers.REPO, m)(recorded, ctx)
            for m in ("dft_matmul_ms", "relayout_ms", "device_idle_share",
                      "hbm_roofline_share")}
    assert round(read["dft_matmul_ms"], 3) == 36.267
    assert round(read["relayout_ms"], 3) == 22.512
    assert round(read["device_idle_share"], 3) == 2.102
    assert round(read["hbm_roofline_share"], 3) == 3.572


def test_breakdown_of_the_recorded_trace(recorded):
    b = xplane.breakdown(recorded)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    name, secs = b["device_ops"][0]
    assert name == "fusion.5 [loop fusion]"
    assert secs > 0
    assert all(label in ("dispatch", "block", "step")
               for label, _ in b["idle_gaps"])
    assert sum(s for _, s in b["idle_gaps"]) <= (WINDOW_NS - BUSY_NS) / 1e9


# -- a trace written out by hand: two chips with exchanges ---------------------

def _hand_trace() -> bytes:
    """Two steps on two chips, times in microseconds on one clock.

    Host: step 0 = [0, 100), dispatch [0, 5), block [5, 100);
          step 1 = [100, 200), dispatch [100, 105), block [105, 200).
    Chip 0, per step at offset s: conv [10, 40), all-to-all [40, 60),
          a loop fusion [50, 55) overlapping it, copy [60, 70) in the
          executor; a slice [70, 75) in another program (the crop).
    Chip 1: the same with the all-to-all [40, 80) and the copy
          [80, 90); no crop.
    """
    def ev(mid, lo, hi):
        return (f"events {{ metadata_id: {mid} offset_ps: {lo * 10**6} "
                f"duration_ps: {(hi - lo) * 10**6} }}")

    md = {1: ("conv", "convolution fusion"), 2: ("a2a", "all-to-all"),
          3: ("loop", "loop fusion"), 4: ("copy", "data formatting"),
          5: ("slice", "slice")}
    planes = []
    for chip, a2a_end in ((0, 60), (1, 80)):
        ops, mods = [], []
        for s in (0, 100):
            ops += [ev(1, s + 10, s + 40), ev(2, s + 40, s + a2a_end),
                    ev(4, s + a2a_end, s + a2a_end + 10)]
            if chip == 0:
                ops += [ev(3, s + 50, s + 55), ev(5, s + 70, s + 75)]
                mods += [ev(10, s + 10, s + 70), ev(11, s + 70, s + 75)]
            else:
                mods += [ev(10, s + 10, s + 90)]
        meta = "\n".join(
            f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" '
            f'stats {{ metadata_id: 1 str_value: "{c}" }} }} }}'
            for k, (n, c) in md.items())
        meta += ('\nevent_metadata { key: 10 value { id: 10 name: '
                 '"jit_execute_slab(1)" } }'
                 '\nevent_metadata { key: 11 value { id: 11 name: '
                 '"jit_slice(2)" } }')
        planes.append(
            f'planes {{ id: {chip + 1} name: "/device:TPU:{chip}"\n'
            f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n'
            + "\n".join(mods) + '}\n'
            f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n'
            + "\n".join(ops) + '}\n' + meta +
            '\nstat_metadata { key: 1 value { id: 1 name: "hlo_category" } }'
            '\n}')
    host = ['planes { id: 9 name: "/host:CPU" lines { id: 1 name: "main" '
            'timestamp_ns: 0']
    spans = {21: "chipbench.step", 22: "chipbench.dispatch",
             23: "chipbench.block"}
    for s in (0, 100):
        host += [ev(21, s, s + 100), ev(22, s, s + 5),
                 ev(23, s + 5, s + 100)]
    host.append("}")
    host += [f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
             for k, n in spans.items()]
    host.append("}")
    raw = ProfileData.text_proto_to_serialized_xspace(
        "\n".join(planes + host))
    return raw


@pytest.fixture(scope="module")
def hand(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(_hand_trace())
    return xplane.load(path)


def test_hand_trace_clock_and_window(hand):
    assert hand.steps == 2
    assert hand.window_ns == 200e3
    # first op at 10 us, first dispatch at 0; last op ends at 190 us, last
    # wait at 200: the shift lies in [-10, +10] us; its midpoint is 0
    assert hand.ops["/device:TPU:0"][0].start_ns == 10e3


@pytest.mark.parametrize("metric,expected", [
    # chip 0 busy: [10, 75) = 65 us a step; chip 1: [10, 90) = 80 us
    ("device_idle_share", 100 * (1 - (130 + 160) / 2 / 200)),
    ("dft_matmul_ms", 30e-3),                  # 30 us a step on each chip
    ("relayout_ms", 10e-3),
    # the busiest chip's all-to-all: chip 1, 40 us a step
    ("exchange_ms", 40e-3),
    # chip 0 hides 5 of its 20 us under the loop fusion; chip 1 hides none
    ("exchange_exposed_ms", 40e-3),
    # programs other than the executor: chip 0's slice, 5 us a step;
    # mean over the two chips
    ("frontend_device_ms", 2.5e-3),
])
def test_each_reader_on_the_hand_trace(hand, metric, expected):
    ctx = {"dispatch_s": [5e-6, 5e-6],
           "work": {"ops": 1.0, "bytes": 819e9 * 36e-6},
           "peaks": work.peaks_for("TPU v5 lite")}
    value = harness._load_reader(helpers.REPO, metric)(hand, ctx)
    assert value == pytest.approx(expected, rel=1e-9)


def test_roofline_share_on_the_hand_trace(hand):
    # least time 36 us (bytes bound); busy per step (65 + 80) / 2 = 72.5 us
    ctx = {"work": {"ops": 1.0, "bytes": 819e9 * 36e-6},
           "peaks": work.peaks_for("TPU v5 lite")}
    value = harness._load_reader(helpers.REPO, "hbm_roofline_share")(hand,
                                                                     ctx)
    assert value == pytest.approx(100 * 36 / 72.5, rel=1e-9)


def test_exposed_part_of_an_exchange(hand):
    d0 = "/device:TPU:0"
    ex = xplane.exchange_intervals(hand, d0)
    others = [(o.start_ns, o.end_ns) for o in hand.device_ops(d0)
              if o.category not in xplane.EXCHANGE]
    assert xplane.length(ex) == 40e3
    assert xplane.length(xplane.subtract(ex, others)) == 30e3


def test_idle_gaps_are_named_by_the_host_span(hand):
    gaps = xplane.idle_gaps(hand, "/device:TPU:1")
    assert gaps == [(0.0, 10e3), (90e3, 110e3), (190e3, 200e3)]
    # named by the host span at each gap's midpoint: 5, 100 and 195 us
    assert [xplane.host_activity(hand, (a + b) / 2) for a, b in gaps] == [
        "block", "dispatch", "block"]


@pytest.mark.parametrize("intervals,expected", [
    ([], []),
    ([(0, 1), (1, 2)], [(0, 2)]),
    ([(0, 3), (1, 2), (5, 6)], [(0, 3), (5, 6)]),
    ([(2, 2), (4, 3)], []),
])
def test_merge(intervals, expected):
    assert xplane.merge(intervals) == expected


def test_subtract():
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]
    assert xplane.subtract([(0, 10)], [(-1, 11)]) == []
