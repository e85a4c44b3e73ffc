"""``dft_fused_ms``: the fused kernel's device time, read from the
``repro_dft_fused`` scope of a trace written out by hand, and absent from
traces of a program without the kernel."""

import json

import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, xplane
from chipbench.tests import helpers
from chipbench.tests.test_chipbench_scopes import (RECORDED,
                                                   RECORDED_SCOPES, _ev,
                                                   _place)

METRIC = "dft_fused_ms"
CTX = {"dispatch_s": [8e-6, 8e-6]}

#: op metadata: HLO name, category, ``tf_op``
OPS = {
    1: ("%tpu_custom_call.1 = (f32[8]{0}, f32[8]{0}) custom-call("
        "f32[8]{0} %x, f32[8]{0} %y)", "custom-call",
        "jit(_execute_local)/jit(fft_four_step)/repro_dft_fused/"
        "pallas_call"),
    2: ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %tpu_custom_call.1)",
        "loop fusion", "jit(_execute_local)/repro_r2c/add"),
    3: ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %fusion.2)",
        "convolution fusion", "jit(_execute_local)/dot_general"),
    # a layout copy of the kernel's output that XLA gave the kernel's
    # op_name, as in the slab executor
    4: ("%copy.4 = f32[8]{1,0} copy(f32[8]{0} %tpu_custom_call.1)",
        "data formatting",
        "jit(execute_slab)/shard_map/jit(fft_four_step)/repro_dft_fused/"
        "pallas_call"),
}
#: per chip, (metadata id, start, end) in microseconds from a step's start
CHIP_OPS = {0: [(1, 4, 34), (4, 34, 36), (2, 36, 41), (3, 42, 52)],
            1: [(1, 4, 24), (4, 24, 26), (2, 26, 31), (3, 32, 42)]}


def _fused_trace() -> bytes:
    """Two steps at 0 and 100 us on two chips.  Host, per step at s: step
    [s, s+100), dispatch [s, s+8), block [s+8, s+60), ``repro.rfftn``
    [s+1, s+7) with ``repro.execute`` [s+2, s+5) inside it."""
    meta = []
    for k, (name, cat, tf_op) in OPS.items():
        meta.append(
            f'event_metadata {{ key: {k} value {{ id: {k} name: "{name}" '
            f'stats {{ metadata_id: 1 str_value: "{cat}" }} '
            f'stats {{ metadata_id: 2 str_value: "{tf_op}" }} }} }}')
    meta.append('event_metadata { key: 10 value { id: 10 name: '
                '"jit__execute_local(1)" } }')
    planes = []
    for chip, ops in CHIP_OPS.items():
        evs = [_ev(m, s + lo, s + hi) for s in (0, 100) for m, lo, hi in ops]
        mods = [_ev(10, s + 4, s + 52) for s in (0, 100)]
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
                 _ev(23, s + 8, s + 60), _ev(24, s + 1, s + 7),
                 _ev(25, s + 2, s + 5)]
    host.append("}")
    host += [f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
             for k, n in spans.items()]
    host.append("}")
    return ProfileData.text_proto_to_serialized_xspace(
        "\n".join(planes + host))


@pytest.fixture
def traces(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACES", tmp_path / "traces")
    return tmp_path / "traces"


def _read(metric, trace):
    return harness._load_reader(helpers.REPO, metric)(trace, CTX)


def test_reader_on_a_hand_trace_with_the_kernel(traces):
    path = _place(traces, "hand.cell", _fused_trace())
    trace = xplane.load(path)
    assert trace.steps == 2
    # the custom call only: 30 us a step on chip 0, 20 on chip 1; the mean
    assert _read(METRIC, trace) == pytest.approx(25e-3, rel=1e-9)
    # the scoped layout copy (2 us) counts as a relayout, not as the kernel
    assert _read("relayout_ms", trace) == pytest.approx(2e-3, rel=1e-9)
    # the kernel is no matmul fusion: dft_matmul_ms reads the jnp stage
    assert _read("dft_matmul_ms", trace) == pytest.approx(10e-3, rel=1e-9)
    assert _read("r2c_pack_unpack_ms", trace) == pytest.approx(5e-3,
                                                              rel=1e-9)


@pytest.mark.parametrize("recorded", [RECORDED, RECORDED_SCOPES],
                         ids=["without_scopes", "scopes_without_kernel"])
def test_reader_on_programs_without_the_kernel(traces, recorded):
    """The parent's program, with and without its own scopes, has no
    kernel: the metric is absent, not 0."""
    _place(traces, "r2c2d_16384.local", recorded.read_bytes())
    assert _read(METRIC, xplane.load(recorded)) is None


def test_benchmark_lists_dft_fused_ms():
    bench = json.loads((helpers.REPO / "BENCHMARK.json").read_text())
    m = {m["name"]: m for m in bench["per_layer"]}[METRIC]
    assert m["layer"] == "local 1D stages"
    assert (m["moves"], m["better"], m["unit"], m["source"]) == (
        "transform_ms", "lower", "ms", "device_trace")
    assert m["workloads"] == ["r2c2d_16384.local", "r2c2d_32768.slab4"]
