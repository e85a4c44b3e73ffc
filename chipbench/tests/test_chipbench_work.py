"""The transform's algorithmic work and the table of peaks."""

import math

import pytest

from chipbench import work

N = 16384


def test_r2c_forward_work_of_the_paper_problem():
    w = work.call_work((N, N), "r2c", "forward")
    assert w["ops"] == 2.5 * N * N * 28             # 2.5 N log2 N, N = 2^28
    assert w["bytes"] == N * N * 4 + N * (N // 2 + 1) * 8


@pytest.mark.parametrize("shape", [(512, 512, 512), (1 << 24,), (12, 15)])
def test_c2c_work_is_five_n_log2_n_and_pairs_both_ways(shape):
    n = math.prod(shape)
    for call in ("forward", "inverse"):
        w = work.call_work(shape, "c2c", call)
        assert w["ops"] == pytest.approx(5 * n * math.log2(n))
        assert w["bytes"] == 2 * n * 8


def test_inverse_r2c_reads_the_half_spectrum_writes_reals():
    w = work.call_work((64, 48), "r2c", "inverse")
    assert w["bytes"] == 64 * 25 * 8 + 64 * 48 * 4


def test_unknown_call_is_an_error():
    with pytest.raises(ValueError):
        work.call_work((8, 8), "r2c", "sideways")


def test_step_work_is_per_chip_and_sums_the_calls():
    one = work.call_work((N, N), "c2c", "forward")
    step = work.step_work((N, N), "c2c", ["forward", "inverse"], 4)
    assert step["ops"] == 2 * one["ops"] / 4
    assert step["bytes"] == 2 * one["bytes"] / 4


def test_slab_cell_least_time_is_bytes_bound():
    w = work.step_work((2 * N, 2 * N), "r2c", ["forward"], 4)
    peaks = work.peaks_for("TPU v5 lite")
    t, bound = work.least_step_seconds(w, peaks)
    assert bound == "bytes"
    assert t == pytest.approx(
        (2 ** 30 * 4 + 2 * N * (N + 1) * 8) / 4 / 819e9)


def test_ops_bound_when_bytes_are_few():
    peaks = work.peaks_for("TPU v5 lite")
    t, bound = work.least_step_seconds({"ops": 197e12, "bytes": 1.0}, peaks)
    assert (t, bound) == (1.0, "ops")


def test_v5e_peaks_are_the_published_ones():
    p = work.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks_for(kind)
