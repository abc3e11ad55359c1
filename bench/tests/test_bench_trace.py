"""The trace reduction: busy union, idle share, kernel time by name,
collective time and idle gaps named by the host span, on hand-made
intervals and on a small trace recorded on one TPU v5e (two archive
batches)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as T  # noqa: E402

KERNELS = ("fir_hpf", "stft_dft", "fused_tail")
ONE_CHIP = os.path.join(HERE, "data", "archive_1chip.xplane.pb")


def test_union_merges_and_clips():
    assert T.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [
        [1, 4], [5, 10]]
    assert T.union([(0, 1)], 2, 3) == []


def test_reduce_on_hand_made_intervals():
    ms = 1_000_000
    devices = {
        0: [(0 * ms, 4 * ms, "fused_tail.1"), (2 * ms, 5 * ms, "fusion.3"),
            (7 * ms, 8 * ms, "all-gather-start.2")],
        1: [(1 * ms, 9 * ms, "stft_dft.1")],
    }
    host = [(4 * ms, 8 * ms, "bench.run_step"), (0, 10 * ms, "bench.window"),
            (8 * ms, 10 * ms, "bench.bank_draw")]
    red = T.reduce(devices, host, (0, 10 * ms), KERNELS,
                   skip_spans=("bench.window",))
    d0, d1 = red["devices"]
    assert red["window_s"] == pytest.approx(0.010)
    assert d0["busy_s"] == pytest.approx(0.006)      # [0,5) + [7,8)
    assert d1["busy_s"] == pytest.approx(0.008)
    assert d0["kernel_s"] == {"fused_tail": pytest.approx(0.004)}
    assert d1["kernel_s"] == {"stft_dft": pytest.approx(0.008)}
    assert d0["collective_s"] == pytest.approx(0.001)
    # device 0 idles in [5, 7) under run_step and [8, 10) under bank_draw
    assert sorted(red["idle_gaps"][:2]) == [
        ["bench.bank_draw", pytest.approx(0.002)],
        ["bench.run_step", pytest.approx(0.002)]]
    assert dict(red["device_ops"])["fusion.3"] == pytest.approx(0.003)
    assert T.instruction("%stft_dft.1 = f32[64,2688,384]{2,1,0} custom-call("
                         "f32[64,2696,128] %bitcast.39)") == "stft_dft.1"


def _reduced(path):
    if not os.path.exists(path):
        pytest.fail(f"the recorded trace {path} is missing")
    devices, host = T.load(path, "bench.")
    window = T.span_named(host, "bench.window")
    return devices, T.reduce(devices, host, window, KERNELS,
                             skip_spans=("bench.window",))


def test_a_one_chip_trace_recorded_on_the_chip():
    """Two batches of 16 long chunks through the async plan on one v5e."""
    devices, red = _reduced(ONE_CHIP)
    assert list(devices) == [0]
    (d,) = red["devices"]
    assert 0 < d["busy_s"] <= red["window_s"]
    assert 0 < 1 - d["busy_s"] / red["window_s"] < 1
    # each batch runs one detect (fir_hpf, stft_dft) and one fused tail
    assert d["kernel_calls"] == {"fir_hpf": 2, "stft_dft": 2,
                                 "fused_tail": 2}
    assert all(s > 0 for s in d["kernel_s"].values())
    assert d["collective_s"] == 0
    assert sum(s for s in d["kernel_s"].values()) < d["busy_s"] + 1e-9
    # this trace holds no benchmark span inside its window but the window
    assert red["idle_gaps"] and {n for n, _ in red["idle_gaps"]} == {"none"}
    assert sum(s for _, s in red["idle_gaps"]) <= red["window_s"] - d["busy_s"]
