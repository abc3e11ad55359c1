"""A small trace recorded on one TPU v5e (two archive batches,
`record_trace.py`) after the program named its work: the `XLA Modules`
line names the programs by phase, the plan's `repro.*` spans sit on the
host plane inside `bench.window`, each with its batch's `wid`, and the
kernels keep the instruction names the reduction finds them by."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as T  # noqa: E402

NAMED = os.path.join(HERE, "data", "archive_1chip_named.xplane.pb")
PLAN_SPANS = ("repro.detect_dispatch", "repro.h2d_put",
              "repro.detect_enqueue", "repro.mask_wait", "repro.compact",
              "repro.tail_enqueue", "repro.emit", "repro.tail_wait",
              "repro.d2h_wait")


def _events(plane_test, line_test=lambda name: True):
    import jax
    if not os.path.exists(NAMED):
        pytest.fail(f"the recorded trace {NAMED} is missing")
    for plane in jax.profiler.ProfileData.from_file(NAMED).planes:
        if plane_test(plane.name):
            for line in plane.lines:
                if line_test(line.name):
                    yield from line.events


def test_programs_are_named_by_phase():
    modules = {re.sub(r"\(\d+\)$", "", e.name) for e in _events(
        lambda p: T.DEVICE_PLANE.match(p), lambda n: n == "XLA Modules")}
    assert {"jit_detect", "jit_tail_idx_fused"} <= modules
    assert not [m for m in modules if "lambda" in m]


@pytest.mark.parametrize("name", PLAN_SPANS)
def test_plan_spans_sit_in_the_window_with_their_batch(name):
    _, host = T.load(NAMED, "")
    lo, hi = T.span_named(host, "bench.window")
    wids = []
    for e in _events(lambda p: p.startswith("/host:")):
        if e.name == name:
            assert lo <= e.start_ns <= e.start_ns + e.duration_ns <= hi
            wids.append({k: v for k, v in e.stats}["wid"])
    assert sorted(wids) == [0, 1]               # both batches ran a tail


def test_kernels_keep_their_instruction_names():
    devices, host = T.load(NAMED, "bench.")
    red = T.reduce(devices, host, T.span_named(host, "bench.window"),
                   ("fir_hpf", "stft_dft", "fused_tail"),
                   skip_spans=("bench.window",))
    ks = red["devices"][0]["kernel_s"]
    assert ks["fused_tail"] > 0 and ks["stft_dft"] > 0
