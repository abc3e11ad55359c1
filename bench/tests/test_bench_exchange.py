"""The readers of what the four-chip cell adds, `gather_mb.archive` (the
tail's all-gather, from `BatchResult.timings`) and `collective_ms.archive`
(collective device time, from the trace's record), on synthetic records.
No chip needed."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell  # noqa: E402


def reader(name):
    return cell.load_reader(name, cell.metric_files(ROOT)[name])


def test_gather_mb_is_the_mean_over_the_batches_that_ran_a_tail():
    # the second batch kept nothing: no tail, no exchange, not counted
    rec = {"batches": [{"gather_bytes": 254_016_000, "n_kept": 470},
                       {"gather_bytes": 0, "n_kept": 0},
                       {"gather_bytes": 254_016_000, "n_kept": 466},
                       {"gather_bytes": 127_008_000, "n_kept": 9}]}
    assert reader("gather_mb.archive")(rec) == pytest.approx(211.68)


def test_collective_ms_is_the_mean_chip_time_a_batch():
    dev = [{"device": i, "busy_s": 1.0, "kernel_s": {}, "kernel_calls": {},
            "collective_s": s} for i, s in enumerate((0.9, 1.1, 1.0, 1.0))]
    rec = {"trace": {"window_s": 30.0, "devices": dev},
           "batches": [{"n_kept": 1}] * 200}
    assert reader("collective_ms.archive")(rec) == pytest.approx(5.0)


@pytest.mark.parametrize("rec", [
    {"batches": [{"emit_s": 0.04, "d2h_wait_s": 0.02, "n_kept": 118}]},
    {"batches": []},
    {},
], ids=["without_the_key", "no_batches", "no_record"])
def test_gather_mb_with_nothing_to_read_is_none(rec):
    """A program that records no `gather_bytes` (as before the exchange
    was explicit) gives no value, and the cell leaves the metric out."""
    assert reader("gather_mb.archive")(rec) is None


@pytest.mark.parametrize("rec", [
    {"batches": [{"n_kept": 3}]},
    {"trace": {"window_s": 30.0, "devices": []}, "batches": [{}]},
    {"trace": {"window_s": 30.0, "devices": [{"collective_s": 0.1}]},
     "batches": []},
], ids=["untraced", "no_device_plane", "no_batches"])
def test_collective_ms_with_nothing_to_read_is_none(rec):
    assert reader("collective_ms.archive")(rec) is None
