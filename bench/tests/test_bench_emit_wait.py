"""The readers of the emission wait's two parts, `tail_wait_ms.archive`
and `d2h_wait_ms.archive`, on synthetic records. No chip needed."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell  # noqa: E402

KEYS = {"tail_wait_ms.archive": "tail_wait_s",
        "d2h_wait_ms.archive": "d2h_wait_s"}


def reader(name):
    return cell.load_reader(name, cell.metric_files(ROOT)[name])


@pytest.mark.parametrize("name", sorted(KEYS))
def test_mean_over_the_batches_that_ran_a_tail(name):
    key = KEYS[name]
    # the second batch kept nothing, so it ran no tail and has no key
    rec = {"batches": [{key: 0.010, "emit_s": 0.012, "n_kept": 90},
                       {"emit_s": 0.0001, "n_kept": 0},
                       {key: 0.030, "emit_s": 0.031, "n_kept": 118}]}
    assert reader(name)(rec) == pytest.approx(20.0)


@pytest.mark.parametrize("name", sorted(KEYS))
@pytest.mark.parametrize("rec", [
    {"batches": [{"emit_s": 0.04, "readback_s": 0.0002, "n_kept": 118}]},
    {"batches": []},
    {},
], ids=["without_the_key", "no_batches", "no_record"])
def test_nothing_to_read_is_none(name, rec):
    """A program that records neither part (as before the split) gives
    no value, and the cell leaves the metric out."""
    assert reader(name)(rec) is None
