"""The reader of `emit_ready.archive`, the share of batches whose cleaned
rows were on the host before the plan's loop waited for them, on synthetic
records. No chip needed."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell  # noqa: E402

NAME = "emit_ready.archive"


def reader():
    return cell.load_reader(NAME, cell.metric_files(ROOT)[NAME])


def test_share_of_the_batches_that_ran_a_tail():
    # the second batch kept nothing, so it ran no tail and has no key
    rec = {"batches": [{"emit_ready": 1.0, "emit_s": 0.0001, "n_kept": 90},
                       {"emit_s": 0.0001, "n_kept": 0},
                       {"emit_ready": 0.0, "emit_s": 0.012, "n_kept": 118},
                       {"emit_ready": 1.0, "emit_s": 0.0001, "n_kept": 64},
                       {"emit_ready": 1.0, "emit_s": 0.0002, "n_kept": 7}]}
    assert reader()(rec) == pytest.approx(75.0)


@pytest.mark.parametrize("rec", [
    {"batches": [{"emit_s": 0.04, "tail_wait_s": 0.02, "n_kept": 118}]},
    {"batches": []},
    {},
], ids=["without_the_key", "no_batches", "no_record"])
def test_nothing_to_read_is_none(rec):
    """A program that records no `emit_ready` (as before the reader
    thread) gives no value, and the cell leaves the metric out."""
    assert reader()(rec) is None
