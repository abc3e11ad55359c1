"""The benchmark's data files, its work counts and its peak table: found by
name, counted from shapes, keyed by device kind. No chip needed."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cell  # noqa: E402
import work  # noqa: E402


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files():
    bench = benchmark()
    readers = cell.metric_files(ROOT)
    for w in bench["workloads"]:
        _, entry, cfg, traffic, limits = cell.load_cell(w["name"], ROOT)
        assert entry["chips"] == cfg["chips"]
        assert cfg["entry"]["kind"] == "archive"
        assert traffic["label_probs"] and limits
        e2e, layer = cell.cell_metrics(bench, w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer and all(m["name"] in readers for m in layer)


def test_a_file_added_is_picked_up_without_an_edit(tmp_path):
    """A new mix, configuration, limits file and per-layer metric join the
    harness by being added: the code finds them by name."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = benchmark()
    (tmp_path / "bench" / "traffic" / "dawn.json").write_text(
        (tmp_path / "bench" / "traffic" / "mixed.json").read_text())
    (tmp_path / "bench" / "limits" / "archive_dawn.json").write_text(
        '{"keep_mismatch": 0}')
    (tmp_path / "bench" / "metrics" / "kept_share.archive.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench["workloads"].append({"name": "archive_dawn", "config": "serf_archive",
                               "traffic": "dawn", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "kept_share.archive", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "plans", "moves": "audio_rate",
                               "workloads": ["archive_dawn"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _, entry, cfg, traffic, limits = cell.load_cell("archive_dawn", tmp_path)
    assert cfg["name"] == "serf_archive" and limits == {"keep_mismatch": 0}
    readers = cell.metric_files(tmp_path)
    _, layer = cell.cell_metrics(bench, "archive_dawn")
    assert [m["name"] for m in layer] == ["compile_s", "kept_share.archive"]
    read = cell.load_reader("kept_share.archive",
                            readers["kept_share.archive"])
    assert read({}) == 42.0


def test_cells_and_metrics_keep_to_the_names_the_issue_fixed():
    bench = benchmark()
    assert [c["name"] for c in bench["configs"]] == ["serf_archive"]
    assert [(w["name"], w["chips"]) for w in bench["workloads"]] == [
        ("archive_mixed", 1)]
    assert {m["name"] for m in bench["end_to_end"]} == {
        "setup_s", "audio_rate"}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]


@pytest.mark.parametrize("rows,chips", [(192, 1), (768, 4), (24, 1)])
def test_the_warm_up_aims_at_every_tail_bucket(rows, chips):
    """One graded warm-up batch per pow2 survivor bucket, each aimed at
    the middle of its bucket's range."""
    buckets = cell.pow2_buckets(rows, chips)
    assert buckets[-1] == rows and all(b % chips == 0 for b in buckets)
    want = cell.warm_targets(buckets)
    assert [cell.bucket_of(n, buckets) for n in want] == buckets
    assert cell.bucket_of(0, buckets) is None


def test_peaks_refuse_an_unknown_device_kind():
    path = os.path.join(BENCH, "peaks.json")
    v5e = cell.load_peaks("TPU v5 lite", path)
    assert v5e["flops_per_s_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        cell.load_peaks("TPU v9", path)
    with pytest.raises(KeyError):
        cell.load_peaks("cpu", path)


def test_work_counts_the_algorithm_not_the_layout(monkeypatch):
    """A 256-sample window, hop 128, over one 15 s chunk at 22.05 kHz: 2582
    frames, each a 129-bin real DFT (258 outputs of 256 multiply-adds).
    Changing a kernel's padding constants changes nothing."""
    ops, nbytes = work.stft_dft(1, 330_750, 256, 128)
    assert ops == 2 * 2582 * 256 * 258
    assert nbytes == 4 * (330_750 + 2582 * 258)
    before = (work.stft_dft(64, 330_750, 256, 128),
              work.fused_tail(110, 110_250, 256, 128),
              work.fir_hpf(16, 2_646_000, 129, 2))
    from repro.kernels.fused_tail import kernel as ft
    from repro.kernels.stft_dft import kernel as sd
    monkeypatch.setattr(sd, "PAD_OUT", 512)
    monkeypatch.setattr(sd, "FRAME_TILE", 256)
    monkeypatch.setattr(ft, "spectrum_lanes", lambda bins: 1024)
    after = (work.stft_dft(64, 330_750, 256, 128),
             work.fused_tail(110, 110_250, 256, 128),
             work.fir_hpf(16, 2_646_000, 129, 2))
    assert after == before
    assert work.fir_hpf(1, 1000, 129, 2) == (2 * 500 * 129, 4 * 1500)


def test_roofline_share_is_the_bound_over_the_time():
    peaks = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(100, 5, 2.0, peaks) == 50.0    # ops-bound
    assert work.roofline_share(1, 40, 8.0, peaks) == 50.0     # bytes-bound
    assert work.roofline_share(1, 1, 0.0, peaks) is None
