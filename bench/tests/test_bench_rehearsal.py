"""The harness end to end on the CPU at a tiny size: every single-chip cell
proves correct against the reference, the control (the reference in three
bfloat16 passes, put in the program's place) does not, and the command
refuses to run without a chip."""
import json
import os
import subprocess
import sys

import pytest

import rehearse

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CELLS = ["archive_mixed"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_checkout(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", CELLS)
def test_a_cell_proves_correct_on_the_cpu(root, workload):
    rc, last, out, err = rehearse.run_cell(root, workload)
    assert rc == 0 and last is not None, err[-2000:]
    assert list(last)[:5] == KEYS and list(last)[-1] == "check"
    assert last["correct"] is True, (last, out[-2000:])
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"setup_s", "audio_rate"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["device"]["platform"] == "cpu"
    # the numbers compared, each beside its limit, end standard error
    tail = err.strip().splitlines()[-len(last["check"]):]
    assert tail == [f"check {k}: {v['value']} (limit {v['limit']})"
                    for k, v in last["check"].items()]
    assert "compiles inside the window 0 " in out
    assert "reached no tail bucket" not in out


# On the CPU the three-pass control reads cleaned_rel_rms 4.4e-6 to 7.3e-6,
# past the 1.5e-6 limit.
@pytest.mark.parametrize("workload", CELLS)
def test_the_control_comes_out_not_correct(root, workload, monkeypatch):
    import cell
    compare = cell.compare
    monkeypatch.setattr(cell, "compare", lambda sample, inputs, ref: compare(
        cell.control(sample, inputs, ref.g), inputs, ref))
    rc, last, out, err = rehearse.run_cell(root, workload)
    assert rc == 0 and last is not None, err[-2000:]
    assert last["correct"] is False, last["check"]


def _cell_command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/cell.py", "--workload", "archive_mixed",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_without_a_chip():
    p = _cell_command(rehearse.ROOT, {})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "no TPU" in p.stderr


def test_the_command_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ runs nothing."""
    import shutil
    shutil.copytree(rehearse.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(rehearse.ROOT, "BENCHMARK.json"), tmp_path)
    p = _cell_command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    json.loads((tmp_path / "BENCHMARK.json").read_text())
