"""The cells that follow the first, `archive_sparse` and
`archive_mixed_x4`, from their own configuration and mix files, end to
end on the CPU at a tiny size: each proves correct against the reference.
The four-chip cell runs on four CPU devices, in a subprocess."""
import json
import os
import subprocess
import sys

import pytest

import rehearse


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_checkout(tmp_path_factory.mktemp("tiny"))


def test_archive_sparse_proves_correct_on_the_cpu(root):
    rc, last, out, err = rehearse.run_cell(root, "archive_sparse")
    assert rc == 0 and last is not None, err[-2000:]
    assert last["correct"] is True, (last, out[-2000:])
    assert last["device"]["count"] == 1
    assert set(last["metrics"]) == {"setup_s", "audio_rate"}
    assert "compiles inside the window 0 " in out


FOUR_DEVICES = r"""
import json, sys
import rehearse
root = rehearse.tiny_checkout(sys.argv[1])
rc, last, out, err = rehearse.run_cell(root, "archive_mixed_x4")
print(json.dumps([rc, last, out[-2000:], err[-2000:]]))
"""


def test_archive_mixed_x4_proves_correct_on_four_devices(tmp_path):
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(rehearse.ROOT, "src"),
                                           here, rehearse.BENCH]))
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rc, last, out, err = json.loads(p.stdout.strip().splitlines()[-1])
    assert rc == 0 and last is not None, err
    assert last["correct"] is True, (last, out)
    assert last["device"]["count"] == 4
    assert set(last["metrics"]) == {"setup_s", "audio_rate"}
    assert "compiles inside the window 0 " in out
