"""chip_smoke.py on the CPU: its phase function at a tiny size (one long
chunk per batch, kernels in interpret mode against the ref mode), and its
refusal to report anything without a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import runtime
from repro.kernels import backend

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phase_tiny():
    cs = _chip_smoke()
    batches = cs.make_batches(seed=25, n_batches=2, batch_long_chunks=1)
    out = cs.smoke_phase(batches, "interpret", depth=2, n_requests=1)
    run, ref = out["run"], out["ref_run"]
    assert run["kept"] == ref["kept"] and sum(run["kept"]) > 0
    assert run["compiles_in_timed_pass"] == 0
    assert out["ref_max_abs_err"] <= cs.REF_TOL["atol"]
    assert out["serve_max_abs_err"] <= cs.SAME_TOL["atol"]


def _run(script, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_main_fails_without_tpu(tmp_path):
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "ChipRequired" in out.stderr
    # alone in a directory, without the repo, it must fail as well
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("mode,ok", [("auto", True), ("pallas", True),
                                     ("interpret", False), ("ref", False),
                                     ("matmul", False)])
def test_require_chip_refuses_fallbacks(monkeypatch, mode, ok):
    """On a TPU backend only the compiled Pallas kernels pass; on the CPU
    nothing does."""
    with backend.use(mode):
        with pytest.raises(runtime.ChipRequired):
            runtime.require_chip()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        if ok:
            runtime.require_chip()
        else:
            with pytest.raises(runtime.ChipRequired):
                runtime.require_chip()


def test_last_line_contract_shape():
    """The one line a passing run ends with carries exactly the device
    keys the contract names."""
    cs = _chip_smoke()
    line = json.dumps({"ok": True, "device": cs.device_line()})
    assert set(json.loads(line)["device"]) == {"platform", "kind", "count"}


def test_compile_cache_follows_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the checkout's own cache directory is not created. The program
    runs from a copy of the package in its own checkout, so other
    processes writing this checkout's cache cannot touch the check."""
    checkout = tmp_path / "checkout"
    shutil.copytree(ROOT / "src" / "repro", checkout / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import jax\n"
            "from repro.runtime import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           runtime.CACHE_ENV: str(tmp_path / "cache"),
           "PYTHONPATH": str(checkout / "src")}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=checkout, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())
    assert not (checkout / runtime.CACHE_DIRNAME).exists()


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = runtime.enable_compile_cache()
        assert path == str(ROOT / runtime.CACHE_DIRNAME)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
