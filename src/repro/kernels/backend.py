"""Kernel execution backend selection.

Modes:
  auto      — TPU: compiled pallas_call; CPU: the pure-jnp ref path (XLA-
              compiled, fast). This is the production default: interpret-mode
              Pallas executes the kernel body in Python per grid step and is
              a correctness tool, not an execution engine.
  pallas    — force pallas_call (compiled on TPU, interpret on CPU).
  interpret — force interpret-mode pallas_call (kernel correctness tests).
  ref       — force the jnp oracle.

The FUSED SURVIVOR TAIL (kernels/fused_tail) resolves through the same
modes: when a two-phase-family plan detects the canonical post-removal
chain ([hpf ->] mmse), its survivor dispatch becomes one fused pass whose
backend follows resolve()/matmul_dft() exactly like the per-stage ops it
replaces — ref oracle on CPU auto, pallas/interpret kernel when forced,
bf16 matmul-DFT twin under "matmul".

A chip run must not silently take another path: `repro.runtime
.require_chip` refuses to start unless the backend is a TPU and resolve()
gives (True, False).
"""
from __future__ import annotations

import contextlib
import os

import jax

_MODE = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
_VALID = ("auto", "pallas", "interpret", "ref", "matmul")

# How far the Pallas kernels' cleaned audio may stray from the float32
# "ref" path. The kernels run the DFT and FIR as float32 MXU matmuls
# (precision=HIGHEST) that sum in another order than XLA's FFT and conv;
# the largest gap seen is 1.91e-6 on a TPU v5e over 543 cleaned 5 s chunks
# and 1.8e-6 in interpret mode on the CPU. 1e-5 sits 5x above both. A
# kernel whose dots fall back to one bf16 MXU pass is off by ~1e-4 on
# audio of ~0.1 scale and fails it; a wrong bin, frame or tap is O(0.1).
REF_TOL = dict(rtol=0.0, atol=1e-5)


def set_mode(mode: str):
    global _MODE
    if mode not in _VALID:
        raise ValueError(f"mode {mode!r} not in {_VALID}")
    _MODE = mode


def get_mode() -> str:
    return _MODE


@contextlib.contextmanager
def use(mode: str):
    prev = _MODE
    set_mode(mode)
    try:
        yield
    finally:
        set_mode(prev)


def resolve() -> tuple[bool, bool]:
    """Returns (use_pallas, interpret)."""
    on_tpu = jax.default_backend() == "tpu"
    mode = _MODE
    if mode == "auto":
        return (True, False) if on_tpu else (False, False)
    if mode == "pallas":
        return True, not on_tpu
    if mode == "interpret":
        return True, True
    return False, False


def matmul_dft() -> bool:
    """True when the SPMD-partitionable matmul-DFT path should replace the
    XLA FFT op (mode "matmul"; used by the dry-run — see stft ref.py)."""
    return _MODE == "matmul"
