"""Process-level rules the entry points apply before any work: where the
persistent compile cache lives, what counts as a chip run, and that a chip
belongs to one process. Importing this module changes nothing; each
function acts only when an entry point calls it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.kernels import backend

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, so every process on this tree finds the entries the last one wrote
# (the directory is part of the cache key); listed in .gitignore
CACHE_DIRNAME = ".jax_cache"


class ChipRequired(RuntimeError):
    """A chip run was asked for where the compiled TPU kernels cannot run."""


def checkout_root() -> Path:
    """The directory holding `src/repro` — the root of the checkout."""
    return Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX already reads it and this sets
    nothing; otherwise the cache goes to `.jax_cache/` in the checkout."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(checkout_root() / CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chip():
    """Refuse to start unless JAX's backend is a TPU and the kernels
    resolve to compiled Pallas — a CPU backend, interpret mode or a
    REPRO_KERNEL_BACKEND override cannot pass for a chip run."""
    platform = jax.default_backend()
    if platform != "tpu":
        raise ChipRequired(f"no TPU: JAX's default backend is {platform!r}")
    if backend.resolve() != (True, False):
        raise ChipRequired(
            f"kernel backend mode {backend.get_mode()!r} does not run the "
            "compiled Pallas kernels (REPRO_KERNEL_BACKEND or backend.use)")


def refuse_worker_processes(what: str):
    """One process per chip: a TPU belongs to the process that opened it,
    so on a TPU backend a worker process would find its chip held by this
    one (or a sibling) and fail or hang. Raise before spawning instead."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what}: one process per chip — this process holds the TPU "
            "and a worker process would need it too. On a chip host run "
            "the in-process plans or transport='inproc'.")
