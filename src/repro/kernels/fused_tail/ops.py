"""Dispatch + autotuner for the fused survivor tail.

`fused_tail` is the single public entry: backend-mode resolution follows
kernels/backend.py exactly as the staged per-stage ops do —

    ref / auto-on-CPU  ->  ref.fused_tail_ref      (jnp oracle)
    matmul             ->  ref.fused_tail_matmul   (bf16 DFT dry-run twin)
    pallas / interpret ->  kernel.fused_tail_pallas + kernel.finish
    auto-on-TPU        ->  compiled kernel.fused_tail_pallas

The kernel path takes a `TailConfig` (frame_block) chosen by the
autotuner: `autotune` enumerates CANDIDATES, drops any whose scoped-VMEM
model (`vmem_bytes`) exceeds the per-core budget, times the survivors
(min-of-reps, block_until_ready) and caches the winner per (backend mode,
survivor bucket, S, hpf). `best_config` is the hot-path accessor: tuned
entry if present, else the first feasible candidate — it never probes, so
plans can call it inside a jit trace without timing side effects. The
kernel is compiled with the budget as its scoped-VMEM limit, so a
candidate the model admits is one the compiler accepts
(tests/test_tpu_compile.py compiles each at the model's own figure).

frame_block only re-tiles the DFT dot's M dimension, a perf knob: rows of
a matmul do not depend on how the rows are tiled.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.fused_tail import kernel as K
from repro.kernels.fused_tail import ref as R

VMEM_BUDGET = int(os.environ.get("REPRO_FUSED_VMEM_BYTES", 16 * 2 ** 20))


@dataclasses.dataclass(frozen=True)
class TailConfig:
    frame_block: int = 2   # FRAME_TILE-tiles of frames per DFT dispatch


CANDIDATES = tuple(TailConfig(fb) for fb in (1, 2, 4, 8))

# Working set of one frame block, per frame row: the compiler's own
# scoped-VMEM reports (RESOURCE_EXHAUSTED "Scoped allocation with size
# ..." at a lowered vmem_limit_bytes) for every candidate, with and
# without HPF, at S = 16,640 / 110,250 on a described v5e, minus the
# pipeline buffers and scratch below, peak at 10,012 bytes per row
# (frame_block 1, S = 16,640). Five (rows, 2 * KP) f32 values covers it.
WORK_ROW_VALUES = 5
# Mosaic's own internal scratch, above the buffers the model names.
INTERNAL_BYTES = 512 * 2 ** 10


def vmem_bytes(tc: TailConfig, S, window=256, hop=128, hpf=False) -> int:
    """Scoped VMEM one grid step needs: the double-buffered in/out blocks
    and the scratch the kernel declares (all (8, 128)-tile aligned
    already), plus a per-frame-row working set fit to the compiler."""
    _, _, F, _ = K.tail_geometry(S, window, hop)
    kp = K.spectrum_lanes(window // 2 + 1)
    rows = F + 8                               # the (rows, 128) signal view
    n = 2 * rows * hop                         # survivor row block
    n += 2 * window * 2 * kp                   # DFT basis block
    n += 2 * F * 2 * kp                        # packed spectrum block
    n += F * kp                                # power scratch
    if hpf:
        n += 2 * 2 * K.LANES * K.LANES         # banded tap matrices
        n += rows * hop                        # filtered-row scratch
    block_rows = min(tc.frame_block * K.FRAME_TILE, F)
    n += WORK_ROW_VALUES * block_rows * 2 * kp
    return 4 * n + INTERNAL_BYTES


def feasible(S, window=256, hop=128, hpf=False, budget=None):
    budget = VMEM_BUDGET if budget is None else budget
    return [tc for tc in CANDIDATES
            if vmem_bytes(tc, S, window, hop, hpf) <= budget]


# (backend mode, rows, S, hpf) -> TailConfig
_TUNED: dict[tuple, TailConfig] = {}
# same key -> [(TailConfig, seconds)] probe records, for benches/tests
_PROBES: dict[tuple, list] = {}


def _key(rows, S, hpf):
    return (backend.get_mode(), int(rows), int(S), bool(hpf))


def best_config(rows, S, cfg, hpf=False) -> TailConfig:
    """Tuned winner if autotune ran for this key, else the first feasible
    candidate. Never probes — safe on the dispatch hot path."""
    tuned = _TUNED.get(_key(rows, S, hpf))
    if tuned is not None:
        return tuned
    feas = feasible(S, cfg.stft_window, cfg.stft_hop, hpf)
    if not feas:
        raise ValueError(
            f"no VMEM-feasible fused-tail config for S={S} "
            f"(budget {VMEM_BUDGET} bytes)")
    default = TailConfig()
    return default if default in feas else feas[0]


def autotune(wave, idx, cfg, hpf=False, reps=2) -> TailConfig:
    """Probe every VMEM-feasible candidate on (wave, idx), cache and
    return the fastest. No-op (returns the cached winner) on a warm key."""
    rows, S = idx.shape[0], wave.shape[1]
    key = _key(rows, S, hpf)
    if key in _TUNED:
        return _TUNED[key]
    use_pallas, interpret = backend.resolve()
    feas = feasible(S, cfg.stft_window, cfg.stft_hop, hpf)
    if not feas:
        raise ValueError(f"no VMEM-feasible fused-tail config for S={S}")
    records = []
    for tc in feas:
        if use_pallas:
            fn = jax.jit(lambda w, i, tc=tc: _kernel_path(
                w, i, cfg, hpf, tc, interpret))
        else:
            # ref path ignores tiling; probe once so records stay uniform
            fn = jax.jit(lambda w, i: R.fused_tail_ref(w, i, cfg, hpf))
        fn(wave, idx).block_until_ready()  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(wave, idx).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        records.append((tc, best))
        if not use_pallas:
            break  # tiling is dead on the ref path; one probe suffices
    records.sort(key=lambda r: r[1])
    _PROBES[key] = records
    _TUNED[key] = records[0][0]
    return _TUNED[key]


def clear_tuning():
    _TUNED.clear()
    _PROBES.clear()


def fused_tail(wave, idx, cfg, hpf=False, tile: TailConfig | None = None):
    """The fused survivor tail: (B, S) batch + (R,) padded survivor index
    vector -> cleaned (R, S). Mode-dispatched like every staged op."""
    if backend.matmul_dft():
        return R.fused_tail_matmul(wave, idx, cfg, hpf)
    use_pallas, interpret = backend.resolve()
    if not use_pallas:
        return R.fused_tail_ref(wave, idx, cfg, hpf)
    tc = tile or best_config(idx.shape[0], wave.shape[1], cfg, hpf)
    return _kernel_path(wave, idx, cfg, hpf, tc, interpret)


def _kernel_path(wave, idx, cfg, hpf, tc, interpret):
    # named apart, so a device trace tells the kernel from the resynthesis
    with jax.named_scope("kernel"):
        packed = K.fused_tail_pallas(wave, idx, cfg, hpf, tc.frame_block,
                                     interpret=interpret,
                                     vmem_limit_bytes=VMEM_BUDGET)
    with jax.named_scope("istft"):
        return K.finish(packed, wave.shape[1], cfg)
