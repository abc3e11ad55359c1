"""Fused survivor tail vs the staged per-stage tail: wall clock and
HBM-boundary bytes across survivor buckets.

The fused pass (kernels/fused_tail) replaces the staged gather -> [hpf ->]
stft -> mmse -> istft dispatch chain with ONE kernel whose only HBM
crossing is the packed gain-filtered spectrum; the staged chain
materialises every intermediate (gathered batch, padded batch, raw
spectrum, filtered spectrum) between dispatches. Two measurements per
pow2 survivor bucket {2, 8, 32, full}:

  wall clock      jit(tail_indexed) vs jit(tail_indexed_fused), one warm
                  pass (compile) then min-of-`reps` timed passes. On CPU
                  both resolve to XLA-compiled jnp (backend auto), so this
                  measures the fusion's dispatch/materialisation economy,
                  not kernel quality — the compiled-TPU sweep is the open
                  ROADMAP item.
  boundary bytes  the analytic per-dispatch HBM traffic model: bytes every
                  staged intermediate materialises vs the fused kernel's
                  packed-spectrum handoff. Exact array sizes, f32/c64.

A roofline sketch per bucket (benchmarks/roofline.py `fused_tail_record`)
classifies the fused pass compute- vs memory-bound at TPU v5e constants.

Writes `results/BENCH_fused.json`.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SERF_AUDIO as cfg
from repro.core.graph import PipelineGraph
from repro.data.loader import audio_batch_maker
from repro.kernels.fused_tail import kernel as FTK
from benchmarks import roofline
from benchmarks.util import table, save_json


def boundary_bytes(R, S, window, hop, hpf=False):
    """(staged, fused) inter-dispatch HBM bytes for an R-row tail.

    Staged: every stage output materialises — the gathered (R,S) f32
    batch, the optional hpf (R,S) f32, the (R,S_pad) f32 pad, the raw
    (R,Fv,bins) c64 spectrum, the filtered (R,Fv,bins) c64 spectrum, and
    the (R,S) f32 resynthesis. Fused: the kernel's packed (R,F,2*KP)
    f32 spectrum plus the same (R,S) f32 resynthesis out of `finish`."""
    _, S_pad, F, Fv = FTK.tail_geometry(S, window, hop)
    bins = window // 2 + 1
    staged = R * S * 4                 # gather
    if hpf:
        staged += R * S * 4            # hpf output
    staged += R * S_pad * 4            # pad_for_stft
    staged += R * Fv * bins * 8        # raw spectrum (complex64)
    staged += R * Fv * bins * 8        # gain-filtered spectrum
    staged += R * S * 4                # istft output
    fused = R * F * 2 * FTK.spectrum_lanes(bins) * 4   # packed spectrum
    fused += R * S * 4                 # istft output (finish)
    return staged, fused


def _min_wall(fn, wave, idx, reps):
    jax.block_until_ready(fn(wave, idx))          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(wave, idx))
        best = min(best, time.perf_counter() - t0)
    return best


def run(buckets=(2, 8, 32, None), reps=2, seed=13, batch_long_chunks=3):
    make = audio_batch_maker(seed=seed,
                             batch_long_chunks=batch_long_chunks)
    g = PipelineGraph(cfg)
    det = g.detection(jnp.asarray(make(0)[0]))
    wave5 = det.wave5
    B, S = wave5.shape
    window, hop = cfg.stft_window, cfg.stft_hop
    staged_fn = jax.jit(lambda w, i: g.tail_indexed(w, i))
    fused_fn = jax.jit(lambda w, i: g.tail_indexed_fused(w, i))

    rows, recs = [], []
    for b in buckets:
        R = B if b is None else min(b, B)
        idx = jnp.arange(R, dtype=jnp.int32)
        t_staged = _min_wall(staged_fn, wave5, idx, reps)
        t_fused = _min_wall(fused_fn, wave5, idx, reps)
        by_s, by_f = boundary_bytes(R, S, window, hop)
        roof = roofline.roofline_terms(
            roofline.fused_tail_record(R, S, window, hop))
        rec = {
            "bucket": "full" if b is None else b, "rows": R,
            "staged_wall_s": t_staged, "fused_wall_s": t_fused,
            "speedup": t_staged / t_fused,
            "staged_boundary_bytes": by_s, "fused_boundary_bytes": by_f,
            "boundary_reduction": 1 - by_f / by_s,
            "roofline_dominant": roof["dominant"],
            "roofline_compute_s": roof["compute_s"],
            "roofline_memory_s": roof["memory_s"],
        }
        recs.append(rec)
        rows.append(["full" if b is None else b, R, t_staged, t_fused,
                     t_staged / t_fused, by_s / 2**20, by_f / 2**20,
                     f"{rec['boundary_reduction']:.0%}", roof["dominant"]])
    table(rows, ["bucket", "rows", "staged s", "fused s", "speedup",
                 "staged MB", "fused MB", "boundary cut", "v5e bound"],
          title=f"Fused vs staged survivor tail (B={B}, S={S}, "
                f"min-of-{reps})")

    tot_s = sum(r["staged_wall_s"] for r in recs)
    tot_f = sum(r["fused_wall_s"] for r in recs)
    findings = {
        "fused_no_slower_than_staged": bool(tot_f <= tot_s * 1.05),
        "total_speedup": tot_s / tot_f,
        "boundary_cut_every_bucket": all(
            r["boundary_reduction"] > 0 for r in recs),
        "min_boundary_reduction": min(
            r["boundary_reduction"] for r in recs),
    }
    path = save_json("BENCH_fused", {"rows": recs, "findings": findings})
    print(f"\nfused tail vs staged over buckets "
          f"{[r['bucket'] for r in recs]}: total {tot_s:.2f}s -> "
          f"{tot_f:.2f}s ({findings['total_speedup']:.2f}x); boundary "
          f"bytes cut {findings['min_boundary_reduction']:.0%}+ per bucket")
    print(f"record -> {path}")
    return findings


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--batch-long-chunks", type=int, default=3)
    args = ap.parse_args()
    run(reps=args.reps, batch_long_chunks=args.batch_long_chunks)


if __name__ == "__main__":
    main()
