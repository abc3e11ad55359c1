"""Pipeline depth x padding sweep: what the depth-K async executor and
bucketed survivor shapes buy over the one-ahead streaming baseline.

Two axes, crossed:
  * dispatch depth 1/2/4/8 — detect batches in flight ahead of the tail
  * tail padding 'linear' (historic: next pad_multiple, retraces the tail
    jit per distinct survivor count) vs 'pow2' (O(log B) bucket shapes)

Timing protocol: every config warms on ONE batch (service warm-up: the
detect compile plus its first tail shape), then times TWO streams of
fresh seeds and reports the faster (min-of-2 absorbs shared-machine load
spikes; each pass still pays its structural compile costs, because its
survivor counts are new — linear padding retraces per count exactly as
on a real unbounded stream, while pow2 lands in already-compiled
buckets). Per-stage overlap, host-boundary bytes, and tail compile
counts come from the plans' own BatchResult.timings records plus the
shared CompileCache.

Writes the machine-readable `results/BENCH_pipeline.json` regression
record; `benchmarks/run.py --smoke` gates on the async executor
separately (ordering + overlap on a tiny stream).
"""
from __future__ import annotations

import time

import numpy as np

from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import JIT_CACHE, Preprocessor
from repro.data.loader import audio_batch_maker
from repro.launch.preprocess import pipeline_report
from benchmarks.util import table, save_json


def _stream(seed, n_batches, batch_long_chunks):
    make = audio_batch_maker(seed=seed, batch_long_chunks=batch_long_chunks)
    return [(w, (make(w)[0], None)) for w in range(n_batches)]


def _run_one(plan, stream, **kw):
    pre = Preprocessor(cfg, plan=plan, pad_multiple=1, **kw)
    t0 = time.perf_counter()
    results = list(pre.run(stream))
    wall = time.perf_counter() - t0
    assert [r.wid for r in results] == [w for w, _ in stream], \
        f"plan {plan} broke stream order"
    timings = [r.timings for r in results if r.timings is not None]
    n_chunks = sum(int(r.det.stats["n_chunks5"]) for r in results)
    src = sum(r.src_bytes for r in results)
    keep = np.concatenate([np.asarray(r.det.keep) for r in results])
    cleaned = np.concatenate([r.cleaned for r in results])
    return wall, timings, n_chunks, src, (keep, cleaned)


def run(minutes=16.0, batch_long_chunks=2, depths=(1, 2, 4, 8), seed=11):
    n_batches = max(4, int(round(minutes / batch_long_chunks)))
    warm = _stream(seed, 1, batch_long_chunks)
    timed = [_stream(seed + 1 + i, n_batches, batch_long_chunks)
             for i in range(2)]

    def tail_compiles():
        return sum(1 for k in JIT_CACHE.keys()
                   if k[0] in ("tail", "tail_idx", "tail_idx_fused"))

    rows, recs = [], []
    refs = [None, None]
    configs = [("two_phase", {}), ("streaming", {})]
    configs += [("async", {"depth": d, "bucket": b})
                for b in ("linear", "pow2") for d in depths]
    for plan, kw in configs:
        JIT_CACHE.clear()
        _run_one(plan, warm, **kw)          # warm: compiles for stream A
        passes = []
        for i, stream in enumerate(timed):
            before = tail_compiles()
            wall, timings, n_chunks, src, out = _run_one(plan, stream,
                                                         **kw)
            retraces = tail_compiles() - before  # fresh counts force these
            if refs[i] is None:
                refs[i] = out
            else:                            # every config, bit-identical
                np.testing.assert_array_equal(out[0], refs[i][0])
                np.testing.assert_array_equal(out[1], refs[i][1])
            passes.append((wall, timings, n_chunks, src, retraces))
        wall, timings, n_chunks, src, retraces = min(passes,
                                                     key=lambda p: p[0])
        rep = pipeline_report(timings) if timings else {}
        label = plan + (f" d={kw['depth']} {kw['bucket']}" if kw else "")
        rec = {
            "plan": plan, **kw, "wall_s": wall,
            "chunks_per_s": n_chunks / wall, "mb_per_s": src / 2**20 / wall,
            "tail_retraces": retraces, **rep,
        }
        recs.append(rec)
        rows.append([label, wall, n_chunks / wall, retraces,
                     rep.get("overlapped", 0),
                     rep.get("d2h_bytes_per_batch", 0) / 2**20,
                     rep.get("h2d_bytes_per_batch", 0) / 2**20])
    table(rows, ["config", "wall s", "chunks/s", "tail retraces",
                 "overlapped", "D2H MB/batch", "H2D MB/batch"],
          title=f"Dispatch depth x padding ({n_batches} batches, "
                f"{batch_long_chunks} long chunks each)")

    by = {(r["plan"], r.get("depth"), r.get("bucket")): r for r in recs}
    stream_wall = by[("streaming", None, None)]["wall_s"]
    d_head = 4 if 4 in depths else depths[-1]     # headline depth
    a4 = by[("async", d_head, "pow2")]
    findings = {
        "headline_depth": d_head,
        "async_d4_pow2_beats_streaming": bool(a4["wall_s"] < stream_wall),
        "speedup_vs_streaming": stream_wall / a4["wall_s"],
        "pow2_caps_retraces": all(
            r["tail_retraces"] <= np.ceil(np.log2(
                batch_long_chunks * 12)) + 1
            for r in recs if r.get("bucket") == "pow2"),
        # host-boundary traffic: the batch + idx up, mask + padded
        # cleaned down
        "boundary_per_batch": a4["d2h_bytes_per_batch"]
        + a4["h2d_bytes_per_batch"],
    }
    path = save_json("BENCH_pipeline", {"rows": recs, "findings": findings})
    print(f"\nasync d={d_head} pow2 vs streaming: {stream_wall:.2f}s -> "
          f"{a4['wall_s']:.2f}s "
          f"({findings['speedup_vs_streaming']:.2f}x, "
          f"{'beats' if findings['async_d4_pow2_beats_streaming'] else 'does NOT beat'}"
          f" the one-ahead baseline); host boundary "
          f"{findings['boundary_per_batch'] / 2**20:.2f} MB/batch")
    print(f"record -> {path}")
    return findings


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=16.0)
    ap.add_argument("--batch-long-chunks", type=int, default=2)
    args = ap.parse_args()
    run(minutes=args.minutes, batch_long_chunks=args.batch_long_chunks)


if __name__ == "__main__":
    main()
