#!/usr/bin/env python3
"""Bring-up check: the preprocessing pipeline end to end on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # data parallelism over four chips

One chip (the default) runs three phases on one stream of `--batches`
batches of `--batch-long-chunks` 60 s stereo 44.1 kHz long chunks (the
full SERF geometry: 15 s detection splits, 5 s final splits, the fused
survivor tail):

  1. the main path, `Preprocessor(SERF_AUDIO, plan="async", depth=4)` with
     the kernels in backend mode "auto" (compiled Pallas): a warm-up pass
     over the stream compiles every shape, then a timed pass;
  2. the serving front-end: a `ContinuousBatcher` over
     `WorkerPool(transport="inproc")` answers single-chunk requests, each
     equal to the batch path's result for that chunk;
  3. the same stream with the kernels in "ref" mode (the float32 jnp
     reference, XLA-compiled): equal survivor sets, cleaned audio within
     REF_TOL (`repro.kernels.backend`).

On a host with several chips the one-chip run uses the first of them.

`--chips 4` runs only the four-chip path — the batch split over every
local chip (`make_local_mesh`, the "chunks" sharding rule,
pad_multiple=len(devices)) — and the same stream on one of those chips in
this process, and checks they agree.

Without a TPU, with the kernels forced to another mode
(REPRO_KERNEL_BACKEND), on any failed check or any exception, it exits
non-zero and prints no result. Wall-clock rates are smoke figures from the
host clock, not a benchmark. The last line of a passing run is exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.kernels.backend import REF_TOL  # noqa: E402

# the AsyncPlan dispatch window the main path runs at
DEPTH = 4
# Same compiled kernels, same per-row arithmetic: only XLA's fusion of the
# surrounding jnp code (mono mix, indices, irfft overlap-add) may change
# with the batch shape, a few float32 ulps of unit-scale audio.
SAME_TOL = dict(rtol=0.0, atol=1e-5)

COMPILE_EVENT_PREFIX = "/jax/core/compile/"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Compile seconds (trace + lower + backend compile or cache fetch)
    and program count, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def __call__(self, event, duration, **_):
        if event.startswith(COMPILE_EVENT_PREFIX):
            self.seconds += duration
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def make_batches(seed, n_batches, batch_long_chunks):
    """The seeded synthetic SERF-like stream, made up front (set-up)."""
    from repro.data.loader import audio_batch_maker
    make = audio_batch_maker(seed=seed, batch_long_chunks=batch_long_chunks)
    return [make(w)[0] for w in range(n_batches)]


def run_stream(batches, mode, rules, pad_multiple, depth, meter):
    """A warm-up pass over the stream (compiles every shape), then a timed
    pass. Returns the timed pass's results and its record."""
    from repro.configs import SERF_AUDIO
    from repro.core.plans import Preprocessor
    from repro.kernels import backend

    stream = [(w, (b, None)) for w, b in enumerate(batches)]
    with backend.use(mode):
        pre = Preprocessor(SERF_AUDIO, rules, plan="async", depth=depth,
                           pad_multiple=pad_multiple)
        c0, t0 = meter.seconds, time.perf_counter()
        list(pre.run(stream))
        t1, c1, p1 = time.perf_counter(), meter.seconds, meter.programs
        results = list(pre.run(stream))
        t2 = time.perf_counter()
    if len(results) != len(batches):
        raise AssertionError(f"{mode}: {len(results)} of {len(batches)} "
                             "batches emitted")
    audio_s = sum(b.shape[0] for b in batches) * SERF_AUDIO.long_split_s
    return pre, results, {
        "warmup_s": t1 - t0, "compile_s": c1 - c0, "timed_s": t2 - t1,
        "compiles_in_timed_pass": meter.programs - p1,
        "audio_s": audio_s, "kept": [r.n_kept for r in results]}


def compare(name, got, want, tol):
    """Equal survivor sets batch by batch; cleaned audio within `tol`.
    Returns the largest absolute difference."""
    worst = 0.0
    for a, b in zip(got, want, strict=True):
        if not np.array_equal(np.asarray(a.det.keep), np.asarray(b.det.keep)):
            raise AssertionError(f"{name}: survivor sets differ in batch "
                                 f"{a.wid}")
        if a.cleaned.size:
            worst = max(worst, float(np.abs(a.cleaned - b.cleaned).max()))
        np.testing.assert_allclose(a.cleaned, b.cleaned, err_msg=name, **tol)
    return worst


def serve_requests(chunks, batch_res, n_requests, mode):
    """`n_requests` single-chunk requests through the continuous batcher
    over an in-process worker pool; each must equal `batch_res` (the batch
    path's result for the batch holding these chunks) for its chunk.
    Returns the largest absolute difference."""
    from repro.configs import SERF_AUDIO
    from repro.kernels import backend
    from repro.serve import ContinuousBatcher, WorkerPool

    with backend.use(mode):
        pool = WorkerPool(SERF_AUDIO, workers=1, transport="inproc").start()
        try:
            batcher = ContinuousBatcher(pool=pool, max_batch=n_requests,
                                        linger_s=0.0)
            rids = [batcher.submit(chunks[j]) for j in range(n_requests)]
            recs = [batcher.wait(r, timeout_s=900.0) for r in rids]
        finally:
            pool.shutdown(drain=True)
    keep = np.asarray(batch_res.det.keep)
    per = keep.size // chunks.shape[0]
    offs = np.concatenate([[0], np.cumsum(keep)]).astype(int)
    worst = 0.0
    for j, rec in enumerate(recs):
        if not rec["ok"]:
            raise AssertionError(f"request {j} failed: {rec}")
        lo, hi = j * per, (j + 1) * per
        if not np.array_equal(rec["keep"], keep[lo:hi]):
            raise AssertionError(f"request {j}: survivor set differs from "
                                 "the batch path")
        want = batch_res.cleaned[offs[lo]:offs[hi]]
        np.testing.assert_allclose(rec["cleaned"], want,
                                   err_msg=f"request {j}", **SAME_TOL)
        if want.size:
            worst = max(worst, float(np.abs(rec["cleaned"] - want).max()))
    return worst


def smoke_phase(batches, mode, rules=None, depth=DEPTH, n_requests=2,
                meter=None):
    """Phases 1-3 of the one-chip run on `batches`, the kernels in `mode`
    against "ref"; raises on any miss. Returns a dict of what it
    measured."""
    from repro.distributed.sharding import NULL_RULES

    rules = NULL_RULES if rules is None else rules
    meter = meter or CompileMeter()
    pre, got, rec = run_stream(batches, mode, rules, 1, depth, meter)
    serve_err = serve_requests(batches[0], got[0], n_requests, mode)
    _, want, ref_rec = run_stream(batches, "ref", rules, 1, depth, meter)
    return {"pre": pre, "run": rec, "ref_run": ref_rec,
            "serve_max_abs_err": serve_err,
            "ref_max_abs_err": compare("kernels vs reference", got, want,
                                       REF_TOL)}


def kernels_in(compiled):
    """Names of the Pallas TPU kernels (tpu_custom_call) in a compiled
    program, and the collectives it contains."""
    text = compiled.as_text()
    names = sorted({m for line in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in line
                    for m in re.findall(r'op_name="[^"]*?/(\w+)/pallas_call',
                                        line)})
    colls = {c: text.count(c + "(") + text.count(c + "-start(")
             for c in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter")}
    return names, {c: n for c, n in colls.items() if n}


def program_report(pre, batch_shape, rules):
    """Compile the detect and fused-tail phase programs for this batch
    shape (the persistent cache holds them from the run) and list their
    kernels; both must contain compiled Pallas kernels."""
    import jax
    import jax.numpy as jnp
    from repro.configs import SERF_AUDIO as cfg

    n5 = batch_shape[0] * int(round(cfg.long_split_s / cfg.final_split_s))
    ndev = 1 if rules.mesh is None else rules.mesh.size
    x = jax.ShapeDtypeStruct(batch_shape, jnp.float32,
                             sharding=rules.sharding("chunks", None, None))
    w = jax.ShapeDtypeStruct((n5, cfg.final_split_samples), jnp.float32,
                             sharding=rules.sharding("chunks", None))
    i = jax.ShapeDtypeStruct((8 * ndev,), jnp.int32)
    out = {}
    for kind, args in (("detect", (x,)), ("tail_idx_fused", (w, i))):
        names, colls = kernels_in(jax.jit(pre.phase_fn(kind)).lower(
            *args).compile())
        if not names:
            raise AssertionError(f"the compiled {kind} program holds no "
                                 "tpu_custom_call kernel")
        out[kind] = (names, colls)
    return out


def device_line():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def print_run(label, rec):
    rate = rec["audio_s"] / rec["timed_s"]
    print(f"{label}: warm-up pass {rec['warmup_s']:.2f} s (compile "
          f"{rec['compile_s']:.2f} s), timed pass {rec['timed_s']:.3f} s "
          f"over {len(rec['kept'])} batches, "
          f"{rec['compiles_in_timed_pass']} compiles in the timed pass; "
          f"smoke figure, not a benchmark: {rate:.1f} s of audio per s")
    print(f"{label}: kept per batch {rec['kept']} "
          f"(total {sum(rec['kept'])})")


def first_chip_rules():
    """Rules over a one-device mesh holding only the first chip."""
    import jax
    from jax.sharding import Mesh
    from repro.distributed.sharding import ShardingRules
    return ShardingRules(Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                              ("data", "model")))


def one_chip(args, meter):
    t0 = time.perf_counter()
    batches = make_batches(args.seed, args.batches, args.batch_long_chunks)
    print(f"set-up: {len(batches)} batches of {batches[0].shape} f32 "
          f"({batches[0].nbytes / 2**20:.0f} MiB each) made in "
          f"{time.perf_counter() - t0:.1f} s")
    rules = first_chip_rules()
    out = smoke_phase(batches, "auto", rules=rules, n_requests=args.requests,
                      meter=meter)
    print_run("main path (compiled Pallas)", out["run"])
    print_run("reference (jnp, XLA)", out["ref_run"])
    for kind, (names, colls) in program_report(
            out["pre"], batches[0].shape, rules).items():
        print(f"compiled {kind} program: tpu_custom_call kernels {names}"
              + (f", collectives {colls}" if colls else ""))
    print(f"serving: {args.requests} requests through ContinuousBatcher + "
          f"WorkerPool(inproc) equal the batch path (survivor sets equal, "
          f"max abs diff {out['serve_max_abs_err']:.3g})")
    print(f"reference: survivor sets equal in all {len(batches)} batches, "
          f"max abs error {out['ref_max_abs_err']:.3g} (tolerance "
          f"{REF_TOL})")


def four_chips(args, meter):
    import jax
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_local_mesh

    n = len(jax.devices())
    if n < 4:
        raise RuntimeError(f"--chips 4 needs four devices, JAX sees {n}")
    batches = make_batches(args.seed, args.batches, args.batch_long_chunks)
    rules = ShardingRules(make_local_mesh())
    pre, got, rec = run_stream(batches, "auto", rules, n, DEPTH, meter)
    print_run(f"{n} chips (batch split over {rules.mesh.shape})", rec)
    for kind, (names, colls) in program_report(
            pre, batches[0].shape, rules).items():
        print(f"compiled {kind} program ({n} chips): tpu_custom_call "
              f"kernels {names}, collectives {colls}")
    _, want, rec1 = run_stream(batches, "auto", first_chip_rules(), 1, DEPTH,
                               meter)
    print_run("1 chip (same process)", rec1)
    err = compare(f"{n} chips vs 1 chip", got, want, SAME_TOL)
    print(f"{n} chips vs 1 chip: survivor sets equal in all "
          f"{len(batches)} batches, max abs diff {err:.3g} (tolerance "
          f"{SAME_TOL})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--batches", type=int, default=5,
                    help="batches in the stream: every one is timed after "
                         "a warm-up pass (default 5)")
    ap.add_argument("--batch-long-chunks", type=int, default=16)
    ap.add_argument("--requests", type=int, default=4,
                    help="single-chunk serving requests (one chip)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime import enable_compile_cache, require_chip
    cache = enable_compile_cache()
    require_chip()
    from repro.kernels import backend
    dev = device_line()
    print(f"device: platform {dev['platform']}, device_kind {dev['kind']}, "
          f"count {dev['count']}")
    print(f"kernel backend: mode {backend.get_mode()!r} resolves to "
          f"(use_pallas, interpret) = {backend.resolve()}")
    print(f"compile cache: {cache}")
    meter = CompileMeter().install()
    if args.chips == 4:
        four_chips(args, meter)
    else:
        one_chip(args, meter)
    print(f"peak_bytes_in_use (device 0): {peak_bytes()}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
