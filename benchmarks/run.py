"""Benchmark aggregator: one bench per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full]
  PYTHONPATH=src python -m benchmarks.run --smoke   # executor regression gate

Order matters: stage-time calibration feeds the DES benches; comm feeds the
DES transfer model. The roofline table prints from the dry-run records.
"""
from __future__ import annotations

import argparse
import time
import traceback


def smoke(chaos_seed=None):
    """One tiny batch stream through EVERY registered execution plan:
    survivor sets must match bit-for-bit and cleaned audio to rtol=1e-4, so
    executor regressions fail fast (scripts/verify.sh runs this). Then the
    sharded fault-tolerance gate: 2 simulated shards with a forced lease
    expiry AND a mid-stream worker crash must finish with redeliveries >= 1
    and zero lost or duplicated chunks. Then the PROCESS-mode FT gate: the
    same recovery story on 2 REAL worker processes over the proc
    transport, one SIGKILLed mid-stream — zero lost/duplicate chunks,
    output bit-identical to two_phase. Then the STORE-DATA-PLANE gate: the
    same stream over 2 real workers on the TCP transport twice, socket
    plane vs store plane — the store run must cut the socket's data-plane
    bytes (dist_fetch_bytes_total + dist_push_bytes_total, per plane) by
    >= 90% while staying bit-identical. Then the cache gate: the same tiny
    stream twice through CachedPlan over a fresh store — the second pass
    must be >= 90% hits with survivor masks bit-identical to the uncached
    reference. Then the async-pipeline gate: `--plan async --depth 4` on a
    tiny stream must emit every chunk id exactly once IN INPUT ORDER,
    bit-identical to two_phase, with >= 1 overlapped dispatch visible in
    the per-batch timing records. Finally the SERVING gate: a pool of 2
    persistent proc workers behind the continuous batcher takes 12
    concurrent requests including one deadline miss and one worker
    SIGKILL mid-request — every surviving request must resolve exactly
    once, bit-identical to two_phase, with the killed worker's lease
    redelivered. Finally the FUSED-TAIL gate: two_phase with the fused
    single-pass survivor tail vs the staged per-stage tail, bitwise in the
    ref backend, equal masks and cleaned audio within backend.REF_TOL of
    ref in the interpret backend, pad rows zero.
    Then the OBSERVABILITY gate: the driver over 2 real proc workers
    with --trace + --telemetry must yield a schema-valid Chrome trace with
    worker events parented under the master's run span and exactly one
    durable telemetry record per chunk. Finally the CHAOS gate: seeded
    randomized schedules (SIGKILL, mid-run join, graceful drain, SIGSTOP
    stall — at least one of each) fired against 2+ REAL proc workers
    while the stream runs, every chunk exactly once and bit-identical to
    two_phase, plus an injected-straggler scenario where the last chunk's
    holder is SIGSTOPped: an idle survivor must win the speculative
    duplicate lease and the losing incarnation must be attributed in the
    durable telemetry under reason "speculated". Any failing schedule is
    reproducible via --chaos-seed (the seed is printed in the failure)."""
    import numpy as np
    from repro.configs import SERF_AUDIO as cfg
    from repro.core.plans import PLANS, Preprocessor
    from repro.data.synthetic import generate_labelled

    audio, _ = generate_labelled(0, 2 * 12, segment_s=5.0)
    S5 = audio.shape[-1]
    chunks = (audio.reshape(2, 12, 2, S5).transpose(0, 2, 1, 3)
              .reshape(2, 2, 12 * S5))
    stream = [(0, (chunks[:1], None)), (1, (chunks[1:], None))]
    ref_name = ref = None
    failures = []
    for name in sorted(PLANS):
        t0 = time.time()
        try:
            pre = Preprocessor(cfg, plan=name, pad_multiple=1)
            results = sorted(pre.run(stream), key=lambda r: r.wid)
            keep = np.concatenate([np.asarray(r.det.keep) for r in results])
            cleaned = np.concatenate([r.cleaned for r in results])
            assert np.isfinite(cleaned).all(), "non-finite output"
            assert cleaned.shape[0] == int(keep.sum())
            if ref is None:
                ref_name, ref = name, (keep, cleaned)
            else:
                np.testing.assert_array_equal(keep, ref[0])
                np.testing.assert_allclose(cleaned, ref[1],
                                           rtol=1e-4, atol=1e-5)
            print(f"plan {name:10s} OK: {cleaned.shape[0]}/{keep.size} "
                  f"survivors in {time.time() - t0:.1f}s"
                  + ("" if ref[1] is cleaned else f" (== {ref_name})"))
        except Exception:
            failures.append(name)
            traceback.print_exc()
    try:
        _ft_smoke(np, cfg, Preprocessor)
    except Exception:
        failures.append("sharded-ft")
        traceback.print_exc()
    try:
        _proc_ft_smoke(np, cfg, Preprocessor)
    except Exception:
        failures.append("proc-ft")
        traceback.print_exc()
    try:
        _store_plane_smoke(np, cfg, Preprocessor)
    except Exception:
        failures.append("store-plane")
        traceback.print_exc()
    try:
        _cache_smoke(np, cfg, Preprocessor, stream, ref)
    except Exception:
        failures.append("cache")
        traceback.print_exc()
    try:
        _async_smoke(np, cfg, Preprocessor)
    except Exception:
        failures.append("async-pipeline")
        traceback.print_exc()
    try:
        _serving_smoke(np, cfg, Preprocessor)
    except Exception:
        failures.append("serving")
        traceback.print_exc()
    try:
        _fused_smoke(np, cfg, Preprocessor)
    except Exception:
        failures.append("fused-tail")
        traceback.print_exc()
    try:
        _obs_smoke()
    except Exception:
        failures.append("obs")
        traceback.print_exc()
    try:
        _chaos_smoke(np, cfg, Preprocessor, chaos_seed=chaos_seed)
    except Exception:
        failures.append("chaos")
        traceback.print_exc()
    n_gates = len(PLANS) + 9
    print(f"\nsmoke: {n_gates - len(failures)}/{n_gates} "
          f"gates OK" + (f"; FAILED: {failures}" if failures else ""))
    raise SystemExit(1 if failures else 0)


def _ft_smoke(np, cfg, Preprocessor):
    """ShardedPlan recovery gate: a lease forced to expire before the run
    plus shard 1 crashing mid-stream; every chunk id must come out exactly
    once, with at least one queue redelivery."""
    from repro.data.loader import audio_batch_maker, make_shard_pool
    from repro.data.queue import SettableClock, WorkQueue
    from repro.ft.failure import CrashInjector

    t0 = time.time()
    n_batches = 5
    clock = SettableClock()
    queue = WorkQueue(n_batches, lease_timeout_s=30.0, clock=clock)
    ghost = queue.lease("ghost", 1)        # a worker that died pre-run
    clock.t = 31.0                         # ... and whose lease has expired
    injector = CrashInjector()
    injector.kill(1, after_items=1)        # shard 1 dies mid-stream
    make = audio_batch_maker(seed=3, batch_long_chunks=2)
    pool = make_shard_pool(make, n_batches, 2, queue=queue)
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                       injector=injector)
    results = list(pre.run(pool))
    wids = sorted(r.wid for r in results)
    assert wids == list(range(n_batches)), \
        f"lost/duplicated chunks: emitted {wids}"
    assert pre.plan.redeliveries >= 1, "expected at least one redelivery"
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    for r in sorted(results, key=lambda r: r.wid):
        want = ref(make(r.wid)[0])
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(want.det.keep))
        np.testing.assert_allclose(r.cleaned, want.cleaned,
                                   rtol=1e-4, atol=1e-5)
    print(f"plan sharded-ft OK: wid {ghost[0]} redelivered after forced "
          f"lease expiry, shard 1 crashed, {len(wids)}/{n_batches} chunk "
          f"ids exactly once, redeliveries={pre.plan.redeliveries} "
          f"in {time.time() - t0:.1f}s")


def _proc_ft_smoke(np, cfg, Preprocessor):
    """REAL-process fault-tolerance gate: 2 worker processes over the proc
    transport, one SIGKILLed mid-stream while holding a lease; every chunk
    id must come out exactly once, bit-identical to the in-process
    two_phase plan, with the lost lease redelivered to the survivor."""
    from repro.data.loader import audio_batch_maker, make_shard_pool
    from repro.ft.failure import CrashInjector

    t0 = time.time()
    n_batches = 5
    make = audio_batch_maker(seed=3, batch_long_chunks=2)
    pool = make_shard_pool(make, n_batches, 2, lease_timeout_s=120.0)
    injector = CrashInjector()
    # after_items=0: shard1 is SIGKILLed the moment its FIRST lease is
    # granted — deterministic under any compile-time skew (a later fuse
    # could never burn if the other worker drained the queue first)
    injector.kill(1, after_items=0)
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                       transport="proc", injector=injector)
    results = list(pre.run(pool))
    wids = [r.wid for r in results]
    assert wids == list(range(n_batches)), \
        f"lost/duplicated/misordered chunks: emitted {wids}"
    assert pre.plan.redeliveries >= 1, "expected at least one redelivery"
    assert injector.crashed == frozenset({1}), "shard1 was not SIGKILLed"
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    for r in results:
        want = ref(make(r.wid)[0])
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(want.det.keep))
        np.testing.assert_array_equal(r.cleaned, want.cleaned)
    done = {st.worker: st.chunks_done for st in pre.plan.worker_stats}
    print(f"plan proc-ft    OK: 2 real worker processes, shard1 SIGKILLed "
          f"holding a lease, {len(wids)}/{n_batches} chunk ids exactly "
          f"once (per-worker {done}), redeliveries="
          f"{pre.plan.redeliveries}, cleaned bit-identical to two_phase "
          f"in {time.time() - t0:.1f}s")


def _store_plane_smoke(np, cfg, Preprocessor):
    """Store-data-plane gate: the same seeded stream over 2 REAL worker
    processes on the TCP transport (loopback) twice — once on the socket
    data plane (chunk batches and result payloads cross the master's
    control socket) and once on the store data plane (bytes move through
    a shared ChunkStore; the socket carries content keys). The store run
    must cut the master's data-plane socket bytes by >= 90% — measured
    from dist_fetch_bytes_total{plane} + dist_push_bytes_total{plane} —
    with ZERO payload bytes on the socket plane, and both runs must be
    bit-identical to each other and to two_phase."""
    import shutil
    import tempfile

    from repro.data.loader import audio_batch_maker, make_shard_pool
    from repro.obs import metrics as obs_metrics

    t0 = time.time()
    n_batches = 4
    make = audio_batch_maker(seed=11, batch_long_chunks=1)
    reg = obs_metrics.get_registry()

    def plane_bytes(plane):
        return sum(
            reg.counter(name, labels=("plane",)).labels(plane=plane).value
            for name in ("dist_fetch_bytes_total", "dist_push_bytes_total"))

    tmp = tempfile.mkdtemp(prefix="smoke_dplane_")
    try:
        runs, wire = {}, {}
        for mode in ("socket", "store"):
            pool = make_shard_pool(make, n_batches, 2,
                                   lease_timeout_s=120.0)
            kw = {"data_plane": tmp} if mode == "store" else {}
            before = {p: plane_bytes(p) for p in ("socket", "store")}
            pre = Preprocessor(cfg, plan="sharded", shards=2,
                               pad_multiple=1, transport="tcp",
                               lease_items=2, **kw)
            runs[mode] = sorted(pre.run(pool), key=lambda r: r.wid)
            delta = {p: plane_bytes(p) - before[p]
                     for p in ("socket", "store")}
            wire[mode] = delta[mode]
            other = "store" if mode == "socket" else "socket"
            assert delta[other] == 0, \
                f"{mode} run leaked {delta[other]} bytes onto the " \
                f"{other} plane"
            wids = [r.wid for r in runs[mode]]
            assert wids == list(range(n_batches)), \
                f"{mode} run lost/duplicated chunks: {wids}"
        ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
        for a, b in zip(runs["socket"], runs["store"]):
            want = ref(make(a.wid)[0])
            for r in (a, b):
                np.testing.assert_array_equal(np.asarray(r.det.keep),
                                              np.asarray(want.det.keep))
                np.testing.assert_array_equal(r.cleaned, want.cleaned)
        cut = 1.0 - wire["store"] / wire["socket"]
        assert cut >= 0.9, \
            f"store plane cut only {cut:.1%} of data-plane socket bytes " \
            f"({wire['store']:.0f} vs {wire['socket']:.0f})"
        print(f"plan store-dp   OK: 2 real workers over tcp, store plane "
              f"carried {wire['store']:.0f} B of keys vs "
              f"{wire['socket']:.0f} B of payloads on the socket plane "
              f"({cut:.1%} cut), bit-identical to two_phase, "
              f"in {time.time() - t0:.1f}s")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _cache_smoke(np, cfg, Preprocessor, stream, ref):
    """CachedPlan gate: the same tiny stream twice over a fresh store —
    pass 2 must be >= 90% cache hits and its survivor masks / cleaned
    audio must match the uncached plan-equivalence reference."""
    import shutil
    import tempfile

    t0 = time.time()
    store_dir = tempfile.mkdtemp(prefix="smoke_cache_")
    try:
        for pass_no in (1, 2):
            pre = Preprocessor(cfg, plan="cached", inner="two_phase",
                               store=store_dir, pad_multiple=1)
            results = sorted(pre.run(stream), key=lambda r: r.wid)
            keep = np.concatenate([np.asarray(r.det.keep) for r in results])
            cleaned = np.concatenate([r.cleaned for r in results])
            np.testing.assert_array_equal(keep, ref[0])
            np.testing.assert_allclose(cleaned, ref[1],
                                       rtol=1e-4, atol=1e-5)
        st = pre.plan.stats
        assert st.hit_rate >= 0.9, \
            f"warm pass hit rate {st.hit_rate:.0%} < 90%"
        print(f"plan cache      OK: warm pass {st.hits}/{st.hits + st.misses}"
              f" hits, masks bit-identical to uncached, "
              f"in {time.time() - t0:.1f}s")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)


def _async_smoke(np, cfg, Preprocessor):
    """Depth-4 async executor gate: a 5-batch stream must come out in
    input order with zero lost/duplicated chunks, bit-identical to
    two_phase, and the timing records must show at least one dispatch that
    overlapped earlier in-flight work (the whole point of the window)."""
    from repro.data.loader import audio_batch_maker

    t0 = time.time()
    n_batches = 5
    make = audio_batch_maker(seed=5, batch_long_chunks=2)
    stream = [(w, (make(w)[0], None)) for w in range(n_batches)]
    pre = Preprocessor(cfg, plan="async", depth=4, pad_multiple=1)
    results = list(pre.run(stream))
    wids = [r.wid for r in results]
    assert wids == list(range(n_batches)), \
        f"async emitted out of order / lost chunks: {wids}"
    overlapped = sum(1 for t in pre.plan.last_timings
                     if t.get("in_flight", 1) >= 2)
    assert overlapped >= 1, "no overlapped dispatch in the timing record"
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    for r in results:
        want = ref(make(r.wid)[0])
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(want.det.keep))
        np.testing.assert_array_equal(r.cleaned, want.cleaned)
    print(f"plan async-pipe OK: depth 4, {len(wids)}/{n_batches} chunk ids "
          f"in order, {overlapped} overlapped dispatches, cleaned "
          f"bit-identical to two_phase in {time.time() - t0:.1f}s")


def _serving_smoke(np, cfg, Preprocessor):
    """Serving-subsystem gate: a pool of 2 persistent PROC workers behind
    the continuous batcher takes 12 concurrent requests, one with an
    already-expired deadline (must fail, never reach a batch) and one
    worker SIGKILLed the moment it is granted its first lease (its work
    must be redelivered to the survivor). Every surviving request must
    resolve exactly once, bit-identical to the in-process two_phase plan
    on the same assembled batches."""
    from repro.data.loader import audio_batch_maker
    from repro.ft.failure import CrashInjector
    from repro.serve import ContinuousBatcher, WorkerPool

    t0 = time.time()
    n_req = 12
    make = audio_batch_maker(seed=7, batch_long_chunks=1)
    chunks = [make(w)[0][0] for w in range(n_req)]
    pool = WorkerPool(cfg, workers=2, transport="proc", respawn=False,
                      poll_s=0.01).start()
    try:
        injector = CrashInjector()
        injector.kill(0, after_items=0)   # shard0 dies on its 1st grant
        injector.attach(0, pool.pids[0])
        pool.service.on_grant = lambda worker, wid: injector.on_pull(
            pool.service.workers[worker].shard)

        batcher = ContinuousBatcher(pool=pool, max_batch=4, linger_s=0.05)
        rids, doomed = [], None
        for i, c in enumerate(chunks):
            if i == 5:                    # one deadline miss, mid-queue
                doomed = batcher.submit(c, timeout_s=0.0)
                rids.append(doomed)
            else:
                rids.append(batcher.submit(c))
        records = {}
        stall = time.time() + 420
        while len(records) < n_req:
            for rid in batcher.pump():
                records[rid] = batcher.result(rid)
            assert time.time() < stall, \
                f"serving smoke stalled ({len(records)}/{n_req} resolved)"
            time.sleep(0.005)

        # exactly-once: every record was popped exactly once
        assert all(batcher.result(r) is None for r in rids)
        assert records[doomed]["ok"] is False \
            and records[doomed]["error"] == "deadline"
        assert all(e["rids"].count(doomed) == 0
                   for e in batcher.batch_log), \
            "an expired request reached a dispatched batch"
        survivors = [r for r in rids if r != doomed]
        assert all(records[r]["ok"] for r in survivors)
        assert injector.crashed == frozenset({0}), "shard0 not SIGKILLed"
        assert pool.queue.redeliveries >= 1
        assert pool.queue.redelivered_from["shard0"] >= 1

        ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
        by_rid = dict(zip(rids, chunks))
        checked = 0
        for e in batcher.batch_log:
            batch = np.stack([by_rid[r] for r in e["rids"]])
            if e["rows"] > e["n_real"]:
                batch = np.concatenate([batch, np.zeros(
                    (e["rows"] - e["n_real"],) + batch.shape[1:],
                    np.float32)])
            want = ref(batch)
            keep = np.asarray(want.det.keep)
            per = keep.size // e["rows"]
            offs = np.concatenate([[0], np.cumsum(keep)]).astype(int)
            for j, rid in enumerate(e["rids"]):
                lo, hi = j * per, (j + 1) * per
                np.testing.assert_array_equal(records[rid]["keep"],
                                              keep[lo:hi])
                np.testing.assert_array_equal(
                    records[rid]["cleaned"], want.cleaned[offs[lo]:offs[hi]])
                checked += 1
        assert checked == len(survivors)
        print(f"plan serving    OK: 2 proc workers, {len(survivors)}/"
              f"{n_req} requests exactly-once + bit-identical "
              f"(1 deadline miss, shard0 SIGKILLed, redeliveries="
              f"{pool.queue.redeliveries}) in {time.time() - t0:.1f}s")
    finally:
        pool.shutdown(drain=False)


def _fused_smoke(np, cfg, Preprocessor):
    """Fused-survivor-tail gate: two_phase with the fused single-pass tail
    vs two_phase with the staged per-stage tail on the same tiny stream.
    In the ref backend both compose the float32 reference: masks AND
    cleaned audio bitwise equal. In the interpret backend (the kernels'
    own arithmetic) both must give the reference's survivor sets and its
    cleaned audio within backend.REF_TOL. Pad-index slots must come through
    the fused kernel as exactly-zero rows (fill-gather semantics)."""
    import jax.numpy as jnp
    from repro.core.graph import PipelineGraph
    from repro.data.loader import audio_batch_maker
    from repro.kernels import backend

    t0 = time.time()
    make = audio_batch_maker(seed=9, batch_long_chunks=1)
    stream = [(w, (make(w)[0], None)) for w in range(2)]
    ref = None
    for mode in ("ref", "interpret"):
        with backend.use(mode):
            staged = Preprocessor(cfg, plan="two_phase", pad_multiple=1,
                                  fuse_tail=False)
            fused = Preprocessor(cfg, plan="two_phase", pad_multiple=1,
                                 fuse_tail=True)
            assert fused.plan.fuse_tail is True
            got = []
            for a, b in zip(staged.run(stream), fused.run(stream)):
                keep = np.asarray(a.det.keep)
                np.testing.assert_array_equal(keep, np.asarray(b.det.keep))
                got.append((keep, a.cleaned, b.cleaned))
        if ref is None:                 # the ref backend runs first
            ref = got
            for _, a, b in got:
                np.testing.assert_array_equal(a, b)
            continue
        for (keep, a, b), (want_keep, want, _) in zip(got, ref):
            np.testing.assert_array_equal(keep, want_keep)
            np.testing.assert_allclose(a, want, **backend.REF_TOL)
            np.testing.assert_allclose(b, want, **backend.REF_TOL)
    # pad rows: out-of-range survivor slots -> exactly-zero output rows
    g = PipelineGraph(cfg)
    rng = np.random.RandomState(0)
    wave = jnp.asarray(rng.randn(4, cfg.final_split_samples)
                       .astype(np.float32))
    idx = jnp.asarray([2, 99, 0], jnp.int32)
    with backend.use("ref"):
        out = np.asarray(g.tail_indexed_fused(wave, idx))
    assert not out[1].any() and out[0].any() and out[2].any()
    print(f"plan fused-tail OK: fused == staged bitwise in ref, both "
          f"within {backend.REF_TOL} of ref in interpret, pad rows zero, in "
          f"{time.time() - t0:.1f}s")


def _obs_smoke():
    """Observability gate: the real driver (`launch.preprocess`) over 2
    REAL proc workers with `--trace` + `--telemetry` must produce (a) a
    schema-valid Chrome trace (validate_chrome_trace: required keys, known
    phases, X events carry dur, B/E balance LIFO per pid/tid) in which
    worker-process events carry a different pid than the master AND are
    parented under the master's run span across the pickle boundary, and
    (b) exactly ONE durable telemetry 'done' record per chunk, written
    master-side at acceptance with an accept timestamp."""
    import json
    import os
    import shutil
    import tempfile

    from repro.launch import preprocess as launch_pre
    from repro.obs import telemetry as obs_telemetry
    from repro.obs import tracing as obs_tracing

    t0 = time.time()
    n_batches = 2          # --minutes 4 / --batch-long-chunks 2
    tmp = tempfile.mkdtemp(prefix="smoke_obs_")
    trace_path = os.path.join(tmp, "trace.json")
    tdir = os.path.join(tmp, "telemetry")
    prev_tracer = obs_tracing.get_tracer()
    try:
        launch_pre.main([
            "--minutes", "4", "--batch-long-chunks", "2",
            "--plan", "sharded", "--transport", "proc", "--shards", "2",
            "--trace", trace_path, "--telemetry", tdir])
        with open(trace_path) as f:
            data = json.load(f)
        counts = obs_tracing.validate_chrome_trace(data)
        events = data["traceEvents"]
        trace_id = data["otherData"]["trace_id"]
        run_span = trace_id + ":0"
        master_pid = os.getpid()
        roots = [e for e in events
                 if e["name"] == "preprocess_run" and e["ph"] == "B"]
        assert len(roots) == 1 and roots[0]["pid"] == master_pid \
            and roots[0]["args"]["span"] == run_span
        worker_evs = [e for e in events if e["pid"] != master_pid]
        assert worker_evs, "no worker-process events reached the trace"
        assert all(e["args"].get("trace") == trace_id for e in worker_evs)
        # 'E' closers carry no parent by design; every opener/complete must
        assert all(e["args"].get("parent") == run_span
                   for e in worker_evs if e["ph"] != "E"), \
            "worker events not parented under the master run span"
        assert any(e["name"] == "compute" for e in worker_evs)

        recs = obs_telemetry.read_records(tdir)
        done = [r for r in recs if r.get("status") == "done"]
        wids = sorted(r["wid"] for r in done)
        assert wids == list(range(n_batches)), \
            f"telemetry done records not exactly-once per chunk: {wids}"
        assert all(r.get("accept_ts") and r.get("worker") for r in done)
        print(f"plan obs        OK: proc run traced ({len(events)} events, "
              f"phases {counts}), {len(worker_evs)} worker events parented "
              f"under the run span, {len(done)}/{n_batches} telemetry "
              f"records exactly once, in {time.time() - t0:.1f}s")
    finally:
        obs_tracing.set_tracer(prev_tracer)
        shutil.rmtree(tmp, ignore_errors=True)


def _chaos_smoke(np, cfg, Preprocessor, chaos_seed=None):
    """Elastic-fleet chaos gate. N distinct seeded schedules — each mixing
    at least one SIGKILL, one mid-run join, one graceful drain and one
    SIGSTOP stall — fire against REAL proc workers while the stream runs:
    every chunk must come out exactly once, masks AND cleaned audio
    bit-identical to two_phase, every scheduled event must fire, and at
    least one lease redelivery and one registered late joiner must be
    observed across the schedules. Then the injected-straggler speculation
    scenario: the holder of the LAST chunk is SIGSTOPped at grant; an idle
    survivor must win a speculative duplicate lease, with the losing
    incarnation attributed in durable telemetry under reason
    "speculated". Every failure message carries the seed that reproduces
    the schedule (`--chaos-seed`)."""
    from repro.data.loader import audio_batch_maker, make_shard_pool
    from repro.ft.chaos import ACTIONS, ChaosRunner, make_schedule

    t0 = time.time()
    seeds = [int(chaos_seed)] if chaos_seed is not None else [11, 23, 37]
    n_batches = 6
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    total_redeliveries = total_specs = 0
    joined_names = []
    for seed in seeds:
        t1 = time.time()
        make = audio_batch_maker(seed=seed, batch_long_chunks=2)
        pool = make_shard_pool(make, n_batches, 2, lease_timeout_s=300.0)
        pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                           transport="proc", elastic=True)
        schedule = make_schedule(seed, n_batches)
        runner = ChaosRunner(pre.plan, pool, schedule, seed=seed)
        tag = (f"[chaos seed {seed}] reproduce with: PYTHONPATH=src "
               f"python -m benchmarks.run --smoke --chaos-seed {seed}")
        try:
            results, fired = runner.run()
            wids = sorted(r.wid for r in results)
            assert wids == list(range(n_batches)), \
                f"lost/duplicated chunks: emitted {wids}"
            unfired = [e.action for e in schedule if not e.fired]
            assert not unfired, f"events never fired: {unfired}"
            by_action = {a: sum(1 for e in fired if e.action == a)
                         for a in ACTIONS}
            assert all(by_action[a] >= 1 for a in ACTIONS), \
                f"schedule incomplete: {by_action}"
            for r in sorted(results, key=lambda r: r.wid):
                want = ref(make(r.wid)[0])
                np.testing.assert_array_equal(np.asarray(r.det.keep),
                                              np.asarray(want.det.keep))
                np.testing.assert_array_equal(r.cleaned, want.cleaned)
        except Exception as e:
            raise AssertionError(f"{tag}: {e}") from e
        names = {st.worker for st in pre.plan.worker_stats}
        joined_names += [f"shard{e.target}" for e in fired
                         if e.action == "join"
                         and f"shard{e.target}" in names]
        total_redeliveries += pre.plan.redeliveries
        total_specs += pre.plan.speculations
        print(f"  chaos seed {seed}: {len(wids)}/{n_batches} exactly once "
              f"+ bit-identical under {by_action}, redeliveries="
              f"{pre.plan.redeliveries} in {time.time() - t1:.1f}s")
    assert total_redeliveries >= 1, \
        "no schedule produced a lease redelivery"
    assert joined_names, \
        "no late joiner ever registered with the membership registry"
    spec_worker, spec_plan = _chaos_speculation_smoke(np, cfg, Preprocessor,
                                                      ref)
    total_specs += spec_plan.speculations
    print(f"plan chaos      OK: {len(seeds)} seeded schedules "
          f"(seeds {seeds}) exactly once + bit-identical, "
          f"redeliveries={total_redeliveries}, late joiners registered "
          f"{sorted(set(joined_names))}, speculations={total_specs} "
          f"({spec_worker} lost the duplicate-lease race, attributed "
          f"in telemetry) in {time.time() - t0:.1f}s")


def _chaos_speculation_smoke(np, cfg, Preprocessor, ref):
    """Injected-straggler speculation scenario (the deterministic arm of
    the chaos gate): factor-0 detector = every in-flight chunk counts as
    a straggler once any history exists, so the moment the pending queue
    empties, the idle worker receives a speculative duplicate of the
    SIGSTOPped holder's chunk and wins the race."""
    import shutil
    import tempfile
    import threading

    from repro.data.loader import audio_batch_maker, make_shard_pool
    from repro.obs.telemetry import (TelemetryWriter, read_records,
                                     worker_ledger)

    n_batches = 6
    make = audio_batch_maker(seed=7, batch_long_chunks=1)
    pool = make_shard_pool(make, n_batches, 2, lease_timeout_s=300.0)
    tdir = tempfile.mkdtemp(prefix="smoke_chaos_spec_")
    telem = TelemetryWriter(tdir)
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                       transport="proc", speculate=True,
                       straggler_factor=0.0, straggler_min_history=1,
                       telemetry=telem)
    plan = pre.plan
    results, err, stalled = [], [], []

    def consume():
        try:
            results.extend(plan.run(pool))
        except BaseException as e:      # noqa: BLE001 — reraised below
            err.append(e)

    def on_grant(worker, wid):
        # the LAST chunk's holder becomes a genuine straggler: stopped
        # long enough that the idle survivor computes the duplicate first
        if wid == n_batches - 1 and not stalled:
            stalled.append(worker)
            plan.fleet.stall(plan.fleet.service.workers[worker].shard,
                             20.0)

    t = threading.Thread(target=consume, daemon=True,
                         name="chaos-spec-consumer")
    t.start()
    try:
        while plan.fleet is None and t.is_alive():
            time.sleep(0.01)
        if plan.fleet is not None:
            plan.fleet.service.on_grant = on_grant
        t.join(600.0)
        assert not t.is_alive(), "speculation scenario hung"
        if err:
            raise err[0]
        telem.close()
        wids = sorted(r.wid for r in results)
        assert wids == list(range(n_batches)), \
            f"lost/duplicated chunks: emitted {wids}"
        for r in results:
            want = ref(make(r.wid)[0])
            np.testing.assert_array_equal(np.asarray(r.det.keep),
                                          np.asarray(want.det.keep))
            np.testing.assert_array_equal(r.cleaned, want.cleaned)
        assert stalled, "the last chunk was never granted?"
        assert plan.speculations >= 1, \
            "no speculative duplicate lease was granted"
        assert plan.speculations_lost >= 1, \
            "both incarnations of the speculated chunk vanished"
        recs = read_records(tdir)
        lost = [r for r in recs if r.get("status") == "redelivered"
                and r.get("reason") == "speculated"]
        assert lost, "losing incarnation not attributed in telemetry"
        led = worker_ledger(recs)
        losers = [w for w, e in led.items() if e["speculation_lost"]]
        assert losers, "worker ledger shows no speculation_lost breakout"
        done = sorted(r["wid"] for r in recs if r.get("status") == "done")
        assert done == list(range(n_batches)), \
            f"telemetry done records not exactly-once: {done}"
        return losers[0], plan
    finally:
        telem.close()
        shutil.rmtree(tdir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on 1 CPU core)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny batch through every execution plan, then exit")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="run the chaos gate with this single schedule "
                         "seed (reproduce a failing schedule; default: "
                         "the gate's own seed set)")
    args = ap.parse_args()
    if args.smoke:
        smoke(chaos_seed=args.chaos_seed)
    minutes = 16.0 if args.full else 2.0
    hours = 2.0

    from benchmarks import (bench_stage_times, bench_two_split,
                            bench_detector_accuracy, bench_split_accuracy,
                            bench_comm, bench_config_search, bench_scaling,
                            bench_load_balance, bench_utilization,
                            bench_early_exit, bench_cache,
                            bench_dispatch_depth, bench_queue_depth,
                            bench_serving, bench_fused_tail,
                            bench_obs_overhead, bench_chaos,
                            bench_scaling_real)
    steps = [
        ("Table 1 / Fig 1: stage times",
         lambda: bench_stage_times.run(minutes=minutes)),
        ("Fig 2: two-split HPF",
         lambda: bench_two_split.run(minutes=min(minutes, 4.0))),
        ("Fig 10: communication",
         lambda: bench_comm.run(minutes=4.0 if not args.full else 30.0)),
        ("Tables 2-3 / Fig 3: detector accuracy vs MMSE",
         lambda: bench_detector_accuracy.run(minutes=max(4.0, minutes))),
        ("Tables 4-6 / Figs 4-7: split-length accuracy",
         lambda: bench_split_accuracy.run(minutes=max(6.0, minutes))),
        ("Table 7: config search",
         lambda: bench_config_search.run(hours=hours)),
        ("Table 7: queue depth (lease batching)",
         lambda: bench_queue_depth.run(
             minutes=8.0 if not args.full else 16.0)),
        ("Figs 11-13: scaling", lambda: bench_scaling.run(hours=hours)),
        ("Figs 11-12 measured: real-process scaling (tcp + store plane)",
         lambda: bench_scaling_real.run(
             shards=(1, 2, 4, 8, 16) if args.full else (1, 2, 4))),
        ("Figs 14-18: load balance",
         lambda: bench_load_balance.run(hours=hours)),
        ("Figs 19-20: utilisation",
         lambda: bench_utilization.run(hours=hours)),
        ("Headline: early-exit economy (on-device)",
         lambda: bench_early_exit.run(minutes=4.0)),
        ("Store: cold/warm/partial-overlap cache economics",
         lambda: bench_cache.run(minutes=8.0 if not args.full else 32.0)),
        ("Pipeline: dispatch depth x survivor buckets",
         lambda: bench_dispatch_depth.run(
             minutes=16.0 if not args.full else 32.0)),
        ("Serving: worker pool + continuous batching p50/p99",
         lambda: bench_serving.run(
             minutes=6.0 if not args.full else 16.0)),
        ("Kernel: fused survivor tail vs staged",
         lambda: bench_fused_tail.run(reps=2 if not args.full else 4)),
        ("Observability: off/metrics/full overhead",
         lambda: bench_obs_overhead.run(reps=2 if not args.full else 4)),
        ("Elasticity: membership overhead + speculative tail cut",
         lambda: bench_chaos.run()),
    ]
    failures = []
    for name, fn in steps:
        print(f"\n{'=' * 72}\n>> {name}\n{'=' * 72}", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"[{name}] done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()

    print(f"\n{'=' * 72}\n>> Roofline (from dry-run records, if present)\n"
          f"{'=' * 72}", flush=True)
    try:
        from benchmarks import roofline
        recs = roofline.load_records("results/dryrun_final.json")
        recs += roofline.load_records("results/dryrun_audio_final.json")
        if recs:
            roofline.fmt_table(recs)
        else:
            print("no dry-run records yet (run repro.launch.dryrun --all)")
    except Exception:
        failures.append("roofline")
        traceback.print_exc()

    print(f"\n{len(steps) - len(failures)}/{len(steps)} benches OK"
          + (f"; FAILED: {failures}" if failures else ""))
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
