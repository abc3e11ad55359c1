"""The paper's end-to-end driver: preprocess a stream of bird-acoustic long
chunks through the stage-graph pipeline under a chosen execution plan.

  PYTHONPATH=src python -m repro.launch.preprocess --minutes 8 --plan async --depth 4
  PYTHONPATH=src python -m repro.launch.preprocess --plan sharded --shards 4
  PYTHONPATH=src python -m repro.launch.preprocess --plan sharded --transport proc --shards 2 --lease-items 4
  PYTHONPATH=src python -m repro.launch.preprocess --plan sharded --store /data/store
  PYTHONPATH=src python -m repro.launch.preprocess --store /data/store --resume

Reports per-stage removal fractions and throughput (the paper's headline
metric: MB/s of source audio preprocessed; their 4-VM x 4-core figure was
16.4-16.5 MB/s). Per-batch stats are aggregated weighted by chunk count, so
uneven batches don't skew the fractions. The sharded plan additionally
reports queue redeliveries, the last round's survivor re-shard loads, and a
per-worker progress summary (leases held, chunks done, redeliveries charged,
heartbeat age, idle/busy split) — under BOTH transports: `--transport
inproc` is the simulated single-process mode, `--transport proc` spawns
real worker processes (`python -m repro.dist.worker`) that pull leases over
the master's socket in batches of `--lease-items` (the paper's Table 7
`max_queue_size` knob).

`--plan` choices come straight from the `PLANS` registry, so new plans
appear here without touching this driver. `--plan async` is the deep
pipeline (`--depth` detect batches in flight, device-resident survivor
compaction, bucketed tail shapes via `--bucket`); plans that record
per-batch timings get a per-stage pipeline report (dispatch / mask
readback / compact / tail / emit, overlap count, host-boundary bytes).
`--store DIR` wraps the chosen plan in `CachedPlan` over a
content-addressed `repro.store.ChunkStore` (re-runs over overlapping data
become lookups) plus a `RunJournal`; `--resume` relaunches a killed
`--store` run mid-stream with each chunk emitted exactly once;
`--store-max-bytes` runs the store's least-recently-hit retention sweep
after the run so a rolling archive's cache stays bounded.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import SERF_AUDIO
from repro.core.plans import PLANS, Preprocessor
from repro.core.scheduler import balance_stats
from repro.data.loader import AudioChunkLoader, audio_shard_pool
from repro.distributed.sharding import ShardingRules, pool_rules
from repro.launch.mesh import make_local_mesh
from repro.obs import metrics as obs_metrics
from repro.obs import telemetry as obs_telemetry
from repro.obs import tracing as obs_tracing
from repro.runtime import enable_compile_cache

_FRAC_KEYS = ("frac_rain", "frac_silence", "frac_kept", "frac_cicada15")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=4.0)
    ap.add_argument("--batch-long-chunks", type=int, default=4)
    # the registry IS the choice list: a newly registered plan (e.g.
    # 'cached') shows up here with zero driver edits
    ap.add_argument("--plan", "--mode", dest="plan", default="two_phase",
                    choices=sorted(PLANS))
    ap.add_argument("--shards", type=int, default=2,
                    help="shard / worker count for --plan sharded")
    ap.add_argument("--transport", choices=("inproc", "proc", "tcp"),
                    default="inproc",
                    help="sharded worker runtime: 'inproc' simulates "
                         "every shard in this process (deterministic, "
                         "zero spawn cost); 'proc' runs real worker "
                         "processes over the repro.dist socket transport; "
                         "'tcp' binds non-loopback so workers can join "
                         "from other hosts (pair with --data-plane-store)")
    ap.add_argument("--data-plane-store", default=None, metavar="DIR",
                    help="move the sharded data plane off the master's "
                         "socket: chunk bytes and result payloads flow "
                         "through a shared ChunkStore at DIR, the socket "
                         "carries only content keys (proc/tcp transports)")
    ap.add_argument("--no-speculate", action="store_true",
                    help="disable speculative re-lease of end-of-stream "
                         "stragglers (sharded plan; on by default under "
                         "the proc transport)")
    ap.add_argument("--lease-items", type=int, default=1,
                    help="work ids per queue round-trip (the paper's "
                         "Table 7 max_queue_size knob) for --plan sharded")
    ap.add_argument("--depth", type=int, default=None,
                    help="detect dispatch-ahead window for --plan async "
                         "(default 4)")
    ap.add_argument("--bucket", choices=("pow2", "linear"), default=None,
                    help="survivor-count quantization for the tail jit "
                         "(default: the plan's own — pow2 for async, "
                         "linear elsewhere)")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="content-addressed result store: wraps the chosen "
                         "plan in CachedPlan + a resume journal")
    ap.add_argument("--resume", action="store_true",
                    help="resume a killed --store run from its journal "
                         "(exactly-once emission across the restart)")
    ap.add_argument("--store-max-bytes", type=int, default=None,
                    help="after the run, evict least-recently-hit store "
                         "entries until the payload fits this budget")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write one durable JSONL telemetry record per "
                         "chunk (master-side, at acceptance — survives "
                         "killed workers) into DIR")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write a Chrome trace-event JSON of the run "
                         "(load in chrome://tracing or Perfetto); sharded "
                         "proc workers ship their spans back at sign-off")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.resume and not args.store:
        ap.error("--resume requires --store")
    if args.store_max_bytes is not None and not args.store:
        ap.error("--store-max-bytes requires --store")

    enable_compile_cache()
    cfg = SERF_AUDIO
    n_batches = max(1, int(round(args.minutes / args.batch_long_chunks)))
    mesh = make_local_mesh()
    pad = max(1, len(jax.devices()))
    sharded = args.plan == "sharded"
    if not sharded:
        if args.transport != "inproc":
            ap.error("--transport picks the sharded plan's worker "
                     f"runtime; plan '{args.plan}' has no workers")
        if args.lease_items != 1:
            ap.error("--lease-items batches the sharded plan's queue "
                     f"pulls; plan '{args.plan}' has no lease loop")
        if args.no_speculate:
            ap.error("--no-speculate disables the sharded plan's "
                     f"speculative re-lease; plan '{args.plan}' has none")
        if args.data_plane_store:
            ap.error("--data-plane-store moves the sharded plan's worker "
                     f"data plane; plan '{args.plan}' has no workers")
    if args.data_plane_store and args.transport == "inproc":
        ap.error("--data-plane-store rides the proc/tcp worker runtime "
                 "(the in-proc simulated loop never serializes chunks)")
    rules = pool_rules(args.shards, mesh) if sharded else ShardingRules(mesh)
    plan_kwargs = {"shards": args.shards, "transport": args.transport,
                   "lease_items": args.lease_items,
                   "data_plane": args.data_plane_store,
                   # None = the plan's default (on for proc workers)
                   "speculate": False if args.no_speculate else None} \
        if sharded else {}
    if args.plan == "async":
        plan_kwargs["depth"] = 4 if args.depth is None else args.depth
    elif args.depth is not None:
        ap.error(f"--depth is the async plan's dispatch-ahead window; "
                 f"plan '{args.plan}' has no use for it")
    if args.bucket is not None:
        if args.plan not in ("two_phase", "streaming", "async", "cached"):
            # sharded pads through its Rebalancer (cross-shard re-slicing
            # has its own shape economy), fused has no tail at all
            ap.error(f"--bucket selects the tail-shape quantization of "
                     f"the single-stream two-phase-family plans; plan "
                     f"'{args.plan}' does not take it")
        plan_kwargs["bucket"] = args.bucket
    if args.store:
        # CachedPlan must see chunk content before dispatch, so even a
        # sharded inner is fed the plain stream (it builds its leased pool
        # internally); hits never reach the inner plan at all
        inner = "two_phase" if args.plan == "cached" else args.plan
        plan, plan_kwargs = "cached", {
            "inner": inner, "store": args.store, "journal": True,
            "resume": args.resume, **plan_kwargs}
        loader = AudioChunkLoader(seed=args.seed, n_batches=n_batches,
                                  batch_long_chunks=args.batch_long_chunks)
    elif sharded:
        # per-shard loaders over ONE shared leased queue; in-proc shards
        # share this process's mesh so their compiles dedup in the
        # CompileCache, proc workers compile in their own processes
        plan = "sharded"
        # proc workers heartbeat per item, but the FIRST item of a batch
        # carries the jit compile (~minute on CPU) — give real processes
        # a lease long enough that a healthy compiling worker is never
        # mistaken for a dead one
        loader = audio_shard_pool(
            seed=args.seed, n_batches=n_batches, n_shards=args.shards,
            batch_long_chunks=args.batch_long_chunks,
            lease_items=args.lease_items,
            lease_timeout_s=300.0 if args.transport in ("proc", "tcp")
            else 60.0)
    else:
        plan = args.plan
        loader = AudioChunkLoader(seed=args.seed, n_batches=n_batches,
                                  batch_long_chunks=args.batch_long_chunks)
    telem = (obs_telemetry.TelemetryWriter(args.telemetry)
             if args.telemetry else None)
    tracer = None
    if args.trace:
        tracer = obs_tracing.Tracer()
        obs_tracing.set_tracer(tracer)
        tracer.start_run("preprocess_run")
    if telem is not None and plan == "sharded":
        # the sharded plan's QueueService writes the records itself, at
        # master-side acceptance — a SIGKILLed worker cannot lose them
        plan_kwargs["telemetry"] = telem
    pre = Preprocessor(cfg, rules, plan=plan, pad_multiple=pad,
                       **plan_kwargs)

    tot_bytes = tot_kept = tot_chunks = 0
    agg = {k: 0.0 for k in _FRAC_KEYS}
    last_keep = None
    timings = []
    t0 = time.time()
    for i, res in enumerate(pre.run(loader)):
        w = float(res.det.stats["n_chunks5"])    # weight: chunks in batch
        for k in _FRAC_KEYS:
            agg[k] += float(res.det.stats[k]) * w
        tot_bytes += res.src_bytes
        tot_kept += res.n_kept
        tot_chunks += int(w)
        last_keep = res.det.keep
        if res.timings is not None:
            timings.append(res.timings)
        if telem is not None and plan != "sharded":
            # single-process plans have no acceptance point but this loop
            wid = res.wid if res.wid is not None else i
            obs_telemetry.record_result(telem, wid, res)
    dt = time.time() - t0
    if tracer is not None:
        tracer.finish_run()
        tracer.save(args.trace)
        print(f"trace: {len(tracer.events)} events -> {args.trace}")
    if telem is not None:
        telem.close()
        print(f"telemetry: {telem.records_written} records -> "
              f"{args.telemetry}")
    if args.trace or args.telemetry:
        for line in obs_metrics.summary_lines():
            print("metrics:", line)
    cached = pre.plan if plan == "cached" else None
    exec_plan = cached.inner if cached is not None else pre.plan
    if tot_chunks == 0:
        if cached is not None and args.resume:
            print("nothing left to emit: the journal shows every chunk of "
                  "this stream was already emitted before the kill")
        else:
            print("empty stream: the loader yielded no batches — "
                  "nothing to do")
        return 0
    frac = {k: agg[k] / tot_chunks for k in _FRAC_KEYS}
    print(f"plan={args.plan}  {tot_bytes / 2**20:.0f} MB source audio "
          f"in {dt:.1f}s  ->  {tot_bytes / 2**20 / dt:.2f} MB/s")
    print(f"chunks kept {tot_kept}/{tot_chunks} "
          f"(rain {frac['frac_rain']:.1%}, "
          f"silence {frac['frac_silence']:.1%}, "
          f"cicada-filtered {frac['frac_cicada15']:.1%})")
    bs = jax.jit(lambda k: balance_stats(k, len(jax.devices())))(last_keep)
    print(f"survivor load imbalance (max/mean): "
          f"{float(bs['imbalance']):.3f} -> "
          f"{float(bs['imbalance_after_compact']):.3f} after compaction")
    if exec_plan.name == "sharded":
        asg = exec_plan.last_assignment
        dp = " data_plane=store" if args.data_plane_store else ""
        print(f"shards={args.shards} transport={args.transport}{dp} "
              f"lease_items={args.lease_items} "
              f"redeliveries={exec_plan.redeliveries} "
              f"speculations={exec_plan.speculations} "
              f"(lost races {exec_plan.speculations_lost})")
        if asg is not None:
            st = asg.stats()
            print(f"last-round survivor re-shard: "
                  f"{st['loads_before'].tolist()} -> "
                  f"{st['loads_after'].tolist()} "
                  f"(max/min {st['max_min_before']:.2f} -> "
                  f"{st['max_min_after']:.2f}, moved {st['moved']})")
        for line in worker_summary(exec_plan.worker_stats):
            print(line)
    if timings:
        report = pipeline_report(timings)
        stages = "  ".join(f"{k} {report[k + '_ms']:.2f}ms"
                           for k in ("dispatch", "readback", "compact",
                                     "tail", "emit"))
        print(f"pipeline: {stages}")
        print(f"pipeline: {report['overlapped']}/{report['batches']} "
              f"overlapped dispatches (max in-flight "
              f"{report['max_in_flight']}), host boundary "
              f"{report['d2h_bytes_per_batch'] / 2**20:.2f} MB down + "
              f"{report['h2d_bytes_per_batch'] / 2**20:.2f} MB up per "
              f"batch")
    if cached is not None and cached.stats is not None:
        print(f"store: {cached.stats}")
    if args.store_max_bytes is not None and cached is not None \
            and cached.store is not None:
        rep = cached.store.gc(args.store_max_bytes)
        print(f"store gc: {rep['evicted']} entries / "
              f"{rep['bytes_freed'] / 2**20:.1f} MB evicted -> "
              f"{rep['entries_after']} entries / "
              f"{rep['bytes_after'] / 2**20:.1f} MB retained")
    return tot_kept


def worker_summary(worker_stats):
    """Per-worker progress lines for the end-of-run summary (sharded plan,
    both transports): queue round-trips vs work ids granted (the lease-
    batching economy), chunks finished, leases still held, redeliveries
    charged to the worker (its lost leases), final membership state
    (late joiners appear here; drained workers read "departed"),
    heartbeat age, and — proc transport only — the worker-reported
    idle/busy split."""
    lines = []
    for st in worker_stats or ():
        pid = f" pid={st.pid}" if st.pid else ""
        beat = ("never" if st.last_beat_age_s is None
                else f"{st.last_beat_age_s:.1f}s ago")
        split = (f"  idle {st.idle_s:.1f}s / busy {st.busy_s:.1f}s"
                 if (st.idle_s or st.busy_s) else "")
        lines.append(
            f"worker {st.worker}{pid} [{st.state}]: "
            f"{st.chunks_done} chunks done, "
            f"{st.leased_total} leased over {st.lease_calls} round-trips "
            f"({st.leases_held} still held), "
            f"{st.redeliveries} redelivered, last beat {beat}{split}")
    return lines


def pipeline_report(timings):
    """Aggregate per-batch plan timing records into per-stage means, the
    overlap count, and host-boundary traffic."""
    n = len(timings)
    rep = {"batches": n}
    for k in ("dispatch", "readback", "compact", "tail", "emit"):
        rep[k + "_ms"] = 1e3 * sum(t.get(k + "_s", 0.0)
                                   for t in timings) / n
    rep["overlapped"] = sum(1 for t in timings
                            if t.get("in_flight", 1) >= 2)
    rep["max_in_flight"] = max(t.get("in_flight", 1) for t in timings)
    rep["d2h_bytes_per_batch"] = sum(t.get("d2h_bytes", 0)
                                     for t in timings) / n
    rep["h2d_bytes_per_batch"] = sum(t.get("h2d_bytes", 0)
                                     for t in timings) / n
    return rep


if __name__ == "__main__":
    main()
