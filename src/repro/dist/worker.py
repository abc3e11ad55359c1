"""The worker runtime: one shard of the paper's slave loop, as a real
process.

  python -m repro.dist.worker --master HOST:PORT --lease-items N

The worker ANNOUNCES itself — no shard id on the command line: `hello`
returns its assigned identity along with the setup blob (pipeline
config, stage names, pad_multiple, tail bucket, kernel backend mode),
so the same invocation joins from any host that can reach the master.
It builds its OWN `PipelineGraph` + jitted detect/tail phases
(per-process CompileCache — compiles never cross the boundary), then
loops:

  lease      up to `lease_items` work ids in ONE round-trip — the paper's
             Table 7 queue-size knob (`max_queue_size`): deeper batches
             amortize master round-trips against redelivery exposure.
             With the store data plane (setup blob carries "data_plane")
             the grant arrives as (wid, content key) pairs via
             `lease_chunks` and the fetch step below disappears from the
             master's socket entirely
  fetch      the chunk bytes for the whole lease batch in one round-trip
             (the master owns the loader; the paper's master hands slaves
             files the same way) — or, store plane, read by key from the
             shared ChunkStore
  compute    detect -> device-resident survivor compaction -> tail, the
             exact TwoPhasePlan path, so output bytes match the
             single-process plans
  push       results stream back per item (the paper's send_interval),
             each push doubling as a heartbeat; the MASTER completes the
             work id, so a worker killed after push but before the master
             drains it still resolves exactly-once. Store plane: the
             payload goes to the shared store under the result key paired
             with the lease's raw key (first-write-wins dedups a
             redelivered incarnation's duplicate), and the push carries
             only the tiny key ref

A SIGKILL anywhere in that loop leaves leases registered un-completed —
recovery is the queue's lease expiry or the master's `fail_worker`, never
worker-side cleanup. The runtime is also importable (`run_worker`) so
tests can drive it in-process over an `InProcTransport`.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def run_worker(master, shard=None, lease_items=1, poll_s=0.05,
               transport=None, max_items=None):
    """Run one worker against a served QueueService. Returns the
    idle/busy stats dict it also reports via `bye`. `master` is an
    address for the given transport (HOST:PORT for proc; the service
    object itself for in-proc). `shard=None` (the spawned default)
    announces to the registry and adopts the identity `hello` assigns;
    an explicit shard keeps the legacy self-asserted name (tests).
    `max_items` caps total processed items (tests); None means run
    until the queue is finished."""
    # imports deferred past arg parsing so `--help` stays instant
    from repro.core.graph import PipelineGraph
    from repro.core.plans import TwoPhasePlan
    from repro.dist.transport import InProcTransport, ProcTransport
    from repro.kernels import backend

    if transport is None:
        transport = ProcTransport()
    proxy = transport.connect(master)
    if shard is None:
        spec = proxy.call("hello", None, os.getpid(), -1)
        assigned = spec.get("assigned") or {}
        worker, shard = assigned.get("worker"), assigned.get("shard", -1)
        if worker is None:
            raise RuntimeError("master assigned no identity at hello")
    else:
        worker = f"shard{int(shard)}"
        spec = proxy.call("hello", worker, os.getpid(), int(shard))
    # Trace propagation: when the master runs a tracer, `hello` carries
    # its trace id + run-span parent. The worker traces locally into its
    # own buffer (own pid, master's parent) and ships the events back in
    # `bye` — a SIGKILLed worker simply loses its spans, never the run.
    from repro.obs import tracing as obs_tracing
    tracer = obs_tracing.NULL_TRACER
    if spec.get("trace"):
        tracer = obs_tracing.Tracer(**spec["trace"])
        # Install globally (so plan-internal spans land in it) only in a
        # real worker process. In-proc workers share the master's process:
        # there the master's tracer IS the global one and already catches
        # plan spans — replacing it would clobber the run.
        if not obs_tracing.get_tracer().enabled:
            obs_tracing.set_tracer(tracer)
    if spec.get("backend_mode"):
        backend.set_mode(spec["backend_mode"])
    graph = PipelineGraph(spec["cfg"], spec.get("stages"),
                          spec.get("source_channels", 2))
    plan = TwoPhasePlan(graph, pad_multiple=spec.get("pad_multiple", 1),
                        bucket=spec.get("bucket", "linear"))
    from repro.dist.service import pack_result

    # Store data plane: chunk bytes move through a shared ChunkStore the
    # setup blob points at; the master's socket carries only keys.
    plane = None
    dp_spec = spec.get("data_plane") or {}
    if dp_spec.get("kind") == "store":
        from repro.dist.data_plane import StoreDataPlane
        plane = StoreDataPlane(dp_spec["dir"])

    lease_items = max(1, int(lease_items))
    idle = busy = 0.0
    done = 0
    while max_items is None or done < max_items:
        t0 = time.perf_counter()
        w0 = time.time()
        if plane is None:
            ids = proxy.call("lease", worker, lease_items)
            keys = {}
        else:
            pairs = proxy.call("lease_chunks", worker, lease_items)
            ids = [wid for wid, _ in pairs]
            keys = dict(pairs)
        if not ids:
            # exit on the queue-global signal (finished) OR the per-worker
            # one (drain): a draining worker's lease always comes back
            # empty, and at the top of this loop everything previously
            # leased is already pushed — held leases are finished, so
            # leaving now is the graceful exit drain() promises
            if proxy.call("finished") or proxy.call("draining", worker):
                idle += time.perf_counter() - t0
                break
            proxy.call("heartbeat", worker)
            idle += time.perf_counter() - t0
            time.sleep(poll_s)
            continue
        # `X` complete events, recorded only for NON-empty iterations so
        # an idle worker's poll loop does not flood the trace
        tracer.complete("lease", w0, worker=worker, ids=ids)
        w1 = time.time()
        if plane is None:
            items = list(zip(ids, proxy.call("fetch_many", worker, ids)))
            tracer.complete("fetch_many", w1, worker=worker, n=len(ids))
        else:
            items = [(wid, None if keys[wid] is None
                      else plane.fetch_chunks(keys[wid])) for wid in ids]
            tracer.complete("fetch_store", w1, worker=worker, n=len(ids))
        idle += time.perf_counter() - t0
        for wid, chunks in items:
            if chunks is None:
                # this lease lost a redelivery race: the id completed (and
                # its stream buffer may be released) before our fetch —
                # nothing to compute, the master already has the result
                continue
            t1 = time.perf_counter()
            w2 = time.time()
            # a heartbeat per item bounds lease-expiry exposure to ONE
            # item's compute time (first-item jit compiles are the long
            # pole), not the whole lease batch
            proxy.call("heartbeat", worker)
            res = plan(np.asarray(chunks, np.float32))
            payload = pack_result(res)
            busy += time.perf_counter() - t1
            tracer.complete("compute", w2, worker=worker, wid=wid,
                            n_kept=int(res.n_kept))
            t2 = time.perf_counter()
            w3 = time.time()
            if plane is None:
                proxy.call("push_result", worker, wid, payload)
            else:
                ref = plane.push(keys[wid], payload)
                proxy.call("push_result", worker, wid, ref)
            tracer.complete("push", w3, worker=worker, wid=wid)
            idle += time.perf_counter() - t2
            done += 1
    stats = {"idle_s": idle, "busy_s": busy, "chunks": done}
    if tracer.enabled:
        stats["spans"] = tracer.drain()
    try:
        proxy.call("bye", worker, stats)
    finally:
        proxy.close()
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="repro.dist worker process (spawned by the sharded "
                    "plan's proc transport; authkey via env "
                    "REPRO_DIST_AUTHKEY)")
    ap.add_argument("--master", required=True, metavar="HOST:PORT")
    ap.add_argument("--shard", type=int, default=None,
                    help="self-asserted shard id (debugging only; spawned "
                         "workers announce and let the registry assign)")
    ap.add_argument("--lease-items", type=int, default=1,
                    help="work ids per queue round-trip (the paper's "
                         "max_queue_size knob)")
    ap.add_argument("--poll-s", type=float, default=0.05,
                    help="sleep between empty lease polls")
    args = ap.parse_args(argv)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    run_worker(args.master, args.shard, lease_items=args.lease_items,
               poll_s=args.poll_s)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
