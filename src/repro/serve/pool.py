"""Persistent worker pool: long-lived `repro.dist` workers serving a
standing queue.

The batch runtime (`ShardedPlan` proc mode) spawns workers per run and
tears them down with the stream — correct for archives, hopeless for
serving: every request wave would re-pay process spawn + jit compile.
`WorkerPool` inverts the lifecycle. Workers are spawned ONCE over the
existing transports (`InProcTransport` threads or `ProcTransport`
processes — the identical `repro.dist.worker.run_worker` loop either
way), and they stay alive across submissions because the pool's
`StandingWorkQueue` reports `finished` only after `close()` drains: an
idle worker's empty lease turns into heartbeat + poll, not exit. After
the first item per worker, every jit is warm — wave 2 of a pump runs at
steady-state latency on the same pids as wave 1.

Work enters via `submit(chunks) -> wid` (any (B, C, S_long_src) batch —
the continuous batcher assembles those from single-chunk requests) and
leaves via `poll()` / `wait()` as the same `BatchResult` the in-process
plans produce: workers run the exact TwoPhasePlan detect -> device
compaction -> tail path, so pool output is bit-identical to a direct
`two_phase` call on the same batch.

Fault story is inherited, not reinvented: leases + completion gating give
at-least-once delivery with exactly-once emission. A SIGKILLed worker's
leases come back via `fail_worker` (the pool notices the dead pid on the
next poll) or lease expiry, and the redelivered request goes to the front
of the line. `respawn=True` additionally replaces dead proc workers.

Observability: `worker_stats` is the per-worker `WorkerStats` ledger the
batch runtime already keeps; `gauges()` adds the pool-level serving view
(busy/idle workers, queue depth, in-flight leases, oldest-request age).
"""
from __future__ import annotations

import threading
import time

import numpy as np

from repro.core.plans import BatchResult
from repro.data.queue import StandingWorkQueue
from repro.dist.data_plane import StoreDataPlane
from repro.dist.service import QueueService, unpack_result
from repro.dist.transport import InProcTransport, ProcTransport, TcpTransport
from repro.runtime import refuse_worker_processes
from repro.dist.worker import run_worker
from repro.ft.failure import StragglerDetector
from repro.kernels import backend
from repro.obs import metrics as obs_metrics


class WorkerPool:
    """Long-lived preprocessing workers over a standing QueueService.

    Parameters:
      cfg              pipeline config (the setup blob workers build
                       their jits from — same facts ShardedPlan ships)
      workers          pool size
      transport        "proc" (real processes, SIGKILL-able), "tcp" (real
                       processes over a non-loopback bind — workers may
                       join from other hosts; pair with `store=`) or
                       "inproc" (daemon threads driving the same worker
                       runtime — tests and single-host serving without
                       spawn cost)
      store            optional shared-store data plane (a ChunkStore,
                       directory path, or StoreDataPlane): request bytes
                       and result payloads move through the store, the
                       control socket carries only content keys
      stages           optional stage-name override (None = config list)
      pad_multiple / bucket
                       worker-side tail policy; "pow2" bounds tail
                       retraces across the varying survivor counts a
                       request mix produces
      lease_timeout_s  None = transport default (proc workers pay a
                       first-item compile, so their deadline is generous)
      poll_s           worker sleep between empty leases (sets the idle
                       wake-up latency floor for new work)
      respawn          replace dead PROC workers automatically (dead
                       workers always have their leases reclaimed either
                       way; respawn=False lets chaos tests prove the
                       survivors absorb the load)
      min_workers /    queue-depth-driven autoscaling band. max_workers
      max_workers      arms it (None = fixed-size pool): sustained
                       backlog (> autoscale_backlog_s with unleased work
                       queued) spawns a late joiner up to max_workers;
                       a sustained fully-idle pool (no queued or leased
                       work for autoscale_idle_s) DRAINS one idle worker
                       down to min_workers (defaults to `workers`) — the
                       drained worker exits through bye, never reaped
      speculate        arm speculative re-lease: an idle worker whose
                       lease comes back empty may duplicate the slowest
                       straggling in-flight item (first completion wins —
                       exactly-once is already the completion gate's job)
    """

    def __init__(self, cfg, workers=2, transport="proc", stages=None,
                 source_channels=2, pad_multiple=1, bucket="pow2",
                 lease_items=1, lease_timeout_s=None, poll_s=0.01,
                 respawn=True, monitor=None, telemetry=None,
                 min_workers=None, max_workers=None,
                 autoscale_backlog_s=0.75, autoscale_idle_s=5.0,
                 speculate=False, straggler_factor=2.0,
                 straggler_min_history=4, store=None):
        if transport not in ("proc", "tcp", "inproc"):
            raise ValueError(f"unknown transport {transport!r} "
                             "(expected 'proc', 'tcp' or 'inproc')")
        self.cfg = cfg
        self.workers = max(1, int(workers))
        self.transport = transport
        self.lease_items = max(1, int(lease_items))
        self.poll_s = float(poll_s)
        self.respawn = bool(respawn)
        self.min_workers = (self.workers if min_workers is None
                            else max(1, int(min_workers)))
        self.max_workers = None if max_workers is None \
            else max(self.min_workers, int(max_workers))
        self.autoscale_backlog_s = float(autoscale_backlog_s)
        self.autoscale_idle_s = float(autoscale_idle_s)
        self.scale_ups = 0
        self.scale_downs = 0
        self._backlog_since = None      # monotonic ts backlog first seen
        self._idle_since = None         # monotonic ts full idle first seen
        self.monitor = monitor
        if lease_timeout_s is None:
            lease_timeout_s = 300.0 if transport in ("proc", "tcp") else 60.0
        self.queue = StandingWorkQueue(lease_timeout_s=lease_timeout_s)
        self._setup = {"cfg": cfg,
                       "stages": list(stages) if stages else None,
                       "source_channels": int(source_channels),
                       "pad_multiple": int(pad_multiple),
                       "bucket": bucket,
                       "backend_mode": backend.get_mode()}
        straggler = StragglerDetector(
            factor=float(straggler_factor),
            min_history=int(straggler_min_history)) if speculate else None
        if store is not None and not isinstance(store, StoreDataPlane):
            store = StoreDataPlane(store, backend_mode=backend.get_mode())
        self.service = QueueService(self.queue, fetch_item=self._fetch,
                                    setup=self._setup, monitor=monitor,
                                    telemetry=telemetry,
                                    straggler=straggler, data_plane=store)
        self._items = {}        # wid -> chunk bytes (the data plane)
        self._submit_t = {}     # wid -> submit time (oldest-age gauge)
        self._completed = {}    # wid -> BatchResult awaiting claim
        self._claim_lock = threading.Lock()
        self._handles = {}      # shard -> WorkerHandle (proc)
        self._threads = {}      # shard -> Thread (inproc)
        self._dead = set()      # shards whose leases were reclaimed
        self._next_shard = self.workers   # late joiners get fresh ids
        self.respawns = 0
        self._tp = None
        self._started = False
        self._shut = False

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        """Spawn the workers once; they live until shutdown()."""
        if self._started:
            raise RuntimeError("pool already started")
        self._started = True
        if self.transport in ("proc", "tcp"):
            refuse_worker_processes(
                f"WorkerPool(transport={self.transport!r})")
            self._tp = TcpTransport() if self.transport == "tcp" \
                else ProcTransport()
            self._tp.serve(self.service)
            for k in range(self.workers):
                self._handles[k] = self._spawn(k)
        else:
            self._tp = InProcTransport()
            self._tp.serve(self.service)
            for k in range(self.workers):
                self._threads[k] = self._spawn_thread(k)
        return self

    def _spawn(self, shard):
        # the shard id never rides argv: reserve it with the registry so
        # the worker's announce-hello adopts it (handles/pids stay keyed
        # by the id the pool chose)
        h = self._tp.spawn_worker(shard, lease_items=self.lease_items,
                                  poll_s=self.poll_s)
        self.service.reserve(h.pid, shard)
        return h

    def _spawn_thread(self, shard):
        t = threading.Thread(
            target=run_worker, args=(self.service, shard),
            kwargs=dict(lease_items=self.lease_items, poll_s=self.poll_s,
                        transport=InProcTransport()),
            daemon=True, name=f"repro-pool-shard{shard}")
        t.start()
        return t

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.shutdown(drain=exc_type is None)

    # -- work plane ---------------------------------------------------------
    def submit(self, chunks) -> int:
        """Admit one (B, C, S_long_src) batch; returns its work id. The
        item is registered under the queue's own lock TOGETHER with the
        admission, so a worker's lease can never observe a wid whose
        bytes are not yet fetchable."""
        x = np.asarray(chunks, np.float32)
        with self.queue.lock:
            wid = self.queue.add()
            self._items[wid] = x
            self._submit_t[wid] = time.monotonic()
        return wid

    def _fetch(self, wid):
        """Data plane. None answers a redelivered lease that lost the
        race to a straggler's completion — the worker skips it."""
        if self.queue.is_done(wid):
            return None
        with self.queue.lock:
            item = self._items.get(wid)
        if item is None:
            if self.queue.is_done(wid):
                return None
            raise KeyError(f"work id {wid} has no registered item")
        return item

    def _pump(self):
        """Drain worker pushes into the completed set, gating on
        `queue.complete` so at-least-once pushes stay exactly-once
        results; then reclaim dead workers."""
        for worker, wid, payload in self.service.pop_results():
            # winner's name rides into complete() so a lost speculation
            # race attributes the other incarnation
            if not self.queue.complete([wid], worker=worker):
                continue            # a redelivery raced a straggler
            # store data plane: the push was a key ref — materialize it
            # here, after the gate (losers never cost a store read)
            det, f = unpack_result(self.service.resolve_result(payload))
            self.service.note_done(worker, wid=wid,
                                   survivors=int(f["n_kept"]),
                                   bytes_out=f["cleaned"].nbytes)
            with self.queue.lock:
                self._items.pop(wid, None)
                self._submit_t.pop(wid, None)
            res = BatchResult(cleaned=f["cleaned"], det=det,
                              n_kept=f["n_kept"], wid=wid,
                              src_bytes=f["src_bytes"])
            with self._claim_lock:
                self._completed[wid] = res
        self._reap_dead()
        self._autoscale()

    def _departed(self, worker) -> bool:
        st = self.service.workers.get(worker)
        return st is not None and st.state in ("draining", "departed")

    def _reap_dead(self):
        """Return a dead worker's leases immediately (the fail_worker
        fast path — lease expiry is the slow fallback) and, for proc
        pools with respawn, replace the process. A worker that exited in
        state draining/departed left GRACEFULLY (scale-down or its own
        drain request): it holds nothing — forget it, never fail it."""
        for k, h in list(self._handles.items()):
            if h.poll() is None:
                continue
            if self._departed(h.worker):
                del self._handles[k]
                self._dead.discard(k)
                continue
            if k in self._dead:
                continue
            self._dead.add(k)
            self.service.fail_worker(h.worker)
            if self.respawn and not self.queue.closed:
                self._handles[k] = self._spawn(k)
                self._dead.discard(k)
                self.respawns += 1
                obs_metrics.counter(
                    "pool_respawns_total",
                    "dead proc workers replaced").inc()
        for k, t in list(self._threads.items()):
            if t.is_alive():
                continue
            if self._departed(f"shard{k}"):
                del self._threads[k]
                self._dead.discard(k)
                continue
            if k not in self._dead and not self.queue.finished:
                self._dead.add(k)
                self.service.fail_worker(f"shard{k}")

    # -- elasticity ---------------------------------------------------------
    def _live_active(self):
        """Live workers not already on their way out: the autoscaler's
        capacity measure."""
        out = []
        for k, h in self._handles.items():
            if h.poll() is None and not self._departed(h.worker):
                out.append(k)
        for k, t in self._threads.items():
            if t.is_alive() and not self._departed(f"shard{k}"):
                out.append(k)
        return sorted(out)

    def add_worker(self):
        """Spawn one late joiner on a fresh shard id (manual scale-up —
        the autoscaler calls this too). Returns the new shard id."""
        k = self._next_shard
        self._next_shard += 1
        if self.transport in ("proc", "tcp"):
            self._handles[k] = self._spawn(k)
        else:
            self._threads[k] = self._spawn_thread(k)
        self.scale_ups += 1
        obs_metrics.counter(
            "pool_scale_ups_total",
            "late joiners spawned on sustained backlog").inc()
        return k

    def drain_worker(self, shard=None):
        """Ask one worker to leave gracefully: finish held leases, take
        no more, exit through bye (manual scale-down — the autoscaler
        calls this with an idle pick). Returns the drained shard id or
        None if no drainable worker exists."""
        with self.queue.lock:
            if shard is None:
                for k in reversed(self._live_active()):
                    if not self.queue.leases_held(f"shard{k}"):
                        shard = k
                        break
            if shard is None:
                return None
            self.service.drain(f"shard{shard}")
        if self.monitor is not None:
            self.monitor.forget(f"shard{shard}")
        self.scale_downs += 1
        obs_metrics.counter(
            "pool_scale_downs_total",
            "idle workers drained out on sustained idleness").inc()
        return shard

    def _autoscale(self):
        """Queue-depth-driven elasticity, armed by max_workers: sustained
        unleased backlog spawns a late joiner; a sustained fully-idle
        pool drains one idle worker. One transition per sustain window —
        the since-timestamps re-arm after every action, so the pool walks
        toward the band edge instead of jumping."""
        if self.max_workers is None or self._shut or self.queue.closed:
            return
        queued, leased = self.queue.depth()
        now = time.monotonic()
        live = len(self._live_active())
        if queued > 0:
            self._idle_since = None
            if self._backlog_since is None:
                self._backlog_since = now
            elif (now - self._backlog_since >= self.autoscale_backlog_s
                    and live < self.max_workers):
                self.add_worker()
                self._backlog_since = now
        elif queued == 0 and leased == 0:
            self._backlog_since = None
            if self._idle_since is None:
                self._idle_since = now
            elif (now - self._idle_since >= self.autoscale_idle_s
                    and live > self.min_workers):
                self.drain_worker()
                self._idle_since = now
        else:
            self._backlog_since = None
            self._idle_since = None

    def poll(self):
        """Non-blocking: drain and return every newly completed
        {wid: BatchResult}. Results are handed over exactly once — a
        claimed wid is forgotten (no unbounded result growth)."""
        self._pump()
        with self._claim_lock:
            out, self._completed = self._completed, {}
        return out

    def claim(self, wids):
        """Non-blocking targeted claim: drain, then return whichever of
        `wids` are done as {wid: BatchResult}. Unlike poll() this leaves
        other submitters' results unclaimed, so several front-ends can
        share one pool."""
        self._pump()
        out = {}
        with self._claim_lock:
            for wid in set(wids) & self._completed.keys():
                out[wid] = self._completed.pop(wid)
        return out

    def wait(self, wids, timeout_s=600.0):
        """Block until every wid in `wids` completes; returns
        {wid: BatchResult}. Claims ONLY the asked-for wids — results for
        other submitters stay available to their own poll/wait."""
        want = set(wids)
        got = {}
        deadline = time.monotonic() + timeout_s
        while True:
            self._pump()
            with self._claim_lock:
                for wid in want & self._completed.keys():
                    got[wid] = self._completed.pop(wid)
                want -= got.keys()
            if not want:
                return got
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"pool did not complete {sorted(want)} within "
                    f"{timeout_s:.0f}s (gauges: {self.gauges()})")
            time.sleep(0.002)

    # -- observability ------------------------------------------------------
    @property
    def pids(self):
        """shard -> pid of the live proc workers ({} for inproc): the
        'same workers across waves' acceptance observable."""
        return {k: h.pid for k, h in self._handles.items()
                if h.poll() is None}

    @property
    def worker_stats(self):
        """The per-worker WorkerStats ledger (lease calls, chunks done,
        leases held, redeliveries charged, heartbeat age)."""
        return self.service.worker_report()

    def gauges(self):
        """Pool-level serving gauges: busy/idle workers, queue depth,
        in-flight leases, oldest unserved request age."""
        queued, leased = self.queue.depth()
        with self.queue.lock:
            busy = sum(1 for st in self.service.workers.values()
                       if self.queue.leases_held(st.worker))
            oldest = min(self._submit_t.values(), default=None)
        live = (len([h for h in self._handles.values()
                     if h.poll() is None])
                or len([t for t in self._threads.values() if t.is_alive()]))
        done, total = self.queue.progress()
        out = {"workers": live, "busy": busy,
               "idle": max(0, live - busy),
               "queue_depth": queued, "in_flight": leased,
               "oldest_age_s": (None if oldest is None
                                else time.monotonic() - oldest),
               "submitted": total, "completed": done,
               "epoch": self.service.epoch,
               "scale_ups": self.scale_ups,
               "scale_downs": self.scale_downs}
        reg = obs_metrics.get_registry()
        if reg.enabled:
            # mirror into the registry so metrics_text()/snapshot() carry
            # the live pool view without a second collection path
            reg.gauge("pool_workers", "live workers").set(live)
            reg.gauge("pool_busy", "workers holding leases").set(busy)
            reg.gauge("pool_queue_depth", "unleased work ids").set(queued)
            reg.gauge("pool_in_flight", "leased, uncompleted ids").set(leased)
            reg.gauge("pool_oldest_age_s",
                      "age of the oldest unserved request").set(
                          out["oldest_age_s"] or 0.0)
            reg.gauge("pool_membership_epoch",
                      "pool membership version (joins/drains/deaths)").set(
                          self.service.epoch)
        return out

    def kill_worker(self, shard):
        """SIGKILL a proc worker (chaos testing — the pool must redeliver
        its in-flight request exactly once)."""
        self._handles[shard].kill()

    # -- teardown -----------------------------------------------------------
    def drain(self, timeout_s=600.0):
        """Close admission and pump until every admitted item completed."""
        self.queue.close()
        deadline = time.monotonic() + timeout_s
        while not self.queue.finished:
            self._pump()
            if self.queue.finished:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"pool drain timed out (gauges: {self.gauges()})")
            time.sleep(0.005)

    def shutdown(self, drain=True, timeout_s=600.0):
        """Stop the pool. drain=True serves everything admitted first;
        drain=False abandons unfinished work (`queue.abort`). Workers
        observe `finished`, sign off via `bye` (their idle/busy split
        lands in the ledger), and exit; stragglers are TERM/KILLed."""
        if self._shut:
            return
        self._shut = True
        try:
            if drain:
                self.drain(timeout_s=timeout_s)
            else:
                self.queue.abort()
            deadline = time.monotonic() + 10.0
            for h in self._handles.values():
                try:
                    h.proc.wait(max(0.0, deadline - time.monotonic()))
                except Exception:
                    pass
            for t in self._threads.values():
                t.join(max(0.0, deadline - time.monotonic()))
        finally:
            for h in self._handles.values():
                h.shutdown()
            if self._tp is not None:
                self._tp.close()
