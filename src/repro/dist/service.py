"""QueueService: the master's serviceable surface over one shared WorkQueue.

The paper's master owns three things: the file list (here: the leased
`WorkQueue`), the data hand-off to slaves (here: `fetch`), and the result
collection that gates what counts as done (here: `push_result` + the
master-side `pop_results` drain). `QueueService` packages exactly that as a
set of named methods a transport can serve — `RPC_METHODS` is the whole
wire surface, nothing else on the object is reachable remotely.

It also DUCK-TYPES the WorkQueue it wraps (lease / complete /
heartbeat_extend / fail_worker / state / next_deadline / progress /
finished / clock / lease_timeout_s / redeliveries), so the in-process
simulated path can route every queue mutation through the service and the
per-worker accounting accrues identically under both transports. All
compound operations take the queue's own RLock, so N transport handler
threads and the master loop interleave safely.
"""
from __future__ import annotations

import collections
import hashlib
import os
import time
from dataclasses import dataclass, field

import numpy as np

from repro.core.graph import PipelineOutput
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing

# The complete remote surface. A transport must refuse anything else —
# the service object carries master-side state (result inbox, kill hooks)
# that workers have no business reaching. `metrics` is read-only: a
# snapshot of the master's registry (scrape endpoint over the transport).
# `drain`/`draining` are the graceful-leave pair: a departing worker (or
# the master's autoscaler) calls `drain`, the worker polls `draining` and
# exits once its held leases are finished. `lease_chunks` is the store
# data plane's lease: grants ride back as (wid, content key) pairs so the
# socket never carries chunk bytes.
RPC_METHODS = frozenset({
    "hello", "lease", "lease_chunks", "fetch", "fetch_many", "complete",
    "push_result", "heartbeat", "fail_worker", "state", "progress",
    "finished", "next_deadline", "bye", "metrics", "drain", "draining",
})

# Worker membership states (WorkerStats.state). Transitions bump the
# service's membership epoch and are mirrored into the metrics registry.
WORKER_STATES = ("active", "draining", "departed", "dead")


@dataclass
class WorkerStats:
    """Per-worker progress ledger (the launch driver's end-of-run summary).

    `leases_held` / `redeliveries` / `last_beat_age_s` are filled in by
    `QueueService.worker_report()` at snapshot time; the rest accrue as the
    worker talks to the service."""
    worker: str
    shard: int = -1
    pid: int = None
    state: str = "active"           # membership: active/draining/departed/dead
    lease_calls: int = 0            # queue round-trips (Table 7's axis)
    leased_total: int = 0           # work ids ever granted
    chunks_done: int = 0            # results ACCEPTED by the master (the
                                    # completion gate, not raw pushes — a
                                    # redelivery race's duplicate push is
                                    # not work done)
    idle_s: float = 0.0             # worker-reported: blocked on the queue
    busy_s: float = 0.0             # worker-reported: computing
    last_beat: float = field(default=None, repr=False)
    # snapshot-time fields (worker_report):
    leases_held: int = 0
    redeliveries: int = 0
    last_beat_age_s: float = None


class QueueService:
    """Master-side service: the WorkQueue plus the data/result planes.

    Parameters:
      queue       the shared WorkQueue (its RLock serializes everything)
      fetch_item  wid -> chunk batch (np.ndarray) — the data plane; the
                  master materialises/regenerates the bytes, workers never
                  see the loader (the paper's master hands slaves files)
      setup       picklable blob returned from `hello` — everything a
                  worker needs to build its jits (cfg, stage names,
                  pad_multiple, bucket, kernel backend mode)
      monitor     optional ft.failure.HeartbeatMonitor fed on heartbeats
      telemetry   optional repro.obs.telemetry.TelemetryWriter — per-chunk
                  records written MASTER-side at acceptance/redelivery so
                  they survive SIGKILLed workers
      straggler   optional ft.failure.StragglerDetector — arms speculative
                  re-lease: fed a start per granted id and a complete per
                  retirement; when an ACTIVE worker's lease comes back
                  empty with work still in flight (the end-of-stream
                  shape), the slowest flagged item is duplicated to that
                  idle worker via `WorkQueue.speculate`

    Membership: `hello`/`bye`/`drain` and observed deaths drive a real
    registry — per-worker `state` on WorkerStats plus a monotonically
    increasing `epoch` that bumps on every join/leave/death, mirrored into
    the metrics registry (`dist_membership_epoch`, `dist_workers{state}`).
    Late joiners are first-class: a `hello` mid-run gets the SAME setup
    blob the original fleet got and leases from the same queue.
    """

    def __init__(self, queue, fetch_item=None, setup=None, monitor=None,
                 telemetry=None, straggler=None, data_plane=None):
        self.queue = queue
        self._fetch_item = fetch_item
        self._setup = dict(setup or {})
        self.monitor = monitor
        self.telemetry = telemetry
        self.straggler = straggler
        # optional StoreDataPlane: when set, workers lease via
        # `lease_chunks` (keys, not bytes) and push tiny store refs — the
        # control socket stops carrying chunk payloads entirely.
        self.data_plane = data_plane
        self.workers: dict[str, WorkerStats] = {}
        # registry assignment state: pid -> shard reservations made
        # master-side at spawn, and the next free shard id for workers
        # that join with no reservation (a hand-started remote worker).
        self._reserved: dict[int, int] = {}
        self._next_shard = 0
        # wid -> offered store key (lease_chunks): a redelivered or
        # speculated lease re-offers without re-hashing the batch.
        self._offered: dict[int, str] = {}
        self.lease_calls = 0
        # membership epoch: a version counter over the worker set; every
        # join, drain, departure, and observed death bumps it (gauged as
        # dist_membership_epoch so dashboards see churn, not just counts)
        self.epoch = 0
        self._results = collections.deque()
        # per-chunk event times (lease/fetch/push, content key), keyed by
        # wid; popped into a durable telemetry record at acceptance.
        self._timeline: dict[int, dict] = {}
        # Observe redeliveries at the source: the queue fires this under
        # its own lock for BOTH reclaim paths (expiry and fail_worker),
        # including direct fail_worker calls on the raw queue.
        queue.on_redeliver = self._on_redeliver
        # Observe retirements at the source for the same reason: the
        # detector's latency history must accrue no matter which emit
        # loop (proc, sim, pool) completes the id.
        queue.on_complete = self._on_complete
        # master-side hook, called INSIDE lease() once per granted work id
        # with (worker, wid): the CrashInjector's process-mode trigger — a
        # doomed worker is SIGKILLed while its fresh lease is registered
        # and un-completed, so recovery exercises the real redelivery path.
        self.on_grant = None

    # -- bookkeeping --------------------------------------------------------
    def _w(self, worker) -> WorkerStats:
        st = self.workers.get(worker)
        if st is None:
            st = self.workers[worker] = WorkerStats(worker)
        return st

    # -- membership registry ------------------------------------------------
    def _set_state(self, st: WorkerStats, state: str):
        """Transition one worker's membership state; bumps the epoch and
        re-publishes the membership gauges only on a real change."""
        if st.state == state:
            return
        st.state = state
        self.epoch += 1
        self._publish_membership()

    def _publish_membership(self):
        reg = obs_metrics.get_registry()
        if not reg.enabled:
            return
        by_state = collections.Counter(st.state for st in
                                       self.workers.values())
        g = reg.gauge("dist_workers", "registered workers by membership "
                      "state", ("state",))
        for s in WORKER_STATES:
            g.labels(state=s).set(by_state.get(s, 0))
        reg.gauge("dist_membership_epoch",
                  "membership version: bumps on every join/drain/"
                  "departure/death").set(self.epoch)

    def active_workers(self):
        """Names of workers currently in state 'active'."""
        with self.queue.lock:
            return sorted(w for w, st in self.workers.items()
                          if st.state == "active")

    def note_beat(self, worker):
        """Record liveness WITHOUT extending lease deadlines (the simulated
        in-process path beats once per round; extending there would change
        redelivery timing, which the proc path deliberately does via
        `heartbeat`)."""
        with self.queue.lock:
            self._w(worker).last_beat = self.queue.clock()
        if self.monitor is not None:
            self.monitor.beat(worker)

    def note_done(self, worker, n=1, wid=None, survivors=None,
                  bytes_out=None):
        """Credit accepted work to `worker`. Callers that know WHICH chunk
        was accepted pass `wid` (+ survivor count / output bytes): that is
        the acceptance point, so the durable per-chunk telemetry record —
        with the full lease→fetch→push→accept timeline — is written here,
        master-side, exactly once per chunk (acceptance is gated on
        `WorkQueue.complete` returning the id as newly-done)."""
        with self.queue.lock:
            st = self._w(worker)
            st.chunks_done += n
            obs_metrics.counter(
                "dist_chunks_done_total",
                "results accepted by the master", ("worker",)
            ).labels(worker=worker).inc(n)
            if self.telemetry is not None and wid is not None:
                tl = self._timeline.pop(wid, {})
                self.telemetry.record(
                    event="chunk", status="done", wid=int(wid),
                    worker=worker, shard=st.shard, pid=st.pid,
                    content_key=tl.get("content_key"),
                    lease_ts=tl.get("lease_ts"), fetch_ts=tl.get("fetch_ts"),
                    push_ts=tl.get("push_ts"), accept_ts=time.time(),
                    survivors=None if survivors is None else int(survivors),
                    bytes_in=tl.get("bytes_in"),
                    bytes_out=None if bytes_out is None else int(bytes_out),
                    redelivered=int(tl.get("redelivered", 0)),
                    speculated=int(tl.get("speculated", 0)))

    def _on_redeliver(self, wid, worker, reason):
        """Queue-level reclaim hook (fires under the queue lock): count
        the redelivery and durably attribute the LOSING incarnation, so a
        SIGKILLed worker's half-processed chunk shows both attempts. A
        "speculated" reason is the first-completion-wins race resolving:
        the id is ALREADY done, so the record attributes the loser but the
        timeline is left for the winner's `done` record (written next)."""
        obs_metrics.counter(
            "dist_redeliveries_total", "leases reclaimed",
            ("worker", "reason")).labels(worker=worker, reason=reason).inc()
        if self.telemetry is None:
            return
        st = self.workers.get(worker)
        tl = self._timeline.get(wid, {})
        self.telemetry.record(
            event="chunk", status="redelivered", reason=reason,
            wid=int(wid), worker=worker,
            shard=st.shard if st else -1, pid=st.pid if st else None,
            content_key=tl.get("content_key"),
            lease_ts=tl.get("lease_ts"), fetch_ts=tl.get("fetch_ts"))
        if reason == "speculated":
            return
        # the next lease of this wid starts a fresh timeline but keeps the
        # redelivery and speculation counts, so the eventual "done" record
        # carries them
        self._timeline[wid] = {
            "redelivered": tl.get("redelivered", 0) + 1,
            "speculated": tl.get("speculated", 0)}

    # -- RPC surface --------------------------------------------------------
    def reserve(self, pid, shard):
        """Master-side (NOT served): pin the shard id a spawned process
        will be assigned when its `hello` lands. The spawn path calls
        this right after Popen, long before the child can finish its
        interpreter start-up, so handles/injectors keyed by shard stay
        valid without a shard ever riding argv."""
        with self.queue.lock:
            self._reserved[int(pid)] = int(shard)
            self._next_shard = max(self._next_shard, int(shard) + 1)

    def hello(self, worker=None, pid=None, shard=-1):
        """Worker sign-in: registers identity, returns the setup blob —
        the SAME blob whether the worker is part of the original fleet or
        joins a run already in progress (late joiners are how an elastic
        fleet absorbs churn). A rejoin after departure/death is a fresh
        incarnation: state returns to active and the epoch bumps.

        With `worker=None` the caller is ANNOUNCING, not asserting, its
        identity (the saxml join pattern): the registry assigns it the
        shard reserved for its pid at spawn — or the next free id for a
        walk-up joiner — and ships the assignment back in the setup blob
        under "assigned". When the master has a live tracer, its
        propagation context rides along under "trace"; when a store data
        plane is configured, its spec rides under "data_plane"."""
        assigned = None
        with self.queue.lock:
            if worker is None:
                shard = self._reserved.pop(int(pid), None) \
                    if pid is not None else None
                if shard is None:
                    shard = self._next_shard
                self._next_shard = max(self._next_shard, int(shard) + 1)
                worker = f"shard{int(shard)}"
                assigned = {"worker": worker, "shard": int(shard)}
            elif int(shard) >= 0:
                # explicit identities keep the assignment counter ahead
                # so a later announce never collides with them
                self._next_shard = max(self._next_shard, int(shard) + 1)
            known = worker in self.workers
            st = self._w(worker)
            st.pid, st.shard = pid, int(shard)
            st.last_beat = self.queue.clock()
            if not known or st.state != "active":
                obs_metrics.counter(
                    "dist_workers_joined_total",
                    "workers that signed in (first hello or rejoin)",
                    ("worker",)).labels(worker=worker).inc()
                st.state = "active"
                self.epoch += 1
                self._publish_membership()
        prop = obs_tracing.get_tracer().propagate()
        if prop is None and assigned is None and self.data_plane is None:
            return self._setup
        setup = dict(self._setup)
        if prop is not None:
            setup["trace"] = prop
        if assigned is not None:
            setup["assigned"] = assigned
        if self.data_plane is not None:
            setup["data_plane"] = self.data_plane.spec()
        return setup

    def lease(self, worker, max_items=1):
        with self.queue.lock:
            st = self._w(worker)
            st.lease_calls += 1
            st.last_beat = self.queue.clock()
            self.lease_calls += 1
            obs_metrics.counter(
                "dist_lease_calls_total", "queue round-trips",
                ("worker",)).labels(worker=worker).inc()
            if st.state != "active":
                # draining (or formally departed) workers take no more
                # work — an empty lease + the `draining` poll is their
                # exit signal once held leases are finished
                return []
            ids = self.queue.lease(worker, max_items)
            if not ids:
                # end-of-stream shape: nothing pending but work still in
                # flight, and THIS worker is idle — the backup-task rule
                # duplicates the slowest flagged in-flight item onto it
                ids = self._speculate_for(worker)
            if self.straggler is not None:
                for wid in ids:
                    self.straggler.start(wid)
            st.leased_total += len(ids)
            if ids:
                obs_metrics.counter(
                    "dist_leased_ids_total", "work ids granted",
                    ("worker",)).labels(worker=worker).inc(len(ids))
            if self.telemetry is not None and ids:
                now = time.time()
                for wid in ids:
                    tl = self._timeline.setdefault(wid, {})
                    tl["lease_ts"] = now
                    tl["worker"] = worker
        if self.monitor is not None:
            self.monitor.beat(worker)
        hook = self.on_grant
        if hook is not None:
            for wid in ids:
                hook(worker, wid)
        return ids

    def _speculate_for(self, worker):
        """Try to grant `worker` a speculative duplicate lease on the
        slowest straggling in-flight id. Returns [wid] or []. Called with
        the queue lock held, from an empty normal lease."""
        if self.straggler is None:
            return []
        for wid in self.straggler.stragglers():
            if self.queue.speculate(worker, wid):
                obs_metrics.counter(
                    "dist_speculations_total",
                    "speculative duplicate leases granted",
                    ("worker",)).labels(worker=worker).inc()
                # the eventual `done` record carries the speculation count
                # no matter which incarnation wins
                tl = self._timeline.setdefault(wid, {})
                tl["speculated"] = tl.get("speculated", 0) + 1
                return [wid]
        return []

    def _on_complete(self, wids):
        """Queue-level retirement hook (fires under the queue lock):
        closes the straggler detector's latency samples so its rolling
        p95 reflects every completion path."""
        if self.straggler is not None:
            for wid in wids:
                self.straggler.complete(wid)
        for wid in wids:           # retired ids never get re-offered
            self._offered.pop(wid, None)

    def lease_chunks(self, worker, max_items=1):
        """Store-plane lease: grant work ids AND publish their raw chunk
        batches to the shared store in the same round-trip, returning
        [[wid, key], ...] — the socket carries content keys (~70 bytes),
        never the batches. A key of None means the id retired between
        grant and offer (a redelivery race); the worker skips it. This is
        the whole data plane collapsed into the lease call: zero
        `fetch`/`fetch_many` round-trips remain."""
        if self.data_plane is None:
            raise RuntimeError("this QueueService has no store data plane")
        ids = self.lease(worker, max_items)
        with self.queue.lock:    # one pass over the key manifest, not per-item
            cached = {wid: self._offered.get(wid) for wid in ids}
        out, fresh = [], {}
        for wid in ids:
            item = self._materialize(wid)
            if item is None:     # retired between grant and offer
                out.append([wid, None])
                continue
            key = cached.get(wid)
            if key is None:      # first offer: hash + publish once
                key = fresh[wid] = self.data_plane.offer(wid, item)
            self._note_fetch(wid, item, plane="store", key=key)
            out.append([wid, key])
        if fresh:
            with self.queue.lock:
                self._offered.update(fresh)
        return out

    def _materialize(self, wid):
        """wid -> chunk batch via the master's loader (None when retired)."""
        if self._fetch_item is None:
            raise RuntimeError("this QueueService serves no data plane "
                               "(no fetch_item)")
        return self._fetch_item(wid)

    def _note_fetch(self, wid, item, plane, key=None):
        """Per-item data-plane accounting: the socket plane is charged
        the batch's bytes, the store plane only the key that replaced
        them — `dist_fetch_bytes_total{plane}` is how the tests prove
        the ≥90% cut."""
        raw = np.ascontiguousarray(item)
        wire = len(key) if plane == "store" else int(raw.nbytes)
        obs_metrics.counter(
            "dist_fetch_bytes_total",
            "data-plane bytes the master's socket carried for chunk "
            "fetches", ("plane",)).labels(plane=plane).inc(wire)
        if self.telemetry is not None:
            with self.queue.lock:
                tl = self._timeline.setdefault(wid, {})
                tl["fetch_ts"] = time.time()
                tl["bytes_in"] = int(raw.nbytes)
                tl["content_key"] = key[:21] if key is not None else \
                    hashlib.sha256(raw.tobytes()).hexdigest()[:16]

    def fetch(self, wid):
        """Data plane (socket plane): the chunk batch for one leased work
        id, materialized master-side and shipped over the control socket."""
        item = self._materialize(wid)
        if item is not None:
            self._note_fetch(wid, item, plane="socket")
        return item

    def fetch_many(self, worker, wids):
        """Batched data plane: one round-trip for a whole lease batch
        (without this, lease_items > 1 would amortize the lease call only
        to re-pay per-item fetch RTTs). One server-side pass — the batch
        is materialized and accounted item by item but heartbeats ONCE,
        and with a store data plane configured it degrades gracefully to
        the socket-plane fallback (the bytes still flow, still counted).
        Doubles as a heartbeat — the worker is provably alive and about
        to be busy for a while."""
        items = [self._materialize(wid) for wid in wids]
        for wid, item in zip(wids, items):
            if item is not None:
                self._note_fetch(wid, item, plane="socket")
        self.heartbeat(worker)
        return items

    def complete(self, work_ids, worker=None):
        return self.queue.complete(work_ids, worker=worker)

    def drain(self, worker):
        """Graceful leave: `worker` finishes the leases it holds and takes
        no more. Caller may be the worker itself (a node being
        decommissioned announces its own exit) or the master's autoscaler.
        The worker's runtime polls `draining` and exits once its lease
        comes back empty — the same exit shape as `finished`, scoped to
        one worker."""
        with self.queue.lock:
            st = self._w(worker)
            if st.state == "active":
                obs_metrics.counter(
                    "dist_workers_drained_total",
                    "workers asked to leave gracefully",
                    ("worker",)).labels(worker=worker).inc()
                self._set_state(st, "draining")
        return True

    def draining(self, worker) -> bool:
        """Worker-side poll: has this worker been asked to leave?"""
        with self.queue.lock:
            st = self.workers.get(worker)
            return st is not None and st.state in ("draining", "departed")

    def push_result(self, worker, wid, payload):
        """Result plane: worker hands back one finished work id. The
        master drains with `pop_results` and gates emission on
        `queue.complete`, so pushes from a redelivery race are accepted
        here and discarded there — exactly-once stays the master's call
        (and so does `chunks_done` credit, via `note_done`). Each push
        extends the worker's remaining leases: mid-batch progress IS a
        heartbeat. On the store data plane the payload is a tiny
        `{"store_key": ...}` ref (the result bytes went to the shared
        store); either way the bytes this socket carried are counted
        under `dist_push_bytes_total{plane}`."""
        plane = ("store" if isinstance(payload, dict)
                 and "store_key" in payload else "socket")
        obs_metrics.counter(
            "dist_push_bytes_total",
            "data-plane bytes the master's socket carried for result "
            "pushes", ("plane",)).labels(plane=plane).inc(
                _payload_nbytes(payload))
        with self.queue.lock:
            self.queue.heartbeat_extend(worker)
            self._w(worker).last_beat = self.queue.clock()
            self._results.append((worker, wid, payload))
            obs_metrics.counter(
                "dist_pushes_total", "results pushed (pre-acceptance)",
                ("worker",)).labels(worker=worker).inc()
            if self.telemetry is not None:
                self._timeline.setdefault(wid, {})["push_ts"] = time.time()
        if self.monitor is not None:
            self.monitor.beat(worker)
        return True

    def heartbeat(self, worker):
        with self.queue.lock:
            self.queue.heartbeat_extend(worker)
            self._w(worker).last_beat = self.queue.clock()
        if self.monitor is not None:
            self.monitor.beat(worker)
        return True

    def fail_worker(self, worker):
        """Reclaim a dead worker's leases AND record the death in the
        registry (state -> dead, epoch bump). Safe to call repeatedly —
        the state transition and the gauges settle on first call."""
        with self.queue.lock:
            back = self.queue.fail_worker(worker)
            st = self.workers.get(worker)
            if st is not None and st.state not in ("departed", "dead"):
                self._set_state(st, "dead")
        return back

    def state(self):
        return self.queue.state()

    def progress(self):
        return self.queue.progress()

    @property
    def finished(self):
        return self.queue.finished

    def next_deadline(self):
        return self.queue.next_deadline()

    def bye(self, worker, stats=None):
        """Worker sign-off with its idle/busy split (per-worker idle time
        is a Table 7 observable: deeper lease batches shrink it). A worker
        that traced locally ships its buffered span events here
        (stats["spans"]) — the master merges them into its tracer, which
        is how worker spans cross the pickle boundary."""
        with self.queue.lock:
            st = self._w(worker)
            for k in ("idle_s", "busy_s"):
                if stats and k in stats:
                    setattr(st, k, float(stats[k]))
            if st.state != "dead":
                if st.state != "departed":
                    obs_metrics.counter(
                        "dist_workers_left_total",
                        "workers that signed off gracefully",
                        ("worker",)).labels(worker=worker).inc()
                self._set_state(st, "departed")
        # a departed worker stops heartbeating BY DESIGN — drop it from
        # liveness tracking so it never surfaces in monitor.dead()
        if self.monitor is not None:
            self.monitor.forget(worker)
        if stats and stats.get("spans"):
            obs_tracing.get_tracer().add_events(stats["spans"])
        return True

    def metrics(self, render=False):
        """Read-only scrape of the master's metrics registry: a JSON/
        pickle-safe snapshot, or Prometheus text when `render` is set."""
        reg = obs_metrics.get_registry()
        return reg.render() if render else reg.snapshot()

    # -- master-side (NOT served) -------------------------------------------
    def pop_results(self):
        """Drain the result inbox: [(worker, wid, payload), ...]."""
        out = []
        with self.queue.lock:
            while self._results:
                out.append(self._results.popleft())
        return out

    def resolve_result(self, payload):
        """Materialize a store-plane result ref into the full payload
        (`ChunkStore.fetch` by key); socket-plane payloads pass through.
        Called by the master's emit loop — never in a handler thread, so
        the store read happens off the RPC path."""
        if (self.data_plane is not None and isinstance(payload, dict)
                and "store_key" in payload):
            full = self.data_plane.take(payload["store_key"])
            if full is None:
                raise RuntimeError(
                    "store data plane lost result entry "
                    f"{payload['store_key'][:21]}…")
            return full
        return payload

    def worker_report(self):
        """Snapshot of every known worker's progress, sorted by shard:
        leases held right now, chunks done, redeliveries charged to it,
        seconds since its last heartbeat."""
        with self.queue.lock:
            now = self.queue.clock()
            out = []
            for st in self.workers.values():
                st.leases_held = len(self.queue.leases_held(st.worker))
                st.redeliveries = int(
                    self.queue.redelivered_from.get(st.worker, 0))
                st.last_beat_age_s = (None if st.last_beat is None
                                      else float(now - st.last_beat))
                out.append(st)
            return sorted(out, key=lambda s: (s.shard, s.worker))

    # -- WorkQueue duck-typing extras (simulated in-process path) -----------
    def heartbeat_extend(self, worker):
        self.heartbeat(worker)

    def leases_held(self, worker):
        return self.queue.leases_held(worker)

    @property
    def clock(self):
        return self.queue.clock

    @property
    def lease_timeout_s(self):
        return self.queue.lease_timeout_s

    @property
    def redeliveries(self):
        return self.queue.redeliveries

    @property
    def redelivered_from(self):
        return self.queue.redelivered_from

    @property
    def n_items(self):
        return self.queue.n_items


# -------------------------------------------------------- result protocol

def _payload_nbytes(payload) -> int:
    """Wire-size estimate of one data-plane value: array bytes dominate;
    strings/bytes count their length; scalars a flat 8. Close enough to
    pickled size to grade the socket-vs-store byte cut."""
    if payload is None:
        return 0
    if isinstance(payload, np.ndarray):
        return int(payload.nbytes)
    if isinstance(payload, dict):
        return sum(_payload_nbytes(v) for v in payload.values())
    if isinstance(payload, (list, tuple)):
        return sum(_payload_nbytes(v) for v in payload)
    if isinstance(payload, (str, bytes)):
        return len(payload)
    return 8


def pack_result(res) -> dict:
    """BatchResult -> picklable payload (mirrors the store-entry layout:
    masks + stats + cleaned survivors; the pre-denoise wave5 intermediate
    never crosses the process boundary — only its width does, so the
    master can rebuild a shape-correct det record)."""
    det = res.det
    return {
        "cleaned": np.asarray(res.cleaned, np.float32),
        "keep": np.asarray(det.keep), "rain": np.asarray(det.rain),
        "silence": np.asarray(det.silence),
        "cicada15": np.asarray(det.cicada15),
        "stats": {k: (int(v) if k == "n_chunks5" else float(v))
                  for k, v in det.stats.items()},
        "n_kept": int(res.n_kept), "src_bytes": int(res.src_bytes),
        "wave_width": int(det.wave5.shape[-1]),
    }


def unpack_result(payload):
    """payload -> (PipelineOutput, fields) — fields carries cleaned /
    n_kept / src_bytes for the master's BatchResult. wave5 is zero-filled
    at the recorded shape, the same convention CachedPlan uses for store
    hits: it is an intermediate no downstream consumer reads."""
    keep = payload["keep"]
    wave5 = np.zeros((keep.shape[0], int(payload["wave_width"])),
                     np.float32)
    det = PipelineOutput(wave5=wave5, keep=keep, rain=payload["rain"],
                         silence=payload["silence"],
                         cicada15=payload["cicada15"],
                         stats=dict(payload["stats"]))
    return det, {"cleaned": payload["cleaned"],
                 "n_kept": int(payload["n_kept"]),
                 "src_bytes": int(payload["src_bytes"])}
