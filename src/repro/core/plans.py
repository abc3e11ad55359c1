"""Execution plans: HOW a validated `PipelineGraph` runs on a batch stream.

The graph fixes WHAT computes (stage order, removal points); a plan picks
the execution strategy. Six plans, and when to pick each:

  * `FusedPlan`     — one jit straight through; removed chunks are masked
                      but still computed (the paper's no-early-exit
                      baseline). Pick for graphs without a removal point,
                      for correctness references, or when survivor rates
                      are so high that early exit buys nothing.
  * `TwoPhasePlan`  — detection jit -> host reads the keep mask (the
                      paper's master bookkeeping) -> survivors compacted /
                      re-batched -> tail jit on the survivor batch only.
                      The paper's headline economy: MMSE cost scales with
                      surviving audio. Pick as the single-stream default.
  * `StreamingPlan` — two-phase with dispatch-ahead over a loader: phase-A
                      detection of batch k+1 is enqueued on the device
                      before phase B of batch k, so host-side mask readback
                      + compaction overlap device work. Now a depth-1
                      `AsyncPlan` with the historical linear padding — kept
                      as the conservative dispatch-ahead baseline.
  * `AsyncPlan`     — the deep pipeline: a bounded window of `depth`
                      detection batches in flight (keep masks prefetched
                      with `copy_to_host_async` the moment each detect is
                      enqueued), device-resident survivor compaction (the
                      tail jit gathers survivors out of the still-on-device
                      batch; only the B-bool mask and the cleaned survivors
                      ever cross the host boundary), power-of-two survivor
                      buckets (O(log B) tail compiles instead of one per
                      count), optional buffer donation, and double-buffered
                      cleaned readback finished on a reader thread, so the
                      loop sends the next batch first. Per-batch
                      `BatchResult.timings` record dispatch/readback/
                      compact/tail/emit plus the
                      in-flight depth and transferred bytes. Pick for long
                      single-host streams; `depth` 2-4 is enough to hide
                      mask readback on one device — go deeper only when
                      emission jitter (a slow consumer) must also be
                      absorbed. Emission order is ALWAYS input order.
  * `ShardedPlan`   — the multi-shard execution backbone, now a thin
                      MASTER over a pluggable transport: the shared leased
                      `WorkQueue` is served behind a `repro.dist.
                      QueueService` (lease / complete / heartbeat /
                      fail_worker / state + the fetch/push data planes),
                      and the workers that pull from it are picked by
                      `transport=`:

                        transport   workers                 use when
                        ---------   ---------------------   -----------------
                        "inproc"    simulated loop itera-   tests, single
                                    tions in this process   host, determinism
                                    (the historical mode,   (the default)
                                    preserved bit-for-bit)
                        "proc"      real OS processes        real parallelism
                                    (`python -m repro.       + fault isolation;
                                    dist.worker`), pickled   SIGKILL a worker
                                    messages over authen-    and the stream
                                    ticated localhost        still emits each
                                    sockets                  chunk exactly once
                        "tcp"       real OS processes over   workers on OTHER
                                    a non-loopback bind      hosts; pair with
                                    (0.0.0.0 + advertised    `data_plane=` so
                                    dial address); workers   the master socket
                                    join by announcing at    carries only
                                    hello (registry), same   leases, ids, acks
                                    wire protocol as proc

                      `data_plane=` (a ChunkStore, directory path, or
                      `repro.dist.StoreDataPlane`) moves chunk bytes OFF
                      the master's control socket: leases arrive as
                      content keys (`lease_chunks`), workers read raw
                      batches from and push results into the shared
                      store, and the master materialises payloads by key
                      at acceptance — fetch/push socket traffic drops
                      ≥90% per chunk (`dist_fetch_bytes_total{plane}`,
                      checked by `tests/test_dist_dataplane.py`). Works under
                      "proc" too; it is what makes "tcp" scale past one
                      box. Workers lease work ids in batches (`lease_items`,
                      the paper's Table 7 `max_queue_size` knob —
                      amortizes queue round-trips against redelivery
                      exposure), at-least-once redelivery on lease expiry
                      or `fail_worker` replaces the paper's crash-tracking
                      master, the `Rebalancer` owns the detection->MMSE
                      survivor re-shard (in-proc: physically re-slices;
                      proc: the per-round load ledger of the paper's Figs
                      14-16), and completion gates emission so output
                      stays exactly-once on top of at-least-once delivery.
                      Emission order: ascending work id under "proc" (==
                      the crash-free in-proc order); `worker_stats` holds
                      the per-worker progress report of the last run.
                      Single-batch `__call__` (the serve path) always
                      row-splits in-process — spawning processes per
                      request is not a serving latency anyone wants. Pick
                      for multi-worker runs, or whenever fault tolerance
                      matters.
  * `CachedPlan`    — content-addressed persistence around ANY inner plan
                      (including the sharded one): the `repro.store`
                      ChunkStore is consulted before dispatch, only misses
                      run through the inner plan, cached survivors merge
                      back in stream order, fresh results are written after.
                      With a `RunJournal` a killed `--store`d run relaunched
                      with `--resume` emits each chunk exactly once —
                      PR 2's worker-crash guarantee extended across PROCESS
                      restarts. Pick for rolling archives where runs overlap
                      yesterday's data (re-runs become lookups), for config
                      re-runs, and for any stream that must survive kills.
                      Without a store it degrades to a transparent
                      pass-through of its inner plan.

The two-phase family (`two_phase` / `streaming` / `async`) additionally
owns the FUSED SURVIVOR TAIL switch. When the graph's post-removal chain
is the canonical fused tail — `("mmse",)` or `("hpf", "mmse")`, per
`PipelineGraph.fused_tail_spec` — the plan's survivor dispatch swaps the
staged `tail_idx` phase for `tail_idx_fused`: one Pallas pass
(`kernels/fused_tail`) doing gather-compact + [HPF] + STFT + MMSE gain
with power/spec/gain tiles VMEM-resident, only the iSTFT outside. Keyed
per pow2 survivor bucket in the same CompileCache, same donation rules;
bitwise equal to the staged tail in ref mode and within float32 rounding
of it in the kernel modes. `fuse_tail=` overrides: None (default)
auto-engages on a canonical tail, False forces the staged path, True
demands fusion and raises on a non-canonical tail. Any other survivor
chain silently falls back to the staged per-stage dispatches.

Serving sits ON TOP of these plans rather than being a seventh one: the
batch-stream plans above amortize compile + dispatch over a stream that
already exists, while `repro.serve` answers requests that arrive one at a
time. `serve.WorkerPool` keeps `repro.dist` workers alive across pumps
(a standing work queue instead of `ShardedPlan`'s per-stream one, so jits
stay warm and pids stable between waves), `serve.ContinuousBatcher`
coalesces concurrent requests into zero-padded pow2 device batches with
admission control and per-request deadlines, and `serve.
PreprocessService` checks a `CachedPlan`-style store before ever touching
a worker. Any batch the serving tier dispatches runs the same `two_phase`
stages as the plans here and stays bit-identical to them.

Observability (`repro.obs`): every plan family reports into the one
process-local metrics registry and the run tracer, zero-cost when both
are off. What each plan emits and where it lands:

  * counters/histograms (`obs.metrics`, via `_record_batch` at each
    plan's emission point): `plan_batches_total` / `plan_chunks_total` /
    `plan_survivors_total` / `plan_src_bytes_total` and
    `plan_{d2h,h2d}_bytes_total`, all labeled `{plan=...}`, plus the
    `plan_stage_seconds{plan,stage}` histogram fed from the same numbers
    the per-batch `BatchResult.timings` dict carries (the dict stays —
    it is the per-batch view, the registry is the aggregate).
    `AsyncPlan.last_timings` is now a bounded ring (`TIMINGS_CAP`).
  * spans (`obs.tracing`: the Chrome trace when a tracer is installed,
    and `repro.<name>` on the `jax.profiler` trace's host plane while a
    profiler session records), each carrying the batch's `wid`:
    `detect_dispatch` (async window fill: `h2d_put`, `detect_enqueue`),
    `mask_wait`, `compact`, `tail_enqueue`, `emit` (the emitting loop's
    wait for the cleaned rows), `tail_wait` and `d2h_wait` (the readback,
    on `AsyncPlan`'s reader thread while input flows), `fused_batch`; the
    `_s` timings are these spans' durations (`obs_tracing.timed`). A
    cleaned batch split by rows over a mesh is read shard by shard on the
    plan's shard threads, each in a `d2h_shard` span (also carrying
    `shard`, its place in row order) around the wait for its host value
    and, where that is in row order, its `d2h_shard_copy`; where every
    shard's host value is in column order the copy is split by column
    blocks instead, one `d2h_block_copy` span (carrying `block`) each;
    ShardedPlan's proc master additionally marks `accept` (result
    accepted at the completion gate) and `emit_gated` instants, whose
    gap makes straggler-blocked emission visible. Worker processes
    record their own lease/fetch_many/compute/push spans (see
    `repro.dist.worker`) parented under the master's run span.
  * durable per-chunk telemetry (`obs.telemetry`): pass `telemetry=`
    (a TelemetryWriter) to ShardedPlan — both transports hand it to
    their QueueService, which writes lease/fetch/push/acceptance
    records master-side; redeliveries are attributed via
    `WorkQueue.on_redeliver`.

Elasticity (`ShardedPlan` proc mode + `serve.WorkerPool`): the fleet a
run starts with is not the fleet it must finish with.

  * membership — `QueueService.hello/bye/drain` is a real registry:
    per-worker state (active/draining/departed/dead) plus a membership
    epoch that bumps on every transition (`dist_membership_epoch` /
    `dist_workers{state}` gauges). A worker may `hello` into a run
    already in progress and receives the SAME setup blob the original
    fleet got; `ShardedPlan`'s proc master exposes this as `plan.fleet`
    (a `FleetControl`: spawn/drain/kill/stall live workers mid-run), and
    `WorkerPool` autoscales between `min_workers`/`max_workers` on
    sustained queue backlog, scaling down by DRAINING idle workers — a
    drained worker finishes its held leases, takes no more, and exits
    through `bye`, so nothing is ever reaped from it.
  * speculation — with `speculate=` armed, a `StragglerDetector` inside
    the QueueService watches lease->complete latencies; when an idle
    ACTIVE worker's lease comes back empty with work still in flight
    (the end-of-stream shape), the slowest flagged item is duplicated to
    it via `WorkQueue.speculate` WITHOUT reaping the original lease.
    First completion wins; the loser is attributed in telemetry under
    reason "speculated".
  * when speculation is safe — exactly-once emission needs no new
    machinery precisely because every plan already gates emission on
    `WorkQueue.complete()` returning the id as newly retired: duplicate
    pushes are discarded at that gate, and emission order (ascending
    work id) is position-, not worker-, determined. Speculation is
    therefore safe whenever the computation is a pure function of the
    fetched bytes — true for every stage graph here. It would NOT be
    safe for side-effecting work (per-item external writes) without an
    idempotency layer at the effect site.

All plans sit behind the `Preprocessor` facade, and all jitted phases live
in one keyed LRU `CompileCache`. Keys are *value* fingerprints — config,
stage list, `ShardingRules.fingerprint` (mesh shape + rule table + device
ids), kernel backend mode — never object ids, so logically-equal rules
objects share compiles and the cache cannot alias after GC reuses an id
(the old `_JIT_CACHE`/`id(rules)` bug). `ShardedPlan` accepts per-shard
rules (`distributed.sharding.pool_rules`): same-mesh shards share one
compile, per-host meshes key separately by device ids.
"""
from __future__ import annotations

import collections
import itertools
import operator
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as SCHED
from repro.core.graph import (GraphValidationError, PipelineGraph,
                              PipelineOutput, check_split, chunk_split)
from repro.data.loader import ShardedLoader, make_shard_pool
from repro.data.queue import WorkQueue
from repro.dist.data_plane import StoreDataPlane
from repro.dist.service import QueueService, pack_result, unpack_result
from repro.dist.transport import ProcTransport, TcpTransport
from repro.distributed.sharding import NULL_RULES
from repro.ft.failure import StragglerDetector
from repro.kernels import backend
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.runtime import refuse_worker_processes
from repro.store import ChunkStore, RunJournal, content_key


class CompileCache:
    """Keyed LRU for jitted phase functions (capped — the old global grew
    without bound). Tail compiles key per padded survivor size, so the
    cap bounds COMPILE memory too: hot entries (the every-batch detect,
    pow2's O(log B) buckets) stay resident by recency, while a stream
    that insists on linear padding over more distinct survivor counts
    than the cap re-pays those compiles — the pathology pow2 bucketing
    exists to remove, kept bounded rather than hidden."""

    def __init__(self, maxsize=256):
        self.maxsize = maxsize
        self._d = collections.OrderedDict()

    def get(self, key, build):
        if key in self._d:
            self._d.move_to_end(key)
            return self._d[key]
        val = build()
        self._d[key] = val
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
        return val

    def __len__(self):
        return len(self._d)

    def __contains__(self, key):
        return key in self._d

    def keys(self):
        return list(self._d)

    def clear(self):
        self._d.clear()


JIT_CACHE = CompileCache(maxsize=256)


def _cache_key(kind, graph: PipelineGraph, rules):
    return (kind, graph.fingerprint, rules.fingerprint, backend.get_mode())


def _phase_fn(kind, graph: PipelineGraph, rules):
    """Plain (un-jitted) callable for one phase — what dry-run lowering and
    the jit cache both consume. Each is a named function, so its program
    reads `jit_<phase>` in HLO and in a device trace."""
    if kind == "fused":
        def fused(a):
            return graph.fused(a, rules)
        return fused
    if kind == "detect":
        def detect(a):
            return graph.detection(a, rules)
        return detect
    if kind in ("tail", "mmse"):
        def tail(w):
            return graph.tail(w, rules)
        return tail
    if kind == "tail_idx":
        def tail_idx(w, i):
            return graph.tail_indexed(w, i, rules)
        return tail_idx
    if kind == "tail_idx_fused":
        def tail_idx_fused(w, i):
            return graph.tail_indexed_fused(w, i, rules)
        return tail_idx_fused
    raise KeyError(f"unknown phase {kind!r}")


def _to_device(chunks, rules):
    """Host batch -> device, split over the chunk axes when the rules
    carry a mesh; raises if the batch does not divide over them."""
    sharding = rules.sharding("chunks", *([None] * (np.ndim(chunks) - 1)))
    if sharding is None:
        return jnp.asarray(chunks)
    check_split(np.shape(chunks)[0], rules)
    return jax.device_put(chunks, sharding)


def _jitted(kind, graph, rules, donate=(), shape=None):
    """Jitted phase from the shared cache. `donate` (a donate_argnums
    tuple) is part of the key: a donating and a non-donating caller of the
    same phase must not alias one compile. `shape` (the padded survivor
    count for the tail gather) is keyed too, so one cache entry == one
    XLA compile and the cache length is an honest retrace ledger —
    without it, shape retraces would hide inside a single jit wrapper,
    uncountable and uncapped by the LRU."""
    donate = tuple(donate)
    return JIT_CACHE.get(_cache_key(kind, graph, rules) + (donate, shape),
                         lambda: jax.jit(_phase_fn(kind, graph, rules),
                                         donate_argnums=donate))


@dataclass
class BatchResult:
    """One batch through a plan: compacted survivors + the detection record."""
    cleaned: np.ndarray             # (n_kept, S_final) denoised survivors
    det: PipelineOutput             # detection-phase record (masks, stats)
    n_kept: int
    wid: object = None              # loader work id (when run over a loader)
    labels: object = field(default=None, repr=False)   # loader passthrough
    src_bytes: int = 0              # measured input bytes (throughput acct)
    timings: dict = field(default=None, repr=False)
    # per-batch pipeline instrumentation (two-phase-family plans); each
    # `_s` key is the duration of the `repro.*` span named beside it:
    #   dispatch_s  detect_dispatch: host copy + detect enqueue (async —
    #               not detect compute time)
    #   readback_s  mask_wait: blocking part of the keep-mask readback
    #   compact_s   compact: host index bookkeeping (the "master" role)
    #   tail_s      tail_enqueue: tail enqueue + async cleaned-copy start
    #   emit_s      emit: the emitting loop's blocking wait for the cleaned
    #               rows (the readback itself inline; under AsyncPlan's
    #               reader, only what that thread has not finished)
    #   tail_wait_s tail_wait: until the tail's output exists on device
    #   d2h_wait_s  d2h_wait: the rest of the copy back and its host
    #               re-layout (both on whichever thread reads back)
    #   d2h_shards  shards of a row-split cleaned batch read apart, in
    #               parallel (0: read whole, as on one device)
    #   d2h_col_blocks  column blocks the copy of those shards was split
    #               into (0: copied shard by shard, or read whole)
    #   emit_ready  AsyncPlan: 1.0 when the rows were on the host already
    #               as the loop took the batch, else 0.0 (these four only
    #               for batches that ran a tail)
    #   in_flight   detect batches in the window when this one dispatched
    #   d2h_bytes / h2d_bytes   host-boundary traffic this batch caused
    #               (h2d counts the batch itself when it came from host)
    #   gather_bytes  bytes each device received in the fused tail's
    #               all-gather of the batch ((n-1)/n of it; 0 on one
    #               device and for a batch with no tail)
    #   kept_skew   the most kept chunks among one device's own chunks
    #               over the mean of the devices (1.0 on one device)
    #   tail_rows / n_real      padded tail batch rows vs real survivors


# Cap on retained per-batch timing dicts (`AsyncPlan.last_timings`): long-
# lived streams used to grow this list without bound; the registry now
# keeps the aggregate view, so the attribute is a bounded recent-history
# ring.
TIMINGS_CAP = 4096

# kept_skew is 1.0 when even and at most the device count
SKEW_BUCKETS = (1.0, 1.05, 1.1, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0)

_STAGE_KEYS = ("dispatch_s", "readback_s", "compact_s", "tail_s", "emit_s",
               "tail_wait_s", "d2h_wait_s")


def _record_batch(plan_name, res: "BatchResult"):
    """Mirror one emitted batch into the metrics registry — counters for
    volume, histograms for the per-stage timings that previously lived
    only in the ad-hoc `BatchResult.timings` dict. The dict itself stays
    on the result (callers depend on it); this is the aggregate view."""
    reg = obs_metrics.get_registry()
    if not reg.enabled:
        return
    lab = {"plan": plan_name}
    reg.counter("plan_batches_total", "batches emitted",
                ("plan",)).labels(**lab).inc()
    if res.det is not None:
        reg.counter("plan_chunks_total", "chunks processed",
                    ("plan",)).labels(**lab).inc(int(np.size(res.det.keep)))
    reg.counter("plan_survivors_total", "chunks surviving detection",
                ("plan",)).labels(**lab).inc(int(res.n_kept))
    reg.counter("plan_src_bytes_total", "input bytes consumed",
                ("plan",)).labels(**lab).inc(int(res.src_bytes))
    t = res.timings
    if not t:
        return
    for k in _STAGE_KEYS:
        if k in t:
            reg.histogram("plan_stage_seconds", "per-batch stage wall time",
                          ("plan", "stage")).labels(
                plan=plan_name, stage=k[:-2]).observe(t[k])
    for k, what in (("d2h_bytes", "host-boundary traffic"),
                    ("h2d_bytes", "host-boundary traffic"),
                    ("gather_bytes", "bytes each device received in the "
                     "tail's all-gather"),
                    ("d2h_col_blocks", "column blocks the cleaned "
                     "readback's copy was split into")):
        if k in t:
            reg.counter(f"plan_{k}_total", what,
                        ("plan",)).labels(**lab).inc(int(t[k]))
    if "kept_skew" in t:
        reg.histogram("plan_kept_skew", "kept chunks of the busiest "
                      "device over the mean", ("plan",),
                      buckets=SKEW_BUCKETS).labels(**lab).observe(
            t["kept_skew"])


class _StreamMeta:
    """Internal marker for ShardedPlan's plain-stream wrapper: carries the
    ORIGINAL stream wid + labels through the queue as the item's `extra`,
    unambiguously distinct from user labels that happen to be tuples."""
    __slots__ = ("wid", "labels")

    def __init__(self, wid, labels):
        self.wid = wid
        self.labels = labels


def _iter_batches(batches):
    """Normalise a batch stream: accepts arrays, (chunks, labels) pairs, or
    the (wid, (chunks, labels)) items AudioChunkLoader yields."""
    for i, item in enumerate(batches):
        wid, payload, extra = i, item, None
        if isinstance(item, tuple) and len(item) == 2 \
                and np.ndim(item[0]) == 0:
            wid, payload = item
        if isinstance(payload, tuple):
            chunks = payload[0]
            extra = payload[1] if len(payload) > 1 else None
        else:
            chunks = payload
        yield wid, chunks, extra


class ExecutionPlan:
    """Base: one batch via `__call__`, a stream via `run` (plans override
    `run` to pipeline across batches)."""
    name = ""

    def __init__(self, graph: PipelineGraph, rules=NULL_RULES,
                 pad_multiple=1):
        self.graph = graph
        self.rules = rules
        self.pad_multiple = max(1, int(pad_multiple))

    def __call__(self, audio) -> BatchResult:
        raise NotImplementedError

    def run(self, batches):
        for wid, chunks, extra in _iter_batches(batches):
            res = self(jnp.asarray(chunks))
            yield replace(res, wid=wid, labels=extra)


class FusedPlan(ExecutionPlan):
    name = "fused"

    def __call__(self, audio) -> BatchResult:
        with obs_tracing.span("fused_batch"):
            x = jnp.asarray(audio)
            out = _jitted("fused", self.graph, self.rules)(x)
            keep = np.asarray(out.keep)
            cleaned = np.asarray(out.wave5)[keep]
        res = BatchResult(cleaned=cleaned, det=out, n_kept=int(keep.sum()),
                          src_bytes=int(x.nbytes))
        _record_batch(self.name, res)
        return res


def _shard_reads(indices, shape, n_real):
    """How to read the first `n_real` rows of an array split by rows, one
    shard at a time. `indices` are its shards' `shard.index`, in any order.
    Returns `(i, part, lo, hi)` for each shard that holds real rows: its
    position `i` in `indices`, its place `part` in row order, and the real
    rows [lo, hi) it holds. None unless the indices slice axis 0 alone
    (every other axis whole) into two or more ranges that tile it once."""
    spans = []
    for i, (rows, *rest) in enumerate(indices):
        if not isinstance(rows, slice) or rows.step not in (None, 1) or any(
                not isinstance(r, slice) or r.indices(d) != (0, d, 1)
                for r, d in zip(rest, shape[1:])):
            return None
        lo, hi, _ = rows.indices(shape[0])
        spans.append((lo, hi, i))
    spans.sort()
    edges = [0] + [hi for _, hi, _ in spans]
    if len(spans) < 2 or edges[-1] != shape[0] \
            or [lo for lo, _, _ in spans] != edges[:-1]:
        return None
    return [(i, part, lo, min(hi, n_real))
            for part, (lo, hi, i) in enumerate(spans) if lo < n_real]


# name prefix of the threads that read a row-split cleaned batch's shards
SHARD_THREAD = "repro-shard"


@dataclass
class _PendingTail:
    """A batch whose tail is dispatched but not yet read back: everything
    `_readback` and `_emit` need, held while the device works and the
    cleaned rows stream host-ward via copy_to_host_async."""
    det: PipelineOutput
    out: object                     # device cleaned batch (None: 0 kept)
    n_real: int
    wid: object
    extra: object
    src_bytes: int
    timings: dict


class TwoPhasePlan(ExecutionPlan):
    name = "two_phase"

    def __init__(self, graph, rules=NULL_RULES, pad_multiple=1,
                 bucket="linear", donate=False, fuse_tail=None):
        super().__init__(graph, rules, pad_multiple)
        if not graph.has_removal_point:
            raise GraphValidationError(
                f"plan '{self.name}' needs a 'removal_point' stage in the "
                f"graph (stages: {graph.names}); use the fused plan for "
                f"graphs without early exit")
        self.bucket = bucket
        SCHED.quantize_survivors(0, 1, 1, bucket)     # validate the mode
        if donate is None:                            # auto: off on CPU,
            donate = jax.default_backend() != "cpu"   # on where it pays
        self.donate = bool(donate)
        # fused survivor tail (kernels/fused_tail): None = auto-engage
        # whenever the graph's post-removal chain IS the canonical fused
        # tail; True = require it (error otherwise); False = always staged
        spec = graph.fused_tail_spec
        if fuse_tail is None:
            fuse_tail = spec is not None
        elif fuse_tail and spec is None:
            raise GraphValidationError(
                f"fuse_tail=True but post-removal stages "
                f"{graph.names[graph._cut():]} are not the canonical "
                f"[hpf ->] mmse fused tail")
        self.fuse_tail = bool(fuse_tail)
        # threads reading a row-split cleaned batch's shards: started at
        # the first such readback, one a shard; `AsyncPlan.run` ends them
        # as it ends, else they stay with the plan (idle) until it goes
        self._shard_pool = None
        self._shard_pool_lock = threading.Lock()

    def detect(self, audio) -> PipelineOutput:
        return _jitted("detect", self.graph, self.rules)(jnp.asarray(audio))

    def _detect_donated(self, x) -> PipelineOutput:
        """Detect with the input buffer donated to the jit — only valid
        when the caller owns `x` (it made the device copy itself)."""
        donate = (0,) if self.donate else ()
        return _jitted("detect", self.graph, self.rules, donate)(x)

    def _start_tail(self, det: PipelineOutput, wid=None, extra=None,
                    src_bytes=0, timings=None) -> _PendingTail:
        """Master bookkeeping, device-resident: the host reads back ONLY
        the keep mask (B bools), builds a padded survivor-index vector
        (bucketed so the tail jit compiles O(log B) shape variants), and
        the tail jit gathers + compacts + denoises ON DEVICE — the full
        pre-denoise waveform never crosses the host boundary. With
        `donate` the wave5 buffer is donated to the tail gather, so the
        det record's wave5 must not be read after this call."""
        timings = dict(timings or {})
        with obs_tracing.timed("mask_wait", timings, "readback_s", wid=wid):
            keep = np.asarray(det.keep)               # the only readback
        with obs_tracing.timed("compact", timings, "compact_s", wid=wid):
            idx, n_real = SCHED.survivor_indices(keep, self.pad_multiple,
                                                 self.bucket)
        # the fused tail all-gathers the batch to every device of a mesh:
        # each receives the (n - 1) / n of it that it does not hold
        _, n_dev = chunk_split(self.rules)
        gather = det.wave5.nbytes * (n_dev - 1) // n_dev \
            if n_real and self.fuse_tail else 0
        out = None
        with obs_tracing.timed("tail_enqueue", timings, "tail_s", wid=wid):
            if n_real:
                donate = (0,) if self.donate else ()
                kind = "tail_idx_fused" if self.fuse_tail else "tail_idx"
                tail = _jitted(kind, self.graph, self.rules, donate,
                               shape=len(idx))
                out = tail(det.wave5, jnp.asarray(idx))   # async dispatch
                if hasattr(out, "copy_to_host_async"):
                    out.copy_to_host_async()          # stream back early
        timings.update(
            h2d_bytes=timings.get("h2d_bytes", 0)
            + (idx.nbytes if n_real else 0),
            d2h_bytes=keep.nbytes, gather_bytes=gather,
            kept_skew=SCHED.kept_skew(keep, n_dev),
            tail_rows=0 if idx is None else len(idx), n_real=n_real)
        return _PendingTail(det, out, n_real, wid, extra, src_bytes,
                            timings)

    def _readback(self, pend: _PendingTail) -> np.ndarray:
        """Block on (the remainder of) the cleaned readback and return the
        real rows. Padded rows are sliced off here — and they are zero rows
        from the fill gather, never repeats of real audio. The wait splits
        in two: the tail program finishing on the device, then the rest of
        the copy back (started at dispatch) and its host-side re-layout.
        Touches only `pend` and the plan's shard threads, so it may run on
        a thread of its own."""
        if pend.out is None:
            return np.zeros((0, pend.det.wave5.shape[-1]), np.float32)
        out, n_real, t, wid = pend.out, pend.n_real, pend.timings, pend.wid
        with obs_tracing.timed("tail_wait", t, "tail_wait_s", wid=wid):
            out.block_until_ready()
        with obs_tracing.timed("d2h_wait", t, "d2h_wait_s", wid=wid):
            shards = () if out.is_fully_replicated else out.addressable_shards
            reads = _shard_reads([s.index for s in shards], out.shape, n_real)
            if reads is None:
                cleaned, blocks = np.asarray(out)[:n_real], 0
            else:
                cleaned, blocks = self._read_shards(pend, shards, reads)
        t["d2h_shards"] = 0 if reads is None else len(reads)
        t["d2h_col_blocks"] = blocks
        t["d2h_bytes"] += out.nbytes
        return cleaned

    def _read_shards(self, pend, shards, reads):
        """The real rows of a row-split cleaned batch as a fresh array
        (callers keep `cleaned`), and the column blocks its copy was split
        into. Each shard's host value is waited for on a thread of its own:
        JAX's `np.asarray` of the whole array waits for the shards one
        after another on the calling thread, and copies each into a fresh
        array of every row, padding included.

        A host value in row order is copied into a C-order result at once,
        on its shard's thread. Where every one is in column order (a TPU
        runtime's), the result takes that order and the copy is split by
        column blocks, one a shard thread: each thread then writes
        contiguous runs into pages of its own, where a row copy would
        transpose."""
        out, wid = pend.out, pend.wid
        shape = (pend.n_real,) + out.shape[1:]
        # untouched, so never faulted in, when the column path takes over
        cleaned = np.empty(shape, out.dtype)

        def copy(r, rows):
            _, part, lo, hi = r
            with obs_tracing.span("d2h_shard_copy", wid=wid, shard=part):
                cleaned[lo:hi] = rows[:hi - lo]

        def read(r):
            with obs_tracing.span("d2h_shard", wid=wid, shard=r[1]):
                rows = np.asarray(shards[r[0]].data)   # its copy started
                if np.isfortran(rows):
                    return rows         # copied once every shard's is here
                copy(r, rows)

        n = len(shards)
        held = [(r, rows) for r, rows in zip(
            reads, self._on_shard_threads(read, reads, n)) if rows is not None]
        if len(held) < len(reads):      # some in row order: the rest follow
            self._on_shard_threads(lambda h: copy(*h), held, n)
            return cleaned, 0
        by_cols = np.empty(shape, out.dtype, order="F")
        edges = [shape[1] * k // n for k in range(n + 1)]

        def copy_block(block):
            c0, c1 = edges[block], edges[block + 1]
            with obs_tracing.span("d2h_block_copy", wid=wid, block=block):
                for (_, _, lo, hi), rows in held:
                    by_cols[lo:hi, c0:c1] = rows[:hi - lo, c0:c1]

        self._on_shard_threads(copy_block, range(n), n)
        return by_cols, n

    def _on_shard_threads(self, fn, items, n):
        """`fn(item)` for each of `items` on the plan's shard threads,
        started at the first use, `n` of them; the results in order. A
        failed call fails the batch."""
        with self._shard_pool_lock:
            if self._shard_pool is None:
                self._shard_pool = ThreadPoolExecutor(
                    n, thread_name_prefix=SHARD_THREAD)
            futs = [self._shard_pool.submit(fn, x) for x in items]
        wait(futs)
        return [f.result() for f in futs]

    def _close_shard_pool(self):
        """End the shard threads (after the last readback that uses them)."""
        with self._shard_pool_lock:
            pool, self._shard_pool = self._shard_pool, None
        if pool is not None:
            pool.shutdown(cancel_futures=True)

    def _emit(self, pend: _PendingTail, cleaned) -> BatchResult:
        """The batch's result, mirrored into the metrics registry."""
        res = BatchResult(cleaned=cleaned, det=pend.det,
                          n_kept=pend.n_real, wid=pend.wid,
                          labels=pend.extra, src_bytes=pend.src_bytes,
                          timings=pend.timings)
        _record_batch(self.name, res)
        return res

    def _finish(self, det: PipelineOutput, wid=None, extra=None,
                src_bytes=0, timings=None):
        pend = self._start_tail(det, wid, extra, src_bytes, timings)
        with obs_tracing.timed("emit", pend.timings, "emit_s", wid=wid):
            cleaned = self._readback(pend)
        return self._emit(pend, cleaned)

    def __call__(self, audio) -> BatchResult:
        x = _to_device(audio, self.rules)
        h2d = 0 if isinstance(audio, jax.Array) else int(x.nbytes)
        return self._finish(self.detect(x), src_bytes=int(x.nbytes),
                            timings={"h2d_bytes": h2d})


# name prefix of AsyncPlan's readback thread
READER_THREAD = "repro-readback"


class AsyncPlan(TwoPhasePlan):
    """Depth-K asynchronous streaming executor: a bounded window of `depth`
    detection batches dispatched ahead, each keep mask prefetched to host
    the moment its detect is enqueued (double-buffered mask readback), the
    tail gathering survivors device-side, and one finished tail held back
    so its cleaned rows stream host-ward while the next batch computes
    (double-buffered emission). While input flows, each cleaned readback
    past that hold-back runs on one reader thread (FIFO), so the loop sends
    the next batch instead of waiting on the copy back; it blocks only
    when a further tail is outstanding. Defaults to power-of-two survivor
    buckets and, on non-CPU backends, donated detect/tail buffers. Emission
    is strictly input order; `last_timings` keeps the per-batch records of
    the most recent run()."""
    name = "async"

    def __init__(self, graph, rules=NULL_RULES, pad_multiple=1, depth=2,
                 bucket="pow2", donate=None, emit_buffer=1,
                 fuse_tail=None):
        super().__init__(graph, rules, pad_multiple, bucket=bucket,
                         donate=donate, fuse_tail=fuse_tail)
        self.depth = max(1, int(depth))
        # dispatched tails retained before emission: 1 double-buffers the
        # cleaned readback behind the next batch (+1 batch of emission
        # latency and one extra resident batch, +1 more while the reader
        # works); 0 reads each result back inline the moment its tail is
        # dispatched (the historical streaming schedule, never the reader)
        self.emit_buffer = max(0, int(emit_buffer))
        # bounded ring: the registry holds the aggregate (plan_stage_seconds
        # et al. via _record_batch); this keeps only recent history
        self.last_timings = collections.deque(maxlen=TIMINGS_CAP)

    def run(self, batches):
        self.last_timings = collections.deque(maxlen=TIMINGS_CAP)
        dets = collections.deque()       # detect window (<= depth)
        tails = collections.deque()      # [pending tail, readback future]
        reader = None                    # started at the first hand-off

        def start_oldest_tail():
            tails.append([self._start_tail(*dets.popleft()), None])

        def take_oldest():
            """The oldest tail as a result: its readback's future, or the
            readback itself when it was never handed off. `emit_s` is the
            loop's own wait; `emit_ready` says the rows were on the host
            already."""
            pend, fut = tails.popleft()
            t = pend.timings
            with obs_tracing.timed("emit", t, "emit_s", wid=pend.wid):
                ready = fut is not None and fut.done()
                cleaned = self._readback(pend) if fut is None \
                    else fut.result()
            if pend.out is not None:
                t["emit_ready"] = float(ready)
            self.last_timings.append(t)
            return self._emit(pend, cleaned)

        def settle(feeding):
            """Yield what is due. While input flows (or once the reader
            runs), the tails past the hold-back go to the reader, ready ones
            are yielded, and the loop blocks only past one more tail; else
            the oldest past the hold-back is read back inline."""
            nonlocal reader
            hold = self.emit_buffer
            if hold and len(tails) > hold and (feeding or reader is not None):
                if reader is None:
                    reader = ThreadPoolExecutor(
                        1, thread_name_prefix=READER_THREAD)
                for e in itertools.islice(tails, len(tails) - hold):
                    if e[1] is None:
                        e[1] = reader.submit(self._readback, e[0])
                hold += 1
            while len(tails) > hold or \
                    (tails and tails[0][1] is not None and tails[0][1].done()):
                yield take_oldest()

        try:
            for wid, chunks, extra in _iter_batches(batches):
                timings = {"in_flight": len(dets) + 1}
                with obs_tracing.timed("detect_dispatch", timings,
                                       "dispatch_s", wid=wid):
                    owned = not isinstance(chunks, jax.Array)
                    with obs_tracing.span("h2d_put", wid=wid):
                        x = _to_device(chunks, self.rules)
                    with obs_tracing.span("detect_enqueue", wid=wid):
                        det = self._detect_donated(x) \
                            if owned and self.donate \
                            else self.detect(x)       # async dispatch
                        if hasattr(det.keep, "copy_to_host_async"):
                            det.keep.copy_to_host_async()  # prefetch mask
                timings["h2d_bytes"] = int(x.nbytes) if owned else 0
                dets.append((det, wid, extra, int(x.nbytes), timings))
                if len(dets) > self.depth:
                    start_oldest_tail()
                yield from settle(feeding=True)
            while dets:
                start_oldest_tail()
                yield from settle(feeding=False)
            while tails:
                yield take_oldest()
        finally:
            if reader is not None:       # early stop or error: no thread
                reader.shutdown(cancel_futures=True)   # left behind
            self._close_shard_pool()     # after the reader: it submits there


class StreamingPlan(AsyncPlan):
    """Two-phase with one batch of dispatch-ahead: detection of batch k+1
    is already in the device queue while the host does batch k's mask
    readback, compaction, tail dispatch AND emission — the historical
    schedule, preserved exactly: depth 1, linear tail padding, no
    donation, no emission hold-back (`emit_buffer=0`, so each result is
    yielded the moment its tail is dispatched, one batch earlier than
    `async`'s double-buffered emission). `async` is this plan with the
    dials turned up."""
    name = "streaming"

    def __init__(self, graph, rules=NULL_RULES, pad_multiple=1, depth=1,
                 bucket="linear", donate=False, emit_buffer=0,
                 fuse_tail=None):
        super().__init__(graph, rules, pad_multiple, depth=depth,
                         bucket=bucket, donate=donate,
                         emit_buffer=emit_buffer, fuse_tail=fuse_tail)


class FleetControl:
    """Live handle on an elastic proc fleet, published as `plan.fleet`
    while `ShardedPlan._run_proc` is running (and left in place afterwards
    for post-run inspection of the service's counters).

    This is the membership write-side the chaos harness and benches
    drive: spawn a late joiner, drain a worker out gracefully, SIGKILL
    one, or SIGSTOP-stall one. Everything routes through the same
    transport/service the original fleet uses — a late joiner is just
    `spawn_worker` + `hello` at a later time."""

    def __init__(self, plan, service, transport, handles):
        self.plan = plan
        self.service = service
        self.transport = transport
        self.handles = handles          # shard -> WorkerHandle (live dict,
                                        # shared with the emit loop)
        self._next = max(handles, default=-1) + 1
        self._lock = threading.Lock()

    def live(self):
        """shard -> WorkerHandle for workers whose process still runs."""
        return {k: h for k, h in list(self.handles.items())
                if h.poll() is None}

    def spawn(self, shard=None):
        """Spawn a late joiner (next free shard id unless given). The new
        worker hellos into the in-progress run, gets the same setup blob,
        and starts leasing from the shared queue. The shard id never
        rides argv: it is RESERVED with the service registry against the
        child's pid, and the worker adopts it when its announce-hello
        lands (so handles/injector stay keyed by shard while workers stay
        address-by-registration)."""
        with self._lock:
            if shard is None:
                shard = self._next
            self._next = max(self._next, int(shard) + 1)
        h = self.transport.spawn_worker(shard,
                                        lease_items=self.plan.lease_items,
                                        poll_s=self.plan.worker_poll_s)
        self.service.reserve(h.pid, int(shard))
        self.handles[int(shard)] = h
        if self.plan.injector is not None:
            self.plan.injector.attach(int(shard), h.pid)
        return h

    def drain(self, shard):
        """Ask one worker to leave gracefully (finish held leases, take
        no more, exit through bye)."""
        return self.service.drain(self.handles[int(shard)].worker)

    def kill(self, shard):
        """SIGKILL one worker (chaos: dies holding whatever it holds)."""
        self.handles[int(shard)].kill()

    def stall(self, shard, seconds=None):
        """SIGSTOP one worker, SIGCONT after `seconds` (chaos: a genuine
        straggler — lease clock ticks, no heartbeats)."""
        self.handles[int(shard)].stall(seconds)

    def resume_all(self):
        """SIGCONT everything still alive (chaos teardown safety)."""
        for h in list(self.handles.values()):
            h.resume()


class ShardedPlan(TwoPhasePlan):
    """Fault-tolerant multi-shard execution over a shared leased WorkQueue,
    served by this plan (the MASTER) to its workers over a pluggable
    transport (`repro.dist`).

    In-proc mode — the historical simulated round loop (one round = every
    live shard pulls up to lease_items), every queue mutation routed
    through the `QueueService` so progress accounting matches proc mode:

      pull    each live shard leases work ids from the SHARED queue and
              dispatches detection under its own rules/mesh; a scripted
              `CrashInjector` can kill a shard mid-pull, leaving its lease
              un-completed (the recovery paths are lease expiry and
              `fail_worker`, exactly the paper's crashed-slave re-send).
      shuffle the `Rebalancer` reads every keep mask back ONCE, packs
              survivors in (shard, item) order, and re-slices them near-
              evenly across the live shards — the plan, not the driver,
              owns the mask readback + re-shard decision.
      finish  per-shard tail (MMSE) jits run on the re-balanced survivor
              batches; cleaned rows are scattered back to their source work
              ids; `queue.complete` gates emission so each work id is
              emitted exactly once even when redelivery raced a straggler.

    Proc mode — real worker processes (`repro.dist.worker`) lease in
    batches over the transport, fetch chunk bytes from the master, run the
    exact TwoPhasePlan detect+tail locally, and stream results back; the
    master completes each returned work id (exactly-once gate), runs the
    Rebalancer on the returned masks per drain (the paper's Figs 14-16
    load ledger), emits in ascending work-id order, SIGKILLs armed by the
    `CrashInjector` land on real pids, and dead processes are reclaimed
    via `fail_worker` (fast path) or lease expiry (slow path).

    `rules` may be a single ShardingRules (shared mesh) or one per shard
    (`distributed.sharding.pool_rules`); compiles land in the shared
    CompileCache keyed by each shard's value fingerprint. (Proc/tcp
    workers compile in their own processes; under `transport="tcp"` they
    may live on other hosts entirely — pair with `data_plane=` so chunk
    bytes move through the shared store, not the master's socket.)
    """
    name = "sharded"
    accepts_rules_pool = True

    def __init__(self, graph, rules=NULL_RULES, pad_multiple=1, shards=2,
                 lease_items=1, injector=None, monitor=None,
                 transport="inproc", worker_poll_s=0.05,
                 stall_timeout_s=300.0, lease_timeout_s=None,
                 telemetry=None, speculate=None, straggler_factor=2.0,
                 straggler_min_history=4, elastic=False, data_plane=None):
        self.shards = max(1, int(shards))
        if isinstance(rules, (list, tuple)):
            if len(rules) != self.shards:
                raise ValueError(
                    f"got {len(rules)} per-shard rules for {self.shards} "
                    f"shards")
            pool = tuple(rules)
        else:
            pool = (rules,) * self.shards
        super().__init__(graph, pool[0], pad_multiple)
        self.rules_pool = pool
        self.lease_items = max(1, int(lease_items))
        self.injector = injector
        self.monitor = monitor
        self.transport = transport
        self.worker_poll_s = float(worker_poll_s)
        self.stall_timeout_s = float(stall_timeout_s)
        # lease deadline for the plan's INTERNAL queue (plain-stream runs;
        # a user-supplied pool brings its own queue). None = transport-
        # sensible default: proc workers pay a first-item jit compile
        # (~minute on CPU), so a healthy compiling worker must not blow
        # its deadline; the simulated loop keeps the WorkQueue default.
        self.lease_timeout_s = lease_timeout_s
        # optional repro.obs.telemetry.TelemetryWriter: handed to the
        # QueueService both transports build, which writes durable
        # per-chunk records master-side at lease/fetch/push/acceptance
        self.telemetry = telemetry
        # speculative re-lease of stragglers (see the module docstring's
        # elasticity section). None = on for proc workers (where a slow
        # process is a real tail-latency event), off for the simulated
        # loop (where "slow" is not observable and duplicate computes
        # only burn the one host).
        self.speculate = speculate
        self.straggler_factor = float(straggler_factor)
        self.straggler_min_history = int(straggler_min_history)
        # elastic=True relaxes the proc master's every-worker-exited
        # fast-fail: with a chaos/autoscale driver on plan.fleet, an
        # empty fleet is a moment, not a verdict — late joiners may be a
        # spawn away (the stall timeout stays as the real backstop)
        self.elastic = bool(elastic)
        # store data plane (ChunkStore | directory | StoreDataPlane):
        # chunk bytes move through a shared store, the control socket
        # carries content keys — proc/tcp transports only.
        self.data_plane = data_plane
        self.fleet = None               # FleetControl while _run_proc lives
        kind = self._transport_kind()   # validate early, not mid-stream
        if data_plane is not None and kind == "inproc":
            raise ValueError("data_plane= rides the proc/tcp worker "
                             "runtime; the in-proc simulated loop never "
                             "serializes chunks")
        self.rebalancer = SCHED.Rebalancer(self.shards, pad_multiple)
        self.redeliveries = 0           # mirrored off the queue after run()
        self.speculations = 0           # mirrored off the queue after run()
        self.speculations_lost = 0      # mirrored off the queue after run()
        self.last_assignment = None     # last round's ShardAssignment
        self.worker_stats = None        # per-worker report of the last run
        self._release = None            # stream-item drop hook (see run())

    def _transport_kind(self) -> str:
        t = self.transport
        if isinstance(t, str):
            if t not in ("inproc", "proc", "tcp"):
                raise ValueError(f"unknown transport {t!r} "
                                 "(expected 'inproc', 'proc' or 'tcp')")
            return t
        kind = getattr(t, "name", None)
        if kind not in ("inproc", "proc", "tcp"):
            raise ValueError(f"transport object {t!r} names no known kind")
        return kind

    # -- per-shard phase dispatch (shared CompileCache, per-shard rules) ----
    def _detect_on(self, shard, audio):
        return _jitted("detect", self.graph, self.rules_pool[shard])(audio)

    def _tail_on(self, shard, batch):
        return _jitted("tail", self.graph, self.rules_pool[shard])(batch)

    # -- single batch: row-split across shards, rebalance, reassemble -------
    def __call__(self, audio) -> BatchResult:
        x = np.asarray(audio, np.float32)
        parts = [(j, p) for j, p in enumerate(np.array_split(x, self.shards))
                 if len(p)]
        dets = [(j, self._detect_on(j, jnp.asarray(p))) for j, p in parts]
        det = _merge_outputs([d for _, d in dets])
        waves_keeps = [(np.asarray(d.wave5), np.asarray(d.keep))
                       for _, d in dets]
        cleaned, asg = self._rebalanced_tail(
            waves_keeps, [k for _, k in waves_keeps],
            live=[j for j, _ in dets])
        self.last_assignment = asg
        res = BatchResult(cleaned=cleaned, det=det,
                          n_kept=int(np.asarray(det.keep).sum()),
                          src_bytes=int(x.nbytes))
        _record_batch(self.name, res)
        return res

    def _rebalanced_tail(self, item_waves_keeps, shard_keeps, live):
        """Rebalanced phase B. item_waves_keeps: [(wave5, keep)] per
        detected item in packed order; shard_keeps: one concatenated keep
        mask per LIVE shard (same packed order) — the assignment is made
        per shard, survivors are packed per item. Returns (cleaned rows in
        packed survivor order, ShardAssignment)."""
        with obs_tracing.span("tail_rebalanced", live=len(live)):
            return self._rebalanced_tail_inner(item_waves_keeps,
                                               shard_keeps, live)

    def _rebalanced_tail_inner(self, item_waves_keeps, shard_keeps, live):
        asg = self.rebalancer.assign(shard_keeps, out_shards=len(live))
        surv = [w[k] for w, k in item_waves_keeps if k.any()]
        if not surv:
            width = (item_waves_keeps[0][0].shape[1]
                     if item_waves_keeps else 0)
            return np.zeros((0, width), np.float32), asg
        packed = np.concatenate(surv)
        cleaned = np.empty_like(packed)
        for slot, batch, n_real in self.rebalancer.split(packed, asg):
            lo = int(asg.bounds[slot])
            out = self._tail_on(live[slot], jnp.asarray(batch))
            cleaned[lo:lo + n_real] = np.asarray(out)[:n_real]
        return cleaned, asg

    # -- streams ------------------------------------------------------------
    def run(self, batches):
        """Accepts a ShardedLoader pool (the multi-host path) or any plain
        batch stream, which is wrapped behind an internal WorkQueue so
        single-stream callers get the same leased, rebalanced execution.
        Sized streams (lists, loaders with __len__) are drawn lazily and
        each item is dropped once its work id completes, so memory stays
        O(in-flight); only unsized generators are materialised up front."""
        if isinstance(batches, (list, tuple)) and batches and \
                all(isinstance(b, ShardedLoader) for b in batches):
            yield from self.run_pool(list(batches))
            return
        n = operator.length_hint(batches, -1)
        it = _iter_batches(batches)
        if n < 0:
            drained = list(it)
            n, it = len(drained), iter(drained)
        store, cursor = {}, [0]
        draw = threading.Lock()    # proc fetches come from handler threads

        def make(i):
            with draw:
                while cursor[0] <= i:
                    wid, chunks, extra = next(it)
                    store[cursor[0]] = (chunks, _StreamMeta(wid, extra))
                    cursor[0] += 1
                return store[i]

        timeout = self.lease_timeout_s
        if timeout is None:
            timeout = 300.0 if self._transport_kind() in ("proc", "tcp") \
                else 60.0
        pool = make_shard_pool(make, n, self.shards,
                               lease_items=self.lease_items,
                               lease_timeout_s=timeout)
        self._release = store.pop
        try:
            yield from self.run_pool(pool)
        finally:
            self._release = None

    def run_pool(self, pool):
        # shard-ascending order keeps the packed survivor order consistent
        # with the per-shard masks handed to the Rebalancer
        pool = sorted(pool, key=lambda ld: ld.shard)
        queue = pool[0].queue
        assert all(ld.queue is queue for ld in pool), \
            "a shard pool must share one WorkQueue"
        bad = sorted({ld.shard for ld in pool} - set(range(self.shards)))
        if bad:
            raise ValueError(
                f"pool shard ids {bad} out of range for a "
                f"{self.shards}-shard plan")
        if self._transport_kind() in ("proc", "tcp"):
            yield from self._run_proc(pool, queue)
        else:
            yield from self._run_sim(pool, queue)

    def _make_straggler(self, kind):
        """The speculation arm: a StragglerDetector for the QueueService,
        or None. Default (speculate=None) arms it only under proc
        transport — see __init__."""
        on = (kind in ("proc", "tcp")) if self.speculate is None \
            else bool(self.speculate)
        if not on:
            return None
        return StragglerDetector(factor=self.straggler_factor,
                                 min_history=self.straggler_min_history)

    # -- in-proc master: the historical simulated round loop ----------------
    def _run_sim(self, pool, queue):
        service = QueueService(queue, monitor=self.monitor,
                               telemetry=self.telemetry,
                               straggler=self._make_straggler("inproc"))
        # every queue mutation flows through the service (pure delegation
        # under the queue's own lock, so behavior is bit-for-bit the old
        # direct path) and the per-worker ledger accrues as in proc mode
        for ld in pool:
            ld.queue = service
        try:
            stalls = 0
            while not service.finished:
                round_work = []      # (shard, wid, det, extra, nbytes)
                for ld in pool:
                    if not self._alive(ld.shard):
                        continue
                    # one beat per live shard per round (note_beat also
                    # forwards to the attached HeartbeatMonitor) — the
                    # historical liveness cadence, through the service
                    service.note_beat(ld.worker)
                    for wid, item in ld.pull():
                        if self.injector is not None and \
                                not self.injector.on_pull(ld.shard):
                            break    # died holding this lease
                        chunks, extra = item if isinstance(item, tuple) \
                            else (item, None)
                        x = jnp.asarray(chunks)
                        det = self._detect_on(ld.shard, x)  # async dispatch
                        round_work.append((ld.shard, wid, det, extra,
                                           int(x.nbytes)))
                if round_work:
                    stalls = 0
                    yield from self._finish_round(service, round_work)
                    continue
                if self._reclaim(service, pool) or service.finished:
                    continue
                deadline = service.next_deadline()
                stalls += 1
                if deadline is not None and stalls <= 8 and \
                        any(self._alive(ld.shard) for ld in pool):
                    # a lease nothing declared dead is still ticking (a
                    # worker outside this pool, or an undetected death):
                    # wait out the deadline so the next pull reaps and
                    # redelivers it. Only wall clocks advance while we
                    # sleep; injected clocks (SettableClock etc.) re-poll
                    # and hit the stall cap fast.
                    if queue.clock in (time.monotonic, time.time):
                        time.sleep(max(0.0, min(deadline - queue.clock(),
                                                queue.lease_timeout_s))
                                   + 1e-3)
                    continue
                raise RuntimeError(
                    "sharded plan stalled: work is leased but no live "
                    f"shard can make progress (progress "
                    f"{service.progress()})")
        finally:
            for ld in pool:
                ld.queue = queue
        self.redeliveries = queue.redeliveries
        self.speculations = queue.speculations
        self.speculations_lost = queue.speculations_lost
        self.worker_stats = service.worker_report()

    # -- proc master: real worker processes over the transport --------------
    def _proc_setup(self):
        """The picklable blob workers rebuild their jits from — value
        identity only (config, stage names, pad/bucket, backend mode), the
        same facts the CompileCache keys on."""
        return {"cfg": self.graph.cfg, "stages": list(self.graph.names),
                "source_channels": self.graph.source_geom.channels,
                "pad_multiple": self.pad_multiple, "bucket": self.bucket,
                "backend_mode": backend.get_mode()}

    def _run_proc(self, pool, queue):
        refuse_worker_processes(
            f"ShardedPlan(transport={self._transport_kind()!r})")
        make_item = pool[0].make_item
        extras = {}                 # wid -> labels/_StreamMeta, master-side

        def fetch(wid):
            """Data plane: materialise the batch on the master, ship ONLY
            the chunk bytes — labels stay here for emission. A fetch whose
            redelivered lease lost the race to a straggler's completion
            gets None (the item may already be emitted AND released from
            the stream buffer): the worker skips it, nothing recomputes."""
            if queue.is_done(wid):
                return None
            try:
                item = make_item(wid)
            except KeyError:
                # completed + released between the is_done check and the
                # buffer read — same race, same answer
                if queue.is_done(wid):
                    return None
                raise
            chunks, extra = item if isinstance(item, tuple) \
                else (item, None)
            extras[wid] = extra
            return np.asarray(chunks, np.float32)

        dp = self.data_plane
        if dp is not None and not isinstance(dp, StoreDataPlane):
            # share the CompileCache/CachedPlan value identity so raw
            # entries dedup across runs of the same graph + backend
            dp = StoreDataPlane(dp, graph_fingerprint=self.graph.fingerprint,
                                backend_mode=backend.get_mode())
        service = QueueService(queue, fetch_item=fetch,
                               setup=self._proc_setup(),
                               monitor=self.monitor,
                               telemetry=self.telemetry,
                               straggler=self._make_straggler("proc"),
                               data_plane=dp)
        if not isinstance(self.transport, str):
            tp = self.transport
        else:
            tp = TcpTransport() if self.transport == "tcp" \
                else ProcTransport()
        handles = {}
        if self.injector is not None:
            def on_grant(worker, wid):
                # the real-process CrashInjector trigger: a doomed shard
                # is SIGKILLed the moment its fatal lease is granted, so
                # it dies HOLDING the lease (attach() below arms the pid)
                shard = service.workers[worker].shard
                self.injector.on_pull(shard)
            service.on_grant = on_grant
        snap = queue.state()
        order = [i for i in range(snap["n_items"])
                 if i not in set(snap["done"])]
        try:
            tp.serve(service)
            # the fleet handle is published BEFORE the initial spawns so
            # a chaos/autoscale driver watching plan.fleet sees the same
            # membership the emit loop does; initial workers and late
            # joiners go through the identical spawn path
            self.fleet = FleetControl(self, service, tp, handles)
            for k in range(self.shards):
                self.fleet.spawn(k)
            yield from self._proc_emit_loop(service, queue, handles,
                                            extras, order)
            # the queue is drained: give workers a moment to observe
            # `finished` and sign off (bye carries their idle/busy split)
            deadline = time.monotonic() + 5.0
            for h in list(handles.values()):
                try:
                    h.proc.wait(max(0.0, deadline - time.monotonic()))
                except Exception:
                    pass
        finally:
            if self.fleet is not None:
                self.fleet.resume_all()   # never TERM a SIGSTOPped worker
            for h in list(handles.values()):
                h.shutdown()
            tp.close()
        self.redeliveries = queue.redeliveries
        self.speculations = queue.speculations
        self.speculations_lost = queue.speculations_lost
        self.worker_stats = service.worker_report()

    def _proc_emit_loop(self, service, queue, handles, extras, order):
        """Drain worker results, gate on completion (exactly-once), emit
        in ascending work-id order (== the crash-free in-proc order, so
        transports are emission-order-identical), and reclaim dead worker
        processes fast via fail_worker."""
        buffered = {}
        emit_i = 0
        reclaimed = set()
        last_progress = time.monotonic()
        while emit_i < len(order):
            drained = service.pop_results()
            if drained:
                last_progress = time.monotonic()
                # store data plane: pushes are key refs — materialize the
                # payloads here, master-side, off the RPC handler threads
                # (losing incarnations cost one redundant store read)
                drained = [(w, wid, service.resolve_result(p))
                           for w, wid, p in drained]
                self._note_assignment(service, drained)
            for worker, wid, payload in drained:
                # the winner's name rides into complete() so a lost
                # speculation race attributes the OTHER incarnation
                if not queue.complete([wid], worker=worker):
                    continue        # redelivery raced a straggler
                det, f = unpack_result(payload)
                # accepted == counted; acceptance is ALSO the durable
                # telemetry point (note_done writes the per-chunk record
                # master-side, so it survives a SIGKILLed worker)
                service.note_done(worker, wid=wid, survivors=f["n_kept"],
                                  bytes_out=f["cleaned"].nbytes)
                obs_tracing.instant("accept", wid=wid, worker=worker)
                buffered[wid] = (det, f)
            progressed = bool(drained)
            while emit_i < len(order) and order[emit_i] in buffered:
                wid = order[emit_i]
                emit_i += 1
                det, f = buffered.pop(wid)
                if self._release is not None:
                    self._release(wid, None)
                extra = extras.pop(wid, None)
                orig_wid, labels = (extra.wid, extra.labels) \
                    if isinstance(extra, _StreamMeta) else (wid, extra)
                # emission gating made visible: the gap between a chunk's
                # "accept" instant and this one is time spent buffered
                # behind a straggler (ascending-wid emission order)
                obs_tracing.instant("emit_gated", wid=wid,
                                    buffered=len(buffered))
                res = BatchResult(cleaned=f["cleaned"], det=det,
                                  n_kept=f["n_kept"], wid=orig_wid,
                                  labels=labels, src_bytes=f["src_bytes"])
                _record_batch(self.name, res)
                yield res
            if emit_i >= len(order) or progressed:
                continue
            # no progress this tick: look for dead workers to reclaim.
            # handles is a LIVE dict (late joiners appear mid-iteration
            # via plan.fleet.spawn) — snapshot it. A worker that exited
            # in state draining/departed left gracefully holding nothing:
            # nothing to reclaim, and it must not be marked dead.
            for k, h in list(handles.items()):
                if k in reclaimed or h.poll() is None or queue.finished:
                    continue
                reclaimed.add(k)
                st = service.workers.get(h.worker)
                if st is not None and st.state in ("draining", "departed"):
                    continue
                service.fail_worker(h.worker)
            if self.monitor is not None:
                for w in sorted(set(self.monitor.dead())):
                    service.fail_worker(w)
                    # reclaimed once is reclaimed: drop the dead worker
                    # from liveness tracking so this loop does not re-fail
                    # it every idle tick
                    self.monitor.forget(w)
            if not self.elastic \
                    and all(h.poll() is not None for h in handles.values()) \
                    and not queue.finished:
                raise RuntimeError(
                    "sharded plan stalled: every worker process exited "
                    f"with work outstanding (progress {queue.progress()})")
            if time.monotonic() - last_progress > self.stall_timeout_s:
                raise RuntimeError(
                    f"sharded plan stalled: no worker progress for "
                    f"{self.stall_timeout_s:.0f}s "
                    f"(progress {queue.progress()})")
            time.sleep(0.01)

    def _note_assignment(self, service, drained):
        """The paper's Figs 14-16 ledger under proc mode: run the
        Rebalancer on the masks this drain returned, grouped per source
        shard. No data moves — workers already denoised their own leases —
        but the would-be re-shard (loads before/after, moved count) is the
        measurement the driver reports."""
        by_shard = {}
        for worker, wid, payload in drained:
            st = service.workers.get(worker)
            shard = st.shard if st is not None else -1
            by_shard.setdefault(shard, []).append(
                np.asarray(payload["keep"]))
        keeps = [np.concatenate(v) for _, v in sorted(by_shard.items())]
        if keeps:
            self.last_assignment = self.rebalancer.assign(
                keeps, out_shards=len(keeps))

    def _alive(self, shard):
        return self.injector is None or self.injector.alive(shard)

    def _reclaim(self, queue, pool):
        """All pending work is held by dead shards: return their leases
        (the heartbeat/injector 'said dead' fast path; a slower deployment
        without either still recovers via lease-deadline expiry on the next
        pull). True if any work came back."""
        dead_workers = {ld.worker for ld in pool if not self._alive(ld.shard)}
        if self.monitor is not None:
            dead_workers |= set(self.monitor.dead())
        got = 0
        for w in sorted(dead_workers):
            got += len(queue.fail_worker(w))
        return got > 0

    def _finish_round(self, service, round_work):
        """Rebalanced phase B for one round, then exactly-once emission in
        work-id completion order."""
        live = sorted({s for s, *_ in round_work})
        item_wk = [(np.asarray(d.wave5), np.asarray(d.keep))
                   for _, _, d, _, _ in round_work]
        # packed per (shard, item) order == round_work order (pool order),
        # so the per-shard masks are contiguous slices of it
        shard_keeps = [np.concatenate(
            [k for (s, *_), (_, k) in zip(round_work, item_wk) if s == s2])
            for s2 in live]
        cleaned_all, asg = self._rebalanced_tail(item_wk, shard_keeps, live)
        self.last_assignment = asg
        offs = np.concatenate(
            [[0], np.cumsum([k.sum() for _, k in item_wk])]).astype(int)
        for i, (shard, wid, det, extra, nbytes) in enumerate(round_work):
            if not service.complete([wid]):
                continue             # redelivery raced a straggler: emitted once
            cleaned = cleaned_all[offs[i]:offs[i + 1]]
            service.note_done(f"shard{shard}", wid=wid,
                              survivors=int(offs[i + 1] - offs[i]),
                              bytes_out=cleaned.nbytes)
            if self._release is not None:
                self._release(wid, None)     # drop the buffered stream item
            orig_wid, labels = (extra.wid, extra.labels) \
                if isinstance(extra, _StreamMeta) else (wid, extra)
            res = BatchResult(
                cleaned=cleaned, det=det,
                n_kept=int(offs[i + 1] - offs[i]), wid=orig_wid,
                labels=labels, src_bytes=nbytes)
            _record_batch(self.name, res)
            yield res


class _SizedIter:
    """One-shot iterable with a length hint: lets CachedPlan hand its miss
    stream to a sharded inner lazily (ShardedPlan sizes its queue from the
    hint and draws items as leases demand) without pinning every raw batch
    in a list."""

    def __init__(self, it, n):
        self._it, self._n = iter(it), n

    def __iter__(self):
        return self._it

    def __length_hint__(self):
        return self._n


class CachedPlan(ExecutionPlan):
    """Content-addressed caching + resumability around any inner plan.

    Execution per stream: every batch is keyed by content hash of (raw
    chunk bytes, graph fingerprint, kernel backend mode) and looked up in
    the `ChunkStore` BEFORE any dispatch; only misses flow through the
    inner plan (one sub-stream, so a sharded inner keeps its leased-queue
    batching); cached survivors merge back in stream order; fresh results
    are written to the store as the inner plan emits them. The cache key
    deliberately omits sharding rules — sharding moves work, never values
    (plan equivalence is bit-exact on masks), so runs under different
    shard counts share entries.

    Resumability: with a `RunJournal`, the plan snapshots its emission
    queue after every yielded result; constructing with `resume=True`
    restores that snapshot and skips exactly the work the dead process
    already emitted — each chunk id is emitted once across the kill.
    Results the dead run computed but never emitted come back as store
    hits, so the resumed run pays recomputation only for truly in-flight
    work.

    `store=None` (the default) degrades to a transparent pass-through, so
    'cached' is always safe to select. Cached `det` records carry masks and
    stats but a zero-filled `wave5` — the pre-denoise waveform is an
    intermediate no downstream consumer reads, and persisting it would
    dwarf the survivors it exists to produce.
    """
    name = "cached"
    accepts_rules_pool = True

    def __init__(self, graph, rules=NULL_RULES, pad_multiple=1,
                 inner="two_phase", store=None, journal=None, resume=False,
                 **inner_kwargs):
        inner_cls = PLANS[inner] if isinstance(inner, str) else inner
        if isinstance(rules, (list, tuple)) and not (
                isinstance(inner_cls, type)
                and getattr(inner_cls, "accepts_rules_pool", False)):
            raise ValueError(
                "a per-shard rules list is only valid with the sharded "
                f"plan as inner, not {getattr(inner_cls, 'name', inner_cls)!r}")
        facade_rules = rules[0] \
            if isinstance(rules, (list, tuple)) and rules else rules
        super().__init__(graph, facade_rules, pad_multiple)
        self.inner = inner_cls(graph, rules, pad_multiple, **inner_kwargs)
        if isinstance(store, (str, os.PathLike)):
            # a cache should self-heal: a bit-rotted entry is evicted and
            # recomputed, not fatal on every future run at the same batch.
            # Pass a ChunkStore instance for archival strictness.
            store = ChunkStore(store, evict_corrupt=True)
        self.store = store
        if journal is True:
            if store is None:
                raise ValueError(
                    "journal=True derives the journal path from the store "
                    "directory — pass a store, or an explicit journal")
            journal = os.path.join(store.directory, "journal")
        if isinstance(journal, (str, os.PathLike)):
            journal = RunJournal(journal)
        self.journal = journal
        self.resume = bool(resume)
        if self.resume and self.journal is None:
            raise ValueError("resume=True needs a journal")

    @property
    def stats(self):
        """The store's hit/miss/bytes accounting (None when uncached)."""
        return self.store.stats if self.store is not None else None

    # -- BatchResult <-> store entry ----------------------------------------
    def _key(self, chunks_np):
        return content_key(chunks_np, self.graph.fingerprint,
                           backend.get_mode())

    # one codec for "masks + stats + cleaned, wave5 reduced to its width":
    # repro.dist's pack_result/unpack_result — the store entry and the
    # worker result payload are the SAME shape (ChunkStore.put_payload
    # derives the array/meta split by type, never by a key list that
    # could drift from the codec), which is also what lets the dist
    # store data plane push worker results straight into a ChunkStore

    def _result(self, arrays, meta, wid, extra) -> BatchResult:
        det, f = unpack_result({**arrays, **meta})
        res = BatchResult(cleaned=f["cleaned"], det=det,
                          n_kept=f["n_kept"], wid=wid, labels=extra,
                          src_bytes=f["src_bytes"])
        # store hits bypass the inner plan, so they are counted here —
        # misses are counted at the inner plan's own emission point
        _record_batch(self.name, res)
        return res

    # -- single batch (the warm-cache serving path) -------------------------
    def __call__(self, audio) -> BatchResult:
        if self.store is None:
            return self.inner(audio)
        x = np.asarray(audio, np.float32)
        key = self._key(x)
        hit = self.store.get(key, src_bytes=x.nbytes)
        if hit is not None:
            return self._result(*hit, wid=None, extra=None)
        res = self.inner(x)
        self.store.put_payload(key, pack_result(res))
        return res

    # -- streams ------------------------------------------------------------
    def run(self, batches):
        """Emits BatchResults in STREAM order (cached survivors merged back
        where they belong). Emission follows ShardedPlan's completion-gated
        convention: the queue completes and the journal records IMMEDIATELY
        BEFORE each yield, so at the plan boundary every chunk is emitted
        exactly once across a kill + resume — an abandoned generator resumes
        from precisely the next unemitted item. (The chunk handed over at
        the instant of a hard process kill is the consumer's to recover, as
        with any exactly-once hand-off.)

        Memory: like ShardedPlan, sized streams (lists, loaders with
        __len__) are drawn lazily — hits in the stream-order prefix are
        emitted DURING the probe, raw chunks are retained only for misses,
        and each miss's bytes are released as the inner plan draws them —
        while unsized generators are materialised up front to learn the
        stream length (the journal and resume guard need it)."""
        if isinstance(batches, (list, tuple)) and batches and \
                all(isinstance(b, ShardedLoader) for b in batches):
            raise ValueError(
                "CachedPlan must see chunk content before dispatch — feed "
                "it the plain batch stream; a sharded inner builds its "
                "leased shard pool internally from the misses")

        n = operator.length_hint(batches, -1)
        it = _iter_batches(batches)
        if n < 0:
            drained = list(it)
            n, it = len(drained), iter(drained)

        done, want_key0 = set(), None
        if self.journal is not None and self.resume:
            rec_meta = self.journal.load()
            if rec_meta is not None:
                rec_n = int(rec_meta["queue"]["n_items"])
                if rec_n != n:
                    raise ValueError(
                        f"journal records a {rec_n}-item stream; the "
                        f"resume stream has {n} items — refusing to mix "
                        f"runs")
                done = set(rec_meta["queue"]["done"])
                want_key0 = rec_meta.get("stream_key0")
        queue = WorkQueue.from_state({"n_items": n, "done": sorted(done)})
        order = [p for p in range(n) if p not in done]
        emit_idx = 0
        key0 = None                       # stream identity: first batch key
        results: dict[int, BatchResult] = {}
        misses = []                       # [pos, key, wid, chunks, extra]

        def emit_ready():
            """Completion-gated hand-off of the ready stream-order prefix."""
            nonlocal emit_idx
            while emit_idx < len(order) and order[emit_idx] in results:
                pos = order[emit_idx]
                emit_idx += 1
                queue.complete([pos])
                if self.journal is not None:
                    self.journal.record(queue, meta={"stream_key0": key0})
                yield results.pop(pos)

        for pos, (wid, chunks, extra) in enumerate(it):
            probe = pos not in done and self.store is not None
            if probe or (pos == 0 and self.journal is not None):
                x = np.asarray(chunks, np.float32)
                key = self._key(x)
                if pos == 0:
                    key0 = key
                    if want_key0 is not None and want_key0 != key0:
                        raise ValueError(
                            "journal records a stream with different "
                            "content (first-batch key mismatch) — "
                            "refusing to mix runs")
            if pos in done:
                continue                  # the killed run already emitted it
            if not probe:                 # uncached: everything is a miss
                misses.append([pos, None, wid, chunks, extra])
                continue
            hit = self.store.get(key, src_bytes=x.nbytes)
            if hit is not None:
                results[pos] = self._result(*hit, wid=wid, extra=extra)
                yield from emit_ready()   # warm prefixes flow immediately
            else:
                misses.append([pos, key, wid, x, extra])

        if misses:
            def miss_stream():
                for i, m in enumerate(misses):
                    item = (i, (m[3], m[4]))
                    m[3] = None           # the inner plan owns the bytes now
                    yield item

            for res in self.inner.run(_SizedIter(miss_stream(),
                                                 len(misses))):
                pos, key, wid, _, extra = misses[res.wid]
                if self.store is not None:
                    self.store.put_payload(key, pack_result(res))
                results[pos] = replace(res, wid=wid, labels=extra)
                yield from emit_ready()
        yield from emit_ready()
        assert emit_idx == len(order), "inner plan dropped work ids"


def _merge_outputs(outs):
    """Concatenate per-shard PipelineOutputs (row order preserved) with
    chunk-count-weighted stats — the batch looks as if one shard detected
    it."""
    if len(outs) == 1:
        return outs[0]
    cat = lambda f: np.concatenate([np.asarray(getattr(o, f)) for o in outs])
    ws = np.array([float(o.stats["n_chunks5"]) for o in outs])
    stats = {"n_chunks5": int(ws.sum())}
    for k in outs[0].stats:
        if k != "n_chunks5":
            vals = np.array([float(o.stats[k]) for o in outs])
            stats[k] = float((vals * ws).sum() / ws.sum())
    return PipelineOutput(wave5=cat("wave5"), keep=cat("keep"),
                          rain=cat("rain"), silence=cat("silence"),
                          cicada15=cat("cicada15"), stats=stats)


PLANS = {p.name: p for p in (FusedPlan, TwoPhasePlan, StreamingPlan,
                             AsyncPlan, ShardedPlan, CachedPlan)}


class Preprocessor:
    """The single facade every entry point uses.

        pre = Preprocessor(SERF_AUDIO, rules, plan="streaming",
                           pad_multiple=len(jax.devices()))
        for res in pre.run(loader):        # loader: AudioChunkLoader items
            use(res.cleaned, res.det.stats, res.n_kept)

    `plan` is a name from `PLANS` or an ExecutionPlan subclass; `stages`
    overrides the config-declared stage list for ablations. Extra keyword
    arguments are forwarded to the plan (e.g. `shards=4`, `injector=...`
    for the sharded plan).
    """

    def __init__(self, cfg, rules=NULL_RULES, plan="two_phase",
                 pad_multiple=1, stages=None, source_channels=2,
                 **plan_kwargs):
        self.cfg = cfg
        # facade-level detect()/phase_fn() use one rules object even when
        # the plan gets a per-shard list (sharded multi-host pools)
        self.rules = rules[0] if isinstance(rules, (list, tuple)) and rules \
            else rules
        self.graph = PipelineGraph(cfg, stages, source_channels)
        plan_cls = PLANS[plan] if isinstance(plan, str) else plan
        if isinstance(rules, (list, tuple)) and not (
                isinstance(plan_cls, type)
                and getattr(plan_cls, "accepts_rules_pool", False)):
            raise ValueError(
                "a per-shard rules list is only valid with the sharded "
                "plan (or a cached wrapper around it), not "
                f"{getattr(plan_cls, 'name', plan_cls)!r}")
        self.plan = plan_cls(self.graph, rules, pad_multiple, **plan_kwargs)

    def __call__(self, audio) -> BatchResult:
        """One batch of (B, C, S_long_src) long chunks -> BatchResult."""
        return self.plan(audio)

    def run(self, batches):
        """Iterate BatchResults over a batch stream / AudioChunkLoader."""
        return self.plan.run(batches)

    def detect(self, audio) -> PipelineOutput:
        """The phase-A stages only (shared compile cache; plan-independent).
        For a graph without a removal point this is the whole chain — see
        PipelineGraph.detection."""
        return _jitted("detect", self.graph, self.rules)(jnp.asarray(audio))

    def phase_fn(self, kind):
        """Un-jitted phase callable ('fused' | 'detect' | 'mmse'/'tail')
        for jax.jit(...).lower-style analysis (see launch/dryrun.py)."""
        return _phase_fn(kind, self.graph, self.rules)
