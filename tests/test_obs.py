"""repro.obs: metrics registry units, tracer + Chrome-trace schema,
durable telemetry (crash-safe reader, exactly-once acceptance records),
InProc vs Proc telemetry parity on the sharded plan, SIGKILL redelivery
attribution across worker incarnations, the ring caps that replaced the
unbounded in-memory ledgers, StoreStats mirroring, and the `metrics` RPC.
"""
import dataclasses
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import TIMINGS_CAP, Preprocessor
from repro.data.synthetic import generate_labelled
from repro.obs import metrics as obs_metrics
from repro.obs import telemetry as obs_telemetry
from repro.obs import tracing as obs_tracing
from repro.obs.metrics import MetricsRegistry, NullRegistry, NULL_INSTRUMENT
from repro.obs.tracing import NULL_TRACER, Tracer, validate_chrome_trace
from repro.serve.batcher import BATCH_LOG_CAP, ContinuousBatcher
from repro.store.chunk_store import StoreStats


@pytest.fixture
def fresh_registry():
    """Swap in an isolated registry; restore the global one afterwards."""
    prev = obs_metrics.get_registry()
    reg = MetricsRegistry()
    obs_metrics.set_registry(reg)
    yield reg
    obs_metrics.set_registry(prev)


# ----------------------------------------------------------- metrics

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)                      # counters are monotonic
    g = reg.gauge("g")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 7.0):
        h.observe(v)
    snap = reg.snapshot()
    (series,) = snap["h_seconds"]["series"]
    assert series["count"] == 3 and series["sum"] == pytest.approx(7.55)
    assert series["buckets"]["0.1"] == 1        # cumulative
    assert series["buckets"]["1.0"] == 2
    assert series["buckets"]["+Inf"] == 3


def test_labeled_series_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("rpc_total", labels=("method",))
    c.labels(method="lease").inc(2)
    c.labels(method="fetch").inc()
    snap = reg.snapshot()["rpc_total"]
    got = {tuple(s["labels"].items()): s["value"] for s in snap["series"]}
    assert got == {(("method", "lease"),): 2, (("method", "fetch"),): 1}
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("rpc_total")


def test_render_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("x_total", "things", ("kind",)).labels(kind="a").inc(2)
    reg.histogram("d_seconds", buckets=(1.0,)).observe(0.5)
    text = reg.render()
    assert '# TYPE x_total counter' in text
    assert 'x_total{kind="a"} 2' in text
    assert 'd_seconds_bucket{le="1.0"} 1' in text
    assert 'd_seconds_count 1' in text


def test_disabled_registry_is_null_and_mutation_gated():
    null = NullRegistry()
    assert null.counter("a") is NULL_INSTRUMENT
    assert null.snapshot() == {}
    reg = MetricsRegistry()
    c = reg.counter("a_total")
    reg.enabled = False                # toggled mid-run: live instruments
    c.inc(100)                         # must stop mutating too
    reg.enabled = True
    assert c.value == 0


def test_module_level_instruments_respect_enabled(fresh_registry):
    obs_metrics.counter("m_total").inc()
    assert obs_metrics.snapshot()["m_total"]["series"][0]["value"] == 1
    fresh_registry.enabled = False
    assert obs_metrics.counter("m_total") is NULL_INSTRUMENT


# ----------------------------------------------------------- tracing

def test_tracer_spans_nest_and_validate():
    t = Tracer()
    t.start_run("run")
    with t.span("outer", wid=1):
        with t.span("inner"):
            t.instant("mark", x=2)
    t.complete("work", start_s=1.0, end_s=2.0)
    t.async_begin("request", 7)
    t.async_end("request", 7)
    t.finish_run()
    data = t.chrome()
    counts = validate_chrome_trace(data)
    assert counts == {"B": 3, "E": 3, "i": 1, "X": 1, "b": 1, "e": 1}
    # every opener after start_run is parented under the run span
    run_span = t.trace_id + ":0"
    for ev in data["traceEvents"]:
        if ev["ph"] in ("B", "X", "i") and ev["name"] != "run":
            assert ev["args"]["parent"] == run_span


def test_trace_propagation_parents_child_events():
    parent = Tracer()
    parent.start_run("run")
    spec = parent.propagate()
    child = Tracer(**spec)             # the worker-process twin
    child.complete("compute", start_s=1.0, end_s=2.0, wid=0)
    parent.add_events(child.drain())
    parent.finish_run()
    evs = parent.chrome()["traceEvents"]
    (compute,) = [e for e in evs if e["name"] == "compute"]
    assert compute["args"]["parent"] == parent.trace_id + ":0"
    assert compute["args"]["trace"] == parent.trace_id
    validate_chrome_trace(evs)
    assert child.drain() == []         # drain pops


# the plan's spans on the profiler's host plane, each child inside its
# parent; the rest stand alone
_PROFILED_SPANS = {"detect_dispatch": None, "h2d_put": "detect_dispatch",
                   "detect_enqueue": "detect_dispatch", "mask_wait": None,
                   "compact": None, "tail_enqueue": None, "emit": None,
                   "tail_wait": "emit", "d2h_wait": "emit"}


def _profiled_stream():
    """Three batches of one 15 s stereo long chunk: bird song, silence
    (removed whole, so it runs no tail), bird song."""
    per = 3                                   # 5 s segments a long chunk
    batches = []
    for seed, probs in ((3, (1, 0, 0, 0)), (4, (0, 0, 0, 1)),
                        (5, (1, 0, 0, 0))):
        audio, _ = generate_labelled(seed, per, segment_s=5.0,
                                     label_probs=probs)
        batches.append(audio.transpose(1, 0, 2).reshape(1, 2, -1))
    return list(enumerate((x, None) for x in batches))


def test_plan_spans_reach_the_profiler_trace(tmp_path):
    """Under a `jax.profiler` session the async plan's spans land on the
    trace's host plane as `repro.<name>`, nested as the plan opens them,
    each carrying its batch's `wid`; no Chrome tracer is installed."""
    small = dataclasses.replace(cfg, long_split_s=15.0)
    pre = Preprocessor(small, plan="async", depth=2, pad_multiple=1)
    stream = _profiled_stream()
    list(pre.run(stream))                     # compile outside the trace
    assert not obs_tracing.get_tracer().enabled
    with jax.profiler.trace(str(tmp_path)):
        res = list(pre.run(stream))
    assert not obs_tracing.TraceAnnotation.is_enabled()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}                                # (name, wid) -> (start, end)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs_tracing.PREFIX):
                    name = ev.name[len(obs_tracing.PREFIX):]
                    key = (name, {k: v for k, v in ev.stats}["wid"])
                    assert key not in spans
                    spans[key] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    kept = {r.wid for r in res if r.n_kept}
    assert kept == {0, 2}
    for r in res:
        for name, parent in _PROFILED_SPANS.items():
            if name in ("tail_wait", "d2h_wait") and r.wid not in kept:
                assert (name, r.wid) not in spans
                continue
            lo, hi = spans[(name, r.wid)]
            if parent is not None:
                plo, phi = spans[(parent, r.wid)]
                assert plo <= lo <= hi <= phi, (name, parent, r.wid)
    assert {n for n, _ in spans} == set(_PROFILED_SPANS)


def test_timed_span_writes_its_duration():
    t = {}
    with obs_tracing.timed("probe", t, "probe_s", wid=7):
        pass
    assert t["probe_s"] >= 0.0
    tracer = Tracer()
    obs_tracing.set_tracer(tracer)
    try:
        with obs_tracing.timed("probe", t, "again_s", wid=8):
            pass
    finally:
        obs_tracing.set_tracer(None)
    assert t["again_s"] >= 0.0
    names = [(e["ph"], e["name"], e["args"].get("wid"))
             for e in tracer.events]
    assert names == [("B", "probe", 8), ("E", "probe", None)]
    validate_chrome_trace(tracer.chrome())


def test_validate_chrome_trace_rejects_bad_events():
    base = {"ts": 0, "pid": 1, "tid": 1}
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace([{"ph": "B", **base}])        # no name
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace([{"ph": "?", "name": "x", **base}])
    with pytest.raises(ValueError, match="without dur"):
        validate_chrome_trace([{"ph": "X", "name": "x", **base}])
    with pytest.raises(ValueError, match="closes"):
        validate_chrome_trace([{"ph": "B", "name": "a", **base},
                               {"ph": "E", "name": "b", **base}])
    with pytest.raises(ValueError, match="unclosed"):
        validate_chrome_trace([{"ph": "B", "name": "a", **base}])


def test_tracer_caps_events():
    t = Tracer(max_events=3)
    for i in range(5):
        t.instant(f"e{i}")
    assert len(t.events) == 3 and t.dropped == 2


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x"):
        NULL_TRACER.instant("y")
    assert NULL_TRACER.propagate() is None
    assert NULL_TRACER.start_run() is None


# --------------------------------------------------------- telemetry

def test_telemetry_write_read_and_torn_tail(tmp_path):
    d = tmp_path / "t"
    with obs_telemetry.TelemetryWriter(d) as w:
        w.record(event="chunk", status="done", wid=0, worker="a",
                 survivors=3, accept_ts=1.0)
        w.record(event="chunk", status="done", wid=1, worker="b",
                 survivors=2, accept_ts=2.0)
    assert w.records_written == 2
    # a writer SIGKILLed mid-write leaves a torn trailing line: skipped
    with open(w.path, "a") as f:
        f.write('{"event":"chunk","status":"do')
    recs = obs_telemetry.read_records(str(d))
    assert [r["wid"] for r in recs] == [0, 1]
    led = obs_telemetry.worker_ledger(recs)
    assert led["a"]["chunks_done"] == 1 and led["a"]["survivors"] == 3
    assert led["b"]["first_accept_ts"] == 2.0
    chunks = obs_telemetry.chunk_ledger(recs)
    assert chunks[0]["done"] and chunks[0]["survivors"] == 3


def test_telemetry_torn_mid_file_raises(tmp_path):
    p = tmp_path / "x.jsonl"
    p.write_text('{"event":"chunk","wid":0}\n{"torn\n{"event":"chunk"}\n')
    with pytest.raises(ValueError):
        obs_telemetry.read_records(str(p))


def _sharded_stream(n_batches):
    from repro.data.loader import audio_batch_maker
    make = audio_batch_maker(seed=21, batch_long_chunks=1)
    return make, [(w, (make(w)[0], None)) for w in range(n_batches)]


@pytest.mark.parametrize("transport", ["inproc", "proc"])
def test_sharded_telemetry_exactly_once(transport, tmp_path):
    """Both transports must leave exactly ONE master-side 'done' record
    per chunk, attributing a real worker, with acceptance timestamps.
    The (wid, status, survivors) view is transport-invariant — the
    records describe the work, not the wire (timestamps, pids and
    content keys legitimately differ and are excluded)."""
    _, stream = _sharded_stream(2)
    d = tmp_path / transport
    with obs_telemetry.TelemetryWriter(d) as w:
        pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                           transport=transport, telemetry=w)
        results = list(pre.run(stream))
    assert sorted(r.wid for r in results) == [0, 1]
    recs = obs_telemetry.read_records(str(d))
    done = [r for r in recs if r["status"] == "done"]
    assert sorted(r["wid"] for r in done) == [0, 1]
    by_wid = {r["wid"]: r for r in done}
    for r in results:
        rec = by_wid[r.wid]
        assert rec["survivors"] == int(r.n_kept)
        assert rec["worker"].startswith("shard")
        assert rec["accept_ts"] is not None
        assert rec["redelivered"] == 0


def test_proc_sigkill_leaves_redelivery_attribution(tmp_path):
    """A worker SIGKILLed while holding a lease must leave a durable
    'redelivered' record attributing the LOSING incarnation, and the
    eventual 'done' record must carry the redelivery count and the
    surviving worker — both attempts visible in one ledger. The results
    still come out once each, in input order."""
    from repro.data.loader import audio_batch_maker, make_shard_pool
    from repro.ft.failure import CrashInjector

    n_batches = 3
    make = audio_batch_maker(seed=3, batch_long_chunks=2)
    pool = make_shard_pool(make, n_batches, 2, lease_timeout_s=120.0)
    injector = CrashInjector()
    injector.kill(1, after_items=0)    # shard1 dies at its first grant
    d = tmp_path / "t"
    with obs_telemetry.TelemetryWriter(d) as w:
        pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                           transport="proc", injector=injector,
                           telemetry=w)
        results = list(pre.run(pool))
    assert [r.wid for r in results] == list(range(n_batches))
    assert pre.plan.redeliveries >= 1
    assert injector.crashed == frozenset({1})

    recs = obs_telemetry.read_records(str(d))
    done = {r["wid"]: r for r in recs if r["status"] == "done"}
    assert sorted(done) == list(range(n_batches))   # exactly once each
    redel = [r for r in recs if r["status"] == "redelivered"]
    assert redel, "no durable redelivery attribution"
    assert all(r["worker"] == "shard1" for r in redel)
    for r in redel:
        final = done[r["wid"]]
        assert final["redelivered"] >= 1
        assert final["worker"] == "shard0"          # the survivor won it
    led = obs_telemetry.worker_ledger(recs)
    assert led["shard1"]["redelivered_from"] >= 1
    assert led["shard0"]["chunks_done"] == n_batches


def test_driver_over_proc_workers_traces_and_telemetry(tmp_path):
    """The driver (`launch.preprocess`) over 2 real proc workers with
    `--trace` and `--telemetry`: the Chrome trace validates, one run span
    opens in the master, every worker-process event carries the master's
    trace id and (openers and completes) the run span as parent across
    the pickle boundary, and the durable telemetry holds exactly one
    master-side 'done' record per chunk with its accept timestamp."""
    from repro.launch import preprocess as launch_pre

    n_batches = 2                      # --minutes 4 / --batch-long-chunks 2
    trace_path = tmp_path / "trace.json"
    tdir = tmp_path / "telemetry"
    prev_tracer = obs_tracing.get_tracer()
    prev_cache = jax.config.jax_compilation_cache_dir
    try:
        launch_pre.main([
            "--minutes", "4", "--batch-long-chunks", "2",
            "--plan", "sharded", "--transport", "proc", "--shards", "2",
            "--trace", str(trace_path), "--telemetry", str(tdir)])
    finally:
        obs_tracing.set_tracer(prev_tracer)
        jax.config.update("jax_compilation_cache_dir", prev_cache)
    data = json.loads(trace_path.read_text())
    validate_chrome_trace(data)
    events = data["traceEvents"]
    trace_id = data["otherData"]["trace_id"]
    run_span = trace_id + ":0"
    roots = [e for e in events
             if e["name"] == "preprocess_run" and e["ph"] == "B"]
    assert len(roots) == 1
    assert roots[0]["pid"] == os.getpid()
    assert roots[0]["args"]["span"] == run_span
    worker_evs = [e for e in events if e["pid"] != os.getpid()]
    assert worker_evs, "no worker-process events reached the trace"
    assert all(e["args"].get("trace") == trace_id for e in worker_evs)
    # 'E' closers carry no parent by design
    assert all(e["args"].get("parent") == run_span
               for e in worker_evs if e["ph"] != "E")
    assert any(e["name"] == "compute" for e in worker_evs)

    done = [r for r in obs_telemetry.read_records(str(tdir))
            if r.get("status") == "done"]
    assert sorted(r["wid"] for r in done) == list(range(n_batches))
    assert all(r.get("accept_ts") and r.get("worker") for r in done)


# ---------------------------------------------------------- ring caps

def test_batch_log_is_ring_capped():
    b = ContinuousBatcher(plan=lambda x: x, max_batch=1)
    assert b.batch_log.maxlen == BATCH_LOG_CAP
    for i in range(BATCH_LOG_CAP + 10):
        b.batch_log.append({"rids": [i]})
    assert len(b.batch_log) == BATCH_LOG_CAP
    assert b.batch_log[0]["rids"] == [10]           # oldest evicted


def test_async_plan_timings_ring_capped():
    pre = Preprocessor(cfg, plan="async", pad_multiple=1)
    assert pre.plan.last_timings.maxlen == TIMINGS_CAP


# ----------------------------------------------------- store mirroring

def test_store_stats_mirror_into_registry(fresh_registry):
    st = StoreStats(label="lake")
    st.hits += 2
    st.bytes_saved += 1000
    st.misses += 1
    assert (st.hits, st.misses, st.bytes_saved) == (2, 1, 1000)
    assert st.hit_rate == pytest.approx(2 / 3)
    snap = obs_metrics.snapshot()
    assert snap["store_hits_total"]["series"][0] == {
        "labels": {"store": "lake"}, "value": 2}
    assert snap["store_bytes_saved_total"]["series"][0]["value"] == 1000
    # disabled registry: plain attributes still work, nothing mirrored
    fresh_registry.enabled = False
    st.hits += 5
    assert st.hits == 7


def test_chunk_store_labels_stats_by_directory(tmp_path, fresh_registry):
    from repro.store import ChunkStore
    store = ChunkStore(tmp_path / "mystore")
    store.put("k1", {"a": np.zeros(4, np.float32)})
    assert store.get("k1", src_bytes=64) is not None
    snap = obs_metrics.snapshot()
    assert snap["store_hits_total"]["series"][0]["labels"] == {
        "store": "mystore"}
    assert snap["store_writes_total"]["series"][0]["value"] == 1


# ------------------------------------------------------- metrics RPC

def test_metrics_rpc_over_transport(fresh_registry):
    from repro.data.queue import WorkQueue
    from repro.dist.service import QueueService, RPC_METHODS
    from repro.dist.transport import InProcTransport

    assert "metrics" in RPC_METHODS
    q = WorkQueue(2, lease_timeout_s=60.0)
    svc = QueueService(q)
    proxy = InProcTransport().connect(svc)
    proxy.call("lease", "shard0", 1)
    snap = proxy.call("metrics")
    assert snap["dist_lease_calls_total"]["series"][0] == {
        "labels": {"worker": "shard0"}, "value": 1}
    json.dumps(snap)                   # the RPC payload is JSON-safe
    text = proxy.call("metrics", render=True)
    assert 'dist_lease_calls_total{worker="shard0"} 1' in text


def test_redelivery_counter_fires_without_telemetry(fresh_registry):
    from repro.data.queue import SettableClock, WorkQueue
    from repro.dist.service import QueueService

    clock = SettableClock()
    q = WorkQueue(2, lease_timeout_s=10.0, clock=clock)
    QueueService(q)                    # attaches on_redeliver, no writer
    q.lease("w0", 2)
    clock.t = 11.0
    q.lease("w1", 1)                   # reaps w0's expired leases first
    snap = obs_metrics.snapshot()
    series = snap["dist_redeliveries_total"]["series"]
    (s,) = [s for s in series if s["labels"]["worker"] == "w0"]
    assert s["labels"]["reason"] == "expired" and s["value"] == 2
