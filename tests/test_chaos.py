"""Elastic-fleet primitives: straggler detection, speculative duplicate
leases, the membership registry, seeded chaos schedules, graceful drain +
late join through the real worker runtime, pool autoscaling, and the
fleet under fire: a seeded schedule and an injected end-of-stream
straggler over real proc workers."""
import threading
import time

import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import Preprocessor
from repro.data.loader import audio_batch_maker, make_shard_pool
from repro.data.queue import SettableClock, WorkQueue
from repro.dist.service import QueueService
from repro.dist.transport import InProcTransport
from repro.dist.worker import run_worker
from repro.ft.chaos import ACTIONS, ChaosRunner, make_schedule
from repro.ft.failure import StragglerDetector


# ---------------------------------------------------- straggler detector

def test_straggler_detector_min_history_gates():
    """No speculation before the detector has seen enough completions:
    with an empty latency history every in-flight time looks infinite."""
    clock = SettableClock()
    sd = StragglerDetector(factor=2.0, min_history=5, clock=clock)
    sd.start("t")
    clock.t += 100.0
    assert sd.stragglers() == []          # ancient, but history too thin
    for i in range(5):
        sd.start(i)
        clock.t += 1.0
        sd.complete(i)
    assert sd.stragglers() == ["t"]       # history filled: now it fires


def test_straggler_detector_p95_window():
    clock = SettableClock()
    sd = StragglerDetector(factor=2.0, min_history=10, clock=clock)
    for i in range(100):
        sd.start(i)
        clock.t += 1.0
        sd.complete(i)
    assert sd.p95() == 1.0
    sd.start("x")
    clock.t += 1.5
    assert sd.stragglers() == []          # 1.5 <= 2 x p95
    clock.t += 1.0
    assert sd.stragglers() == ["x"]       # 2.5 > 2 x p95


def test_straggler_detector_latency_truncation():
    """The rolling history stays bounded: past 1000 samples it is cut back
    to the newest 500 (long streams must not grow the list forever)."""
    clock = SettableClock()
    sd = StragglerDetector(clock=clock)
    for i in range(1001):
        sd.start(i)
        sd.complete(i)
    assert len(sd._latencies) == 500


def test_straggler_detector_orders_longest_running_first():
    """Speculation re-leases from the front of the list, so the slowest
    item must come first."""
    clock = SettableClock()
    sd = StragglerDetector(factor=1.0, min_history=1, clock=clock)
    sd.start("old")
    clock.t = 5.0
    sd.start("new")
    clock.t = 6.0
    sd.start("quick")
    sd.complete("quick")
    clock.t = 20.0
    assert sd.stragglers() == ["old", "new"]


# ------------------------------------------------ speculative duplicate leases

def test_work_queue_speculate_refusals_and_grant():
    clock = SettableClock()
    q = WorkQueue(3, lease_timeout_s=10.0, clock=clock)
    assert not q.speculate("w2", 0)       # not leased yet
    assert q.lease("w1", 2) == [0, 1]
    assert not q.speculate("w1", 0)       # self-speculation refused
    assert q.speculate("w2", 0)
    assert not q.speculate("w3", 0)       # at most one backup per id
    assert q.speculated() == [0]
    assert q.leases_held("w2") == [0]     # a spec copy counts as held work
    q.complete([1])
    assert not q.speculate("w2", 1)       # done ids are never duplicated
    assert q.speculations == 1


def test_work_queue_speculation_first_completion_wins():
    losses = []
    clock = SettableClock()
    q = WorkQueue(2, lease_timeout_s=10.0, clock=clock)
    q.on_redeliver = lambda wid, w, reason: losses.append((wid, w, reason))
    q.lease("w1", 2)
    assert q.speculate("w2", 0) and q.speculate("w2", 1)
    assert q.complete([0], worker="w1") == [0]      # primary wins wid 0
    assert losses == [(0, "w2", "speculated")]
    assert q.complete([1], worker="w2") == [1]      # backup wins wid 1
    assert losses[-1] == (1, "w1", "speculated")
    assert q.speculations_lost == 2
    assert q.redeliveries == 0            # a lost race is not a lost lease
    assert q.complete([0], worker="w2") == []       # exactly-once holds
    assert q.finished


def test_work_queue_speculation_promoted_on_primary_expiry():
    """When the primary lease expires while a live backup exists, the
    backup is PROMOTED instead of re-queueing the id — the backup is
    already computing it; a third copy would only add load."""
    clock = SettableClock()
    q = WorkQueue(2, lease_timeout_s=10.0, clock=clock)
    q.lease("w1", 2)
    assert q.speculate("w2", 0)
    clock.t = 5.0
    q.heartbeat_extend("w2")              # backup stays fresh (-> 15)
    clock.t = 12.0                        # w1's primaries (10) expire
    assert q.lease("w3", 5) == [1]        # only the spec-less id re-pends
    assert q.leases_held("w2") == [0]     # the backup is primary now
    assert q.speculated() == []
    assert q.redeliveries == 2


def test_work_queue_speculation_promoted_on_fail_worker():
    clock = SettableClock()
    q = WorkQueue(2, lease_timeout_s=10.0, clock=clock)
    q.lease("w1", 2)
    assert q.speculate("w2", 0)
    assert sorted(q.fail_worker("w1")) == [0, 1]
    assert q.lease("w3", 5) == [1]        # wid 0 went to the backup, not pending
    assert q.leases_held("w2") == [0]
    # and a dead worker's own spec copies just evaporate
    q2 = WorkQueue(1, clock=SettableClock())
    q2.lease("w1", 1)
    assert q2.speculate("w2", 0)
    assert q2.fail_worker("w2") == []
    assert q2.speculated() == [] and q2.leases_held("w1") == [0]
    assert q2.redeliveries == 0


def test_work_queue_spec_expiry_evaporates_silently():
    """An expired backup costs nothing: the primary still owns the id,
    nothing re-pends, no redelivery is counted."""
    clock = SettableClock()
    q = WorkQueue(1, lease_timeout_s=10.0, clock=clock)
    q.lease("w1", 1)
    assert q.speculate("w2", 0)
    clock.t = 5.0
    q.heartbeat_extend("w1")              # primary -> 15; backup stays 10
    clock.t = 12.0
    assert q.lease("w3", 1) == []
    assert q.speculated() == []
    assert q.leases_held("w1") == [0]
    assert q.redeliveries == 0 and q.speculations_lost == 0


# ------------------------------------------------------ membership registry

def test_queue_service_membership_registry():
    q = WorkQueue(4, lease_timeout_s=60.0, clock=SettableClock())
    svc = QueueService(q)
    svc.hello("shard0", pid=1, shard=0)
    svc.hello("shard1", pid=2, shard=1)
    e0 = svc.epoch
    assert e0 >= 2                        # each join bumped the epoch
    assert svc.active_workers() == ["shard0", "shard1"]
    svc.hello("shard0", pid=1, shard=0)   # re-hello while active: no churn
    assert svc.epoch == e0
    assert svc.drain("shard1") is True
    assert svc.draining("shard1")
    assert svc.epoch == e0 + 1
    assert svc.lease("shard1", 4) == []   # draining workers take no work
    assert svc.lease("shard0", 1) == [0]
    svc.bye("shard1")
    assert svc.workers["shard1"].state == "departed"
    assert svc.draining("shard1")         # departed still reads as leaving
    svc.hello("shard1", pid=3, shard=1)   # rejoin: a fresh incarnation
    assert svc.workers["shard1"].state == "active"
    assert svc.lease("shard1", 1) == [1]
    svc.fail_worker("shard0")
    assert svc.workers["shard0"].state == "dead"
    assert svc.active_workers() == ["shard1"]
    assert svc.epoch > e0 + 1


def test_queue_service_grants_speculative_lease_to_idle_worker():
    """The wiring end to end: an ACTIVE worker whose normal lease comes
    back empty receives a duplicate of the slowest flagged in-flight id."""
    clock = SettableClock()
    q = WorkQueue(3, lease_timeout_s=60.0, clock=clock)
    sd = StragglerDetector(factor=2.0, min_history=2, clock=clock)
    svc = QueueService(q, straggler=sd)
    for wid in (0, 1):
        assert svc.lease("w1", 1) == [wid]
        clock.t += 1.0
        assert svc.complete([wid], worker="w1") == [wid]
    assert svc.lease("w1", 1) == [2]      # in flight on w1
    clock.t += 10.0                       # way past 2 x p95(=1.0)
    assert svc.lease("w2", 1) == [2]      # pending empty -> speculated
    assert q.speculated() == [2]
    svc.drain("w2")
    assert svc.lease("w2", 1) == []       # but never to a draining worker
    assert svc.complete([2], worker="w2") == [2]
    assert q.speculations == 1 and q.speculations_lost == 1
    assert q.finished


def test_speculation_telemetry_attributes_loser_and_keeps_done_record(
        tmp_path):
    """Regression: a lost speculation race must write a 'redelivered'
    record with reason 'speculated' attributing the LOSER without
    clobbering the winner's timeline — the 'done' record written at
    acceptance must still appear, exactly once."""
    from repro.obs.telemetry import (TelemetryWriter, read_records,
                                     worker_ledger)
    clock = SettableClock()
    q = WorkQueue(1, lease_timeout_s=60.0, clock=clock)
    tw = TelemetryWriter(str(tmp_path))
    svc = QueueService(q, telemetry=tw)
    svc.lease("w1", 1)
    assert q.speculate("w2", 0)
    assert svc.complete([0], worker="w2") == [0]    # w1 lost the race
    svc.note_done("w2", wid=0, survivors=3, bytes_out=12)
    tw.close()
    recs = read_records(str(tmp_path))
    lost = [r for r in recs if r.get("status") == "redelivered"]
    assert len(lost) == 1
    assert lost[0]["reason"] == "speculated" and lost[0]["worker"] == "w1"
    done = [r for r in recs if r.get("status") == "done"]
    assert len(done) == 1 and done[0]["wid"] == 0
    assert done[0]["worker"] == "w2" and done[0]["accept_ts"]
    led = worker_ledger(recs)
    assert led["w1"]["speculation_lost"] == 1
    assert led["w1"]["redelivered_from"] == 1
    assert led["w2"]["chunks_done"] == 1


# ------------------------------------------------------- chaos schedules

def test_make_schedule_deterministic_and_complete():
    for seed in (0, 11, 23, 37, 99):
        a = make_schedule(seed, 8)
        b = make_schedule(seed, 8)
        assert [(e.after_done, e.action, e.stall_s) for e in a] == \
               [(e.after_done, e.action, e.stall_s) for e in b]
        assert {e.action for e in a} == set(ACTIONS)    # >= 1 of each
        assert all(1 <= e.after_done <= 6 for e in a)   # never past n-2
        join = next(e for e in a if e.action == "join")
        assert join.after_done <= 2     # early: must hello before the drain
        stall = next(e for e in a if e.action == "stall")
        assert stall.after_done >= 5    # late: the speculation shape
        assert [e.after_done for e in a] == sorted(e.after_done for e in a)
    assert [(e.after_done, e.action) for e in make_schedule(23, 8)] != \
           [(e.after_done, e.action) for e in make_schedule(37, 8)]
    assert len(make_schedule(3, 8, extra_events=4)) == len(ACTIONS) + 4


# ------------------------------- drain + late join via the worker runtime

def test_worker_drain_and_late_join_inproc():
    """A drained worker finishes what it holds, takes no more, and exits
    through bye; a late joiner hellos into the run in progress and
    finishes the stream. Every id is accepted exactly once."""
    n = 4
    make = audio_batch_maker(seed=9, batch_long_chunks=1)
    setup = {"cfg": cfg, "stages": None, "source_channels": 2,
             "pad_multiple": 1, "bucket": "linear", "backend_mode": "auto"}
    hold = threading.Event()

    def fetch(wid):
        if wid >= 2:
            # the tail of the stream is held back until the drain below
            # has been issued, so shard0 cannot race through everything
            hold.wait(120.0)
        return make(wid)[0]

    q = WorkQueue(n, lease_timeout_s=120.0)
    svc = QueueService(q, fetch_item=fetch, setup=setup)

    accepted = []

    def accept_all():
        while not q.finished:
            for worker, wid, payload in svc.pop_results():
                if svc.complete([wid], worker=worker):
                    svc.note_done(worker, wid=wid)
                    accepted.append(wid)
            time.sleep(0.002)

    acceptor = threading.Thread(target=accept_all, daemon=True)
    acceptor.start()
    stats0 = {}
    t0 = threading.Thread(
        target=lambda: stats0.update(
            run_worker(svc, shard=0, lease_items=1, poll_s=0.005,
                       transport=InProcTransport())),
        daemon=True)
    t0.start()
    deadline = time.monotonic() + 300.0
    while not accepted and time.monotonic() < deadline:
        time.sleep(0.005)
    assert accepted, "shard0 made no progress"
    svc.drain("shard0")
    hold.set()
    t0.join(120.0)
    assert not t0.is_alive(), "a drained worker must exit"
    assert svc.workers["shard0"].state == "departed"    # left through bye
    assert not q.finished                  # it left work behind
    stats1 = run_worker(svc, shard=1, lease_items=1, poll_s=0.005,
                        transport=InProcTransport())
    acceptor.join(60.0)
    assert q.finished
    assert sorted(accepted) == list(range(n))
    assert 0 < stats0["chunks"] < n        # drained out mid-run
    assert stats1["chunks"] >= 1           # the joiner carried the rest
    assert svc.workers["shard1"].state == "departed"
    assert q.redeliveries == 0             # graceful exits reap nothing


# ---------------------------------------------------- pool autoscaling

def test_worker_pool_autoscale_inproc():
    """Sustained backlog scales the pool up toward max_workers; a
    sustained fully-idle pool drains back toward min_workers. Results
    stay exactly-once and bit-identical to two_phase throughout."""
    from repro.serve import WorkerPool

    make = audio_batch_maker(seed=13, batch_long_chunks=1)
    batches = [make(w)[0] for w in range(6)]
    pool = WorkerPool(cfg, workers=1, transport="inproc", poll_s=0.005,
                      min_workers=1, max_workers=3,
                      autoscale_backlog_s=0.05, autoscale_idle_s=0.1).start()
    try:
        wids = [pool.submit(b) for b in batches]
        got = pool.wait(wids, timeout_s=300.0)
        assert sorted(got) == sorted(wids)
        assert pool.scale_ups >= 1, "sustained backlog never scaled up"
        assert len(pool._live_active()) <= 3
        deadline = time.monotonic() + 120.0
        while len(pool._live_active()) > 1 and time.monotonic() < deadline:
            pool.poll()                    # each pump runs the autoscaler
            time.sleep(0.01)
        assert pool.scale_downs >= 1, "idle pool never drained down"
        assert len(pool._live_active()) == 1
        g = pool.gauges()
        assert g["epoch"] >= 1 and g["scale_ups"] == pool.scale_ups
        ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
        for wid, b in zip(wids, batches):
            want = ref(b)
            np.testing.assert_array_equal(np.asarray(got[wid].det.keep),
                                          np.asarray(want.det.keep))
            np.testing.assert_array_equal(got[wid].cleaned, want.cleaned)
    finally:
        pool.shutdown(drain=False)


# ------------------------------------------- real proc workers under fire

def _assert_matches_two_phase(results, make):
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    for r in results:
        want = ref(make(r.wid)[0])
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(want.det.keep))
        np.testing.assert_array_equal(r.cleaned, want.cleaned)


@pytest.mark.parametrize("seed", [11])
def test_chaos_schedule_over_proc_workers(seed):
    """A seeded schedule (at least one SIGKILL, mid-run join, graceful
    drain and SIGSTOP stall) fired at 2 real proc workers while the
    stream runs: every event fires, every chunk comes out exactly once,
    masks and cleaned audio bit-identical to two_phase, a lease is
    redelivered and the late joiner registers. A failing schedule
    reproduces on its own with `-k <seed>`."""
    n_batches = 6
    make = audio_batch_maker(seed=seed, batch_long_chunks=1)
    pool = make_shard_pool(make, n_batches, 2, lease_timeout_s=300.0)
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1,
                       transport="proc", elastic=True)
    schedule = make_schedule(seed, n_batches)
    results, fired = ChaosRunner(pre.plan, pool, schedule, seed=seed).run()
    assert sorted(r.wid for r in results) == list(range(n_batches))
    assert [e.action for e in schedule if not e.fired] == []
    by_action = {a: sum(e.action == a for e in fired) for a in ACTIONS}
    assert all(n >= 1 for n in by_action.values()), by_action
    _assert_matches_two_phase(results, make)
    assert pre.plan.redeliveries >= 1
    names = {st.worker for st in pre.plan.worker_stats}
    assert any(e.action == "join" and f"shard{e.target}" in names
               for e in fired), "no late joiner registered"


def test_speculation_over_proc_workers_attributes_the_loser(tmp_path):
    """The last chunk's holder is SIGSTOPped at grant: with a factor-0
    straggler detector the idle worker wins a speculative duplicate
    lease, every chunk comes out exactly once and bit-identical to
    two_phase, and durable telemetry attributes the losing incarnation
    under reason "speculated" with one 'done' record per chunk."""
    from repro.obs.telemetry import (TelemetryWriter, read_records,
                                     worker_ledger)

    n_batches = 6
    make = audio_batch_maker(seed=7, batch_long_chunks=1)
    pool = make_shard_pool(make, n_batches, 2, lease_timeout_s=300.0)
    telem = TelemetryWriter(tmp_path)
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
        # stopped far longer than the idle survivor needs for the copy
        if wid == n_batches - 1 and not stalled:
            stalled.append(worker)
            plan.fleet.stall(plan.fleet.service.workers[worker].shard,
                             20.0)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    try:
        while plan.fleet is None and t.is_alive():
            time.sleep(0.01)
        if plan.fleet is not None:
            plan.fleet.service.on_grant = on_grant
        t.join(300.0)
        assert not t.is_alive(), "speculation run hung"
    finally:
        telem.close()
    if err:
        raise err[0]
    assert sorted(r.wid for r in results) == list(range(n_batches))
    _assert_matches_two_phase(results, make)
    assert stalled, "the last chunk was never granted"
    assert plan.speculations >= 1
    assert plan.speculations_lost >= 1
    recs = read_records(str(tmp_path))
    assert any(r.get("status") == "redelivered"
               and r.get("reason") == "speculated" for r in recs)
    assert any(e["speculation_lost"] for e in worker_ledger(recs).values())
    assert sorted(r["wid"] for r in recs
                  if r.get("status") == "done") == list(range(n_batches))
