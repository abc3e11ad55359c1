"""The master/worker runtime: WorkQueue thread-safety under a served-queue
load (8 threads, forced expiries), the QueueService RPC surface + per-worker
ledger, the worker runtime driven in-process over InProcTransport, and the
acceptance parity — the same seeded stream through InProcTransport vs
ProcTransport at shards {1, 2, 4} must yield bit-identical masks and
cleaned audio in identical emission order."""
import collections
import random
import threading
import time

import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import Preprocessor
from repro.data.loader import audio_batch_maker, make_shard_pool
from repro.data.queue import WorkQueue
from repro.dist.service import QueueService, RPC_METHODS
from repro.dist.transport import InProcTransport, RemoteError
from repro.dist.worker import run_worker


# ------------------------------------------------- queue thread-safety

def test_workqueue_thread_hammer_no_lost_or_dup():
    """8 threads lease/complete/fail against ONE queue with a 20 ms lease
    timeout and scripted over-deadline sleeps, so expiry reaps race live
    completes. Exactly-once accounting must survive: every id retired
    once, none lost, none retired twice (the newly-retired return value is
    the dedup gate)."""
    n = 400
    q = WorkQueue(n, lease_timeout_s=0.02)
    retired = collections.Counter()
    lock = threading.Lock()
    errors = []

    def worker(tid):
        rng = random.Random(1000 + tid)
        name = f"w{tid}"
        try:
            while not q.finished:
                ids = q.lease(name, rng.randint(1, 4))
                if not ids:
                    time.sleep(0.001)
                    continue
                if rng.random() < 0.2:
                    time.sleep(0.03)      # blow the deadline: forced expiry
                if rng.random() < 0.05:
                    q.fail_worker(name)   # chaos: drop own live leases
                newly = q.complete(ids)
                with lock:
                    retired.update(newly)
        except Exception as e:            # pragma: no cover - must not fire
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert q.finished
    assert sorted(retired) == list(range(n)), "lost work ids"
    assert max(retired.values()) == 1, "a work id was retired twice"
    assert q.redeliveries >= 1, "the hammer never forced a redelivery"
    assert sum(q.redelivered_from.values()) == q.redeliveries


# --------------------------------------------------- service + transport

def test_queue_service_ledger_and_grant_hook():
    q = WorkQueue(4, lease_timeout_s=60.0)
    svc = QueueService(q)
    granted = []
    svc.on_grant = lambda worker, wid: granted.append((worker, wid))
    assert svc.hello("shard0", pid=123, shard=0) == {}
    assert svc.lease("shard0", 3) == [0, 1, 2]
    assert granted == [("shard0", 0), ("shard0", 1), ("shard0", 2)]
    assert svc.complete([0]) == [0]
    assert svc.complete([0]) == []          # the exactly-once gate
    svc.push_result("shard0", 1, {"x": 1})
    assert svc.pop_results() == [("shard0", 1, {"x": 1})]
    assert svc.pop_results() == []
    assert not svc.finished
    assert svc.progress() == (1, 4)
    (st,) = svc.worker_report()
    assert (st.pid, st.shard) == (123, 0)
    assert st.lease_calls == 1 and st.leased_total == 3
    assert st.chunks_done == 0     # a push is not credit — acceptance is
    svc.note_done("shard0")        # (the master's completion gate calls it)
    assert svc.worker_report()[0].chunks_done == 1
    assert st.leases_held == 2              # ids 1, 2 still registered
    assert st.last_beat_age_s is not None


def test_inproc_transport_serves_only_the_rpc_surface():
    q = WorkQueue(2)
    svc = QueueService(q)
    proxy = InProcTransport().connect(svc)
    assert proxy.call("lease", "w", 1) == [0]
    assert proxy.call("finished") is False  # property, dispatched plainly
    assert proxy.call("complete", [0]) == [0]
    for method in ("pop_results", "worker_report", "queue", "on_grant"):
        assert method not in RPC_METHODS
        with pytest.raises(RemoteError):
            proxy.call(method)


def test_sharded_plan_rejects_unknown_transport():
    with pytest.raises(ValueError, match="transport"):
        Preprocessor(cfg, plan="sharded", shards=2, transport="carrier-pigeon")


# ----------------------------------------------------- worker runtime

def test_worker_runtime_inproc_round_trip():
    """Drive the REAL worker loop (lease -> fetch -> detect+tail -> push)
    in-process over InProcTransport; the master completes what came back.
    Results must match the two_phase reference bit-for-bit — the worker
    runtime is the same computation, reached over the wire protocol."""
    n = 2
    make = audio_batch_maker(seed=9, batch_long_chunks=1)
    setup = {"cfg": cfg, "stages": None, "source_channels": 2,
             "pad_multiple": 1, "bucket": "linear", "backend_mode": "auto"}
    q = WorkQueue(n, lease_timeout_s=60.0)
    svc = QueueService(q, fetch_item=lambda wid: make(wid)[0], setup=setup)
    stats = run_worker(svc, shard=0, lease_items=2,
                       transport=InProcTransport(), max_items=n)
    assert stats["chunks"] == n
    got = {wid: payload for _, wid, payload in svc.pop_results()}
    assert sorted(got) == list(range(n))
    assert q.complete(sorted(got)) == list(range(n))
    svc.note_done("shard0", n)     # master-side acceptance credit
    assert q.finished
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    for wid, payload in got.items():
        want = ref(make(wid)[0])
        np.testing.assert_array_equal(payload["keep"],
                                      np.asarray(want.det.keep))
        np.testing.assert_array_equal(payload["cleaned"], want.cleaned)
        assert payload["n_kept"] == want.n_kept
    (st,) = svc.worker_report()
    assert st.chunks_done == n and st.lease_calls == 1  # one round-trip


def test_worker_skips_stale_fetch():
    """A fetch that answers None (the id completed — possibly emitted and
    released — while this redelivered lease was in flight) is skipped:
    no compute, no push, no crash. This is the recovery path for a lease
    that expired mid-compile and lost the redelivery race."""
    make = audio_batch_maker(seed=9, batch_long_chunks=1)
    q = WorkQueue(2, lease_timeout_s=60.0)
    setup = {"cfg": cfg, "stages": None, "source_channels": 2,
             "pad_multiple": 1, "bucket": "linear", "backend_mode": "auto"}
    svc = QueueService(
        q, setup=setup,
        fetch_item=lambda wid: None if wid == 0 else make(wid)[0])
    stats = run_worker(svc, shard=0, lease_items=2,
                       transport=InProcTransport(), max_items=1)
    assert stats["chunks"] == 1            # wid 0 skipped, wid 1 computed
    results = svc.pop_results()
    assert [wid for _, wid, _ in results] == [1]


# --------------------------------------------------- transport parity

def _stream(n_batches):
    make = audio_batch_maker(seed=21, batch_long_chunks=1)
    return [(w, (make(w)[0], None)) for w in range(n_batches)]


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_transport_parity_bit_identical(shards, tmp_path):
    """Acceptance: the same seeded stream through the in-proc simulated
    transport, through REAL worker processes over loopback, and through
    the TCP transport with the STORE data plane (chunk bytes via a shared
    ChunkStore, the socket carrying only keys) yields bit-identical keep
    masks, bit-identical cleaned audio, and identical emission order."""
    stream = _stream(3)
    runs = {}
    for transport in ("inproc", "proc", "tcp"):
        kw = ({"data_plane": str(tmp_path / "dp")}
              if transport == "tcp" else {})
        pre = Preprocessor(cfg, plan="sharded", shards=shards,
                           pad_multiple=1, transport=transport, **kw)
        results = list(pre.run(list(stream)))
        runs[transport] = results
        assert sorted(r.wid for r in results) == [0, 1, 2]
    orders = [[r.wid for r in rs] for rs in runs.values()]
    assert all(o == orders[0] for o in orders), \
        f"emission order diverged: {orders}"
    for other in ("proc", "tcp"):
        for a, b in zip(runs["inproc"], runs[other]):
            assert a.wid == b.wid
            np.testing.assert_array_equal(np.asarray(a.det.keep),
                                          np.asarray(b.det.keep))
            np.testing.assert_array_equal(a.cleaned, b.cleaned)
            assert a.n_kept == b.n_kept
            assert a.src_bytes == b.src_bytes


# ------------------------------------------------ one process per chip

@pytest.mark.parametrize("runtime", ["spawn_worker", "pool_proc",
                                     "pool_tcp", "sharded_proc"])
def test_worker_processes_refused_on_tpu(monkeypatch, runtime):
    """On a TPU backend every path that would start a worker process
    raises at once, naming the one-process-per-chip rule, and starts
    nothing."""
    import jax
    from repro.dist.transport import ProcTransport
    from repro.serve import WorkerPool

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process per chip"):
        if runtime == "spawn_worker":
            tp = ProcTransport()
            tp.serve(QueueService(WorkQueue(1)))
            try:
                tp.spawn_worker()
            finally:
                tp.close()
        elif runtime.startswith("pool"):
            WorkerPool(cfg, workers=1,
                       transport=runtime.split("_")[1]).start()
        else:
            pre = Preprocessor(cfg, plan="sharded", shards=2,
                               transport="proc", pad_multiple=1)
            list(pre.run(_stream(1)))
