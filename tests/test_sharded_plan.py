"""The multi-shard execution layer: Rebalancer/balance-stat coverage
(skewed masks, all-removed shards, survivor counts that don't divide), the
ShardedPlan's bit-identical-survivor equivalence with TwoPhasePlan, and
crash/lease-expiry recovery with exactly-once emission."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core import scheduler as SCHED
from repro.core.plans import JIT_CACHE, Preprocessor
from repro.data.loader import (ShardedLoader, audio_batch_maker,
                               make_shard_pool)
from repro.data.queue import SettableClock as FakeClock
from repro.data.queue import WorkQueue
from repro.distributed.sharding import pool_rules
from repro.ft.failure import CrashInjector


# ------------------------------------------------------ scheduler coverage

def test_shard_load_and_balance_stats_skewed():
    """One shard holds every survivor: loads are per-shard exact and the
    'before' imbalance is the shard count (max = n, mean = n/k)."""
    keep = jnp.asarray([True] * 6 + [False] * 18)
    loads = np.asarray(SCHED.shard_load(keep, 4))
    assert loads.tolist() == [6, 0, 0, 0]
    bs = jax.jit(lambda k: SCHED.balance_stats(k, 4))(keep)
    assert float(bs["imbalance"]) == pytest.approx(4.0)
    assert float(bs["imbalance_after_compact"]) == pytest.approx(
        np.ceil(6 / 4) / (6 / 4))


def test_shard_load_pads_non_divisible():
    """N not divisible by n_shards: trailing shard sees the short tail."""
    keep = jnp.asarray([True] * 10)          # 10 chunks over 4 shards
    loads = np.asarray(SCHED.shard_load(keep, 4))
    assert loads.tolist() == [3, 3, 3, 1]
    assert int(loads.sum()) == 10            # padding adds no survivors


def test_balance_stats_all_removed():
    keep = jnp.zeros((12,), bool)
    bs = SCHED.balance_stats(keep, 3)
    assert np.asarray(bs["loads"]).tolist() == [0, 0, 0]
    assert np.isfinite(float(bs["imbalance"]))


def test_rebalancer_skewed_and_all_removed_shard():
    """Skewed masks — one shard all-survivor, one all-removed — come out
    within the +-1 of integer division (max/min <= 1.5 for n >= 2k)."""
    keeps = [np.ones(12, bool), np.zeros(12, bool),
             np.array([True, False] * 6)]
    asg = SCHED.Rebalancer(3).assign(keeps)
    st = asg.stats()
    assert st["loads_before"].tolist() == [12, 0, 6]
    assert st["max_min_before"] == 12.0
    assert st["loads_after"].tolist() == [6, 6, 6]
    assert st["max_min_after"] <= 1.5
    assert st["moved"] == 6                  # shard0's overflow -> shard1
    assert asg.bounds.tolist() == [0, 6, 12, 18]


def test_rebalancer_non_divisible_and_fewer_live_shards():
    keeps = [np.ones(7, bool), np.ones(4, bool), np.zeros(5, bool)]
    asg = SCHED.Rebalancer(3).assign(keeps, out_shards=2)   # one shard died
    assert asg.counts_after.tolist() == [6, 5]              # 11 over 2
    assert int(asg.counts_after.sum()) == 11
    assert asg.stats()["max_min_after"] <= 1.5


def test_rebalancer_split_pads_batches():
    reb = SCHED.Rebalancer(2, pad_multiple=4)
    surv = np.arange(10, dtype=np.float32).reshape(5, 2)
    asg = reb.assign([np.ones(3, bool), np.ones(2, bool)])
    parts = list(reb.split(surv, asg))
    assert [(j, b.shape[0], n) for j, b, n in parts] == [(0, 4, 3), (1, 4, 2)]
    np.testing.assert_array_equal(parts[0][1][:3], surv[:3])
    np.testing.assert_array_equal(parts[0][1][3], 0.0)  # pad = zero rows,
    # never repeated audio (PR 4: repeated-row padding wasted MMSE flops)


def test_rebalancer_empty():
    asg = SCHED.Rebalancer(2).assign([np.zeros(4, bool), np.zeros(4, bool)])
    assert asg.counts_after.tolist() == [0, 0]
    assert list(SCHED.Rebalancer(2).split(np.zeros((0, 8)), asg)) == []
    assert asg.stats()["max_min_after"] == 1.0


# ------------------------------------------------- plan equivalence / FT

def _long_chunks(seed, n_long):
    from repro.data.synthetic import generate_labelled
    audio, _ = generate_labelled(seed, n_long * 12, segment_s=5.0)
    S5 = audio.shape[-1]
    return (audio.reshape(n_long, 12, 2, S5).transpose(0, 2, 1, 3)
            .reshape(n_long, 2, 12 * S5))


@pytest.fixture(scope="module")
def chunks():
    return _long_chunks(11, 4)


def test_sharded_matches_two_phase_bitwise_masks(chunks):
    """Acceptance: bit-identical survivor masks and matching cleaned audio
    vs TwoPhasePlan on the same stream, compared per work id."""
    stream = [(0, (chunks[:1], None)), (1, (chunks[1:3], None)),
              (2, (chunks[3:], None))]
    ref = {r.wid: r for r in
           Preprocessor(cfg, plan="two_phase", pad_multiple=2).run(stream)}
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=2)
    got = {r.wid: r for r in pre.run(stream)}
    assert sorted(got) == sorted(ref)
    for wid, r in got.items():
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(ref[wid].det.keep))
        np.testing.assert_allclose(r.cleaned, ref[wid].cleaned,
                                   rtol=1e-4, atol=1e-5)
        assert r.n_kept == ref[wid].n_kept


def test_sharded_single_batch_call_matches_fused(chunks):
    """The serve path (__call__): rows split across shards, survivors
    rebalanced, output identical to the fused reference."""
    x = jnp.asarray(chunks)
    ref = Preprocessor(cfg, plan="fused")(x)
    sh = Preprocessor(cfg, plan="sharded", shards=3, pad_multiple=1)(x)
    keep = np.asarray(sh.det.keep)
    np.testing.assert_array_equal(keep, np.asarray(ref.det.keep))
    np.testing.assert_allclose(sh.cleaned, np.asarray(ref.cleaned),
                               rtol=1e-4, atol=1e-5)
    assert sh.det.stats["n_chunks5"] == keep.size


def test_sharded_service_round_trip(chunks):
    from repro.serve.preprocess_service import PreprocessService
    svc = PreprocessService(cfg, batch_long_chunks=2, plan="sharded",
                            shards=2)
    rids = [svc.submit(chunks[i]) for i in range(3)]
    served = []
    while len(served) < len(rids):
        served.extend(svc.pump())
    det = Preprocessor(cfg).detect(jnp.asarray(chunks[:3]))
    keep = np.asarray(det.keep)
    for j, rid in enumerate(rids):
        r = svc.result(rid)
        np.testing.assert_array_equal(r["keep"], keep[j * 12:(j + 1) * 12])
        assert r["cleaned"].shape[0] == int(r["keep"].sum())


def test_sharded_rebalance_ratio_on_skewed_stream():
    """Acceptance: post-rebalance max/min shard load <= 1.5 when the
    per-shard survivor counts are heavily skewed (silence-heavy batches on
    one shard, bird-heavy on the other)."""
    base = _long_chunks(5, 2)
    quiet = np.zeros_like(base) + 1e-4 * np.random.RandomState(0).randn(
        *base.shape).astype(np.float32)     # all-silence batches
    stream = [(0, (base, None)), (1, (quiet, None))]
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1)
    results = list(pre.run(stream))
    assert sorted(r.wid for r in results) == [0, 1]
    st = pre.plan.last_assignment.stats()
    assert int(st["loads_before"].min()) == 0          # the skew is real
    assert st["max_min_after"] <= 1.5
    assert int(st["loads_after"].sum()) == sum(r.n_kept for r in results)


def test_sharded_crash_recovery_exactly_once():
    """Acceptance: a killed worker mid-stream finishes the run with
    redeliveries >= 1, no missing or duplicate chunk ids, and the
    two_phase masks and cleaned audio."""
    n_batches = 6
    make = audio_batch_maker(seed=2, batch_long_chunks=1)
    pool = make_shard_pool(make, n_batches, 3)
    inj = CrashInjector()
    inj.kill(1, after_items=1)
    pre = Preprocessor(cfg, plan="sharded", shards=3, pad_multiple=1,
                       injector=inj)
    results = list(pre.run(pool))
    wids = sorted(r.wid for r in results)
    assert wids == list(range(n_batches))              # exactly once
    assert pre.plan.redeliveries >= 1
    assert not inj.alive(1)
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    for r in results:
        want = ref(make(r.wid)[0])
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(want.det.keep))
        np.testing.assert_allclose(r.cleaned, want.cleaned,
                                   rtol=1e-4, atol=1e-5)


def test_sharded_forced_lease_expiry_redelivers():
    """A lease orphaned by a pre-run crash (deadline already past) is
    reaped on the first pull and the work completes on a live shard."""
    clock = FakeClock()
    n_batches = 3
    queue = WorkQueue(n_batches, lease_timeout_s=5.0, clock=clock)
    orphan = queue.lease("ghost", 1)
    assert orphan == [0]
    clock.t = 6.0
    make = audio_batch_maker(seed=4, batch_long_chunks=1)
    pool = make_shard_pool(make, n_batches, 2, queue=queue)
    pre = Preprocessor(cfg, plan="sharded", shards=2, pad_multiple=1)
    results = list(pre.run(pool))
    assert sorted(r.wid for r in results) == list(range(n_batches))
    assert pre.plan.redeliveries >= 1


def test_sharded_all_shards_dead_raises():
    make = audio_batch_maker(seed=1, batch_long_chunks=1)
    pool = make_shard_pool(make, 4, 2)
    inj = CrashInjector()
    inj.kill(0, after_items=0)
    inj.kill(1, after_items=0)
    pre = Preprocessor(cfg, plan="sharded", shards=2, injector=inj)
    with pytest.raises(RuntimeError, match="stalled"):
        list(pre.run(pool))


def test_sharded_per_shard_rules_share_compile_cache(chunks):
    """pool_rules: same-mesh (here: unmeshed) shards dedup to ONE compiled
    phase in the shared CompileCache — N shards never mean N compiles."""
    JIT_CACHE.clear()
    rules = pool_rules(3)
    assert len({r.fingerprint for r in rules}) == 1
    pre = Preprocessor(cfg, rules, plan="sharded", shards=3, pad_multiple=1)
    pre(jnp.asarray(chunks))
    assert len(JIT_CACHE) == 2            # one detect + one tail, shared
    with pytest.raises(ValueError, match="per-shard rules"):
        Preprocessor(cfg, pool_rules(2), plan="sharded", shards=3)
    with pytest.raises(ValueError, match="only valid with the sharded"):
        Preprocessor(cfg, pool_rules(2), plan="two_phase")


def test_sharded_loader_pool_shares_queue():
    make = audio_batch_maker(seed=0, batch_long_chunks=1)
    pool = make_shard_pool(make, 4, 2)
    assert all(isinstance(ld, ShardedLoader) for ld in pool)
    assert pool[0].queue is pool[1].queue
    got = pool[0].pull()
    assert len(got) == 1
    wid, (batch, labels) = got[0]
    assert batch.shape[0] == 1
    assert pool[0].complete(wid) and not pool[0].complete(wid)
