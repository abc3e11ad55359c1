"""Device-resident compaction + the depth-K async executor: equivalence
with two_phase across keep rates, input-order exactly-once emission,
bucketed tail-compile accounting, zero-fill padding hygiene, the
per-batch pipeline timing records, and the reader thread that finishes
each cleaned readback off the dispatching loop."""
import dataclasses
import threading
import time
import warnings
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core import scheduler as SCHED
from repro.core.plans import (JIT_CACHE, PLANS, READER_THREAD, SHARD_THREAD,
                              AsyncPlan, Preprocessor)
from repro.data.loader import audio_batch_maker
from repro.data.synthetic import generate_labelled
from repro.obs import metrics as obs_metrics


def _stream(seed, n_batches, batch_long_chunks=1):
    make = audio_batch_maker(seed=seed,
                             batch_long_chunks=batch_long_chunks)
    return [(w, (make(w)[0], None)) for w in range(n_batches)]


# ---------------------------------------------------------- equivalence

# keep ~0%: every chunk reads as silence; keep 100%: a graph with no
# removal detectors ahead of the removal point keeps everything; the
# default config sits in between on the synthetic stream.
_ALL_KEPT_STAGES = ("to_mono", "compress", "split_detect", "stft",
                    "cicada_bandstop", "istft", "split_final",
                    "removal_point", "mmse")


@pytest.mark.parametrize("rate, mk", [
    ("0%", lambda: (dataclasses.replace(cfg, silence_snr_threshold=2.0),
                    None)),
    ("~37%", lambda: (cfg, None)),    # seed 25: 13/36 chunks survive
    ("100%", lambda: (cfg, _ALL_KEPT_STAGES)),
])
def test_async_bit_identical_to_two_phase(rate, mk):
    """Masks AND cleaned audio bit-identical to TwoPhasePlan at every
    keep-rate regime — device compaction (mask-only readback + on-device
    gather) must be invisible in the values."""
    c, stages = mk()
    stream = _stream(25 if rate == "~37%" else 21, 3)
    ref = Preprocessor(c, plan="two_phase", stages=stages, pad_multiple=1)
    got = Preprocessor(c, plan="async", stages=stages, depth=4,
                       pad_multiple=1)
    ref_res = sorted(ref.run(stream), key=lambda r: r.wid)
    got_res = list(got.run(stream))
    assert [r.wid for r in got_res] == [0, 1, 2]
    keep = np.concatenate([np.asarray(r.det.keep) for r in ref_res])
    frac = keep.mean()
    if rate == "0%":
        assert frac == 0.0
    elif rate == "100%":
        assert frac == 1.0
    else:
        assert 0.3 < frac < 0.45          # ~37%, incl. one all-removed batch
    for r, w in zip(got_res, ref_res):
        np.testing.assert_array_equal(np.asarray(r.det.keep),
                                      np.asarray(w.det.keep))
        np.testing.assert_array_equal(r.cleaned, w.cleaned)
        assert r.cleaned.shape[0] == r.n_kept == w.n_kept


def test_async_in_order_exactly_once_any_depth():
    """Emission is input order with zero lost/duplicated chunks for
    depths below, at, and beyond the stream length."""
    stream = _stream(22, 5)
    ref = None
    for depth in (1, 3, 5, 9):
        pre = Preprocessor(cfg, plan="async", depth=depth, pad_multiple=1)
        res = list(pre.run(stream))
        assert [r.wid for r in res] == [0, 1, 2, 3, 4], f"depth {depth}"
        cleaned = np.concatenate([r.cleaned for r in res])
        if ref is None:
            ref = cleaned
        else:
            np.testing.assert_array_equal(cleaned, ref)


def test_async_registered_and_call_path():
    assert PLANS["async"] is AsyncPlan
    assert PLANS["streaming"].__mro__[1] is AsyncPlan  # depth-1 baseline
    # streaming preserves its pre-AsyncPlan schedule: no emission
    # hold-back (each result yielded as its tail dispatches), depth 1,
    # linear padding; async double-buffers emission by default
    stream_plan = Preprocessor(cfg, plan="streaming").plan
    assert (stream_plan.depth, stream_plan.emit_buffer,
            stream_plan.bucket) == (1, 0, "linear")
    assert Preprocessor(cfg, plan="async").plan.emit_buffer == 1
    chunks = _stream(23, 1)[0][1][0]
    one = Preprocessor(cfg, plan="async")(jnp.asarray(chunks))
    two = Preprocessor(cfg, plan="two_phase")(jnp.asarray(chunks))
    np.testing.assert_array_equal(one.cleaned, two.cleaned)
    assert one.timings is not None and one.timings["n_real"] == one.n_kept


# ------------------------------------------------------------- buckets

def test_quantize_survivors():
    q = SCHED.quantize_survivors
    assert q(5, 24, 1, "linear") == 5
    assert q(5, 24, 4, "linear") == 8
    assert q(5, 24, 1, "pow2") == 8
    assert q(9, 24, 1, "pow2") == 16
    assert q(17, 24, 1, "pow2") == 24          # clipped at the padded cap
    assert q(24, 24, 1, "pow2") == 24
    assert q(3, 24, 4, "pow2") == 4            # pad-multiple aligned
    assert q(5, 24, 4, "pow2") == 8
    with pytest.raises(ValueError, match="bucket"):
        q(5, 24, 1, "fibonacci")
    # the whole point: a B-row batch admits O(log B) distinct sizes
    sizes = {q(n, 24, 1, "pow2") for n in range(1, 25)}
    assert sizes == {1, 2, 4, 8, 16, 24}
    assert len({q(n, 24, 1, "linear") for n in range(1, 25)}) == 24


def test_bucketed_tail_compile_count():
    """One CompileCache entry == one tail compile; pow2 buckets must hit
    exactly the quantized sizes of the observed survivor counts, linear
    one per distinct count."""
    stream = _stream(24, 4, batch_long_chunks=2)
    for bucket in ("pow2", "linear"):
        JIT_CACHE.clear()
        pre = Preprocessor(cfg, plan="async", depth=2, bucket=bucket,
                           pad_multiple=1)
        res = list(pre.run(stream))
        counts = [r.n_kept for r in res]
        cap = int(np.asarray(res[0].det.keep).size)
        expect = {SCHED.quantize_survivors(n, cap, 1, bucket)
                  for n in counts if n}
        got = {k[-1] for k in JIT_CACHE.keys()
               if k[0] in ("tail_idx", "tail_idx_fused")}
        assert got == expect, (bucket, counts)
    assert len(set(counts)) > 1, "stream too uniform to exercise buckets"


# ----------------------------------------------------- zero-fill padding

def test_pad_batch_zero_fills():
    rows = np.arange(6, dtype=np.float32).reshape(3, 2) + 1
    batch, n = SCHED.pad_batch(rows, 4)
    assert n == 3 and batch.shape == (4, 2)
    np.testing.assert_array_equal(batch[3], 0.0)   # was: a repeated row
    np.testing.assert_array_equal(batch[:3], rows)
    b2, n2 = SCHED.survivor_batch(rows, np.array([True, False, True]), 4)
    assert n2 == 2 and b2.shape == (4, 2)
    np.testing.assert_array_equal(b2[2:], 0.0)


def test_survivor_indices_pad_is_out_of_range():
    keep = np.array([True, False, True, True, False, False])
    idx, n = SCHED.survivor_indices(keep, pad_multiple=1, bucket="pow2")
    assert n == 3 and len(idx) == 4
    np.testing.assert_array_equal(idx[:3], [0, 2, 3])
    assert idx[3] == keep.size                     # fill-gather -> zeros
    assert SCHED.survivor_indices(np.zeros(4, bool)) == (None, 0)


def test_padded_rows_never_reach_cleaned():
    """Regression for the repeated-row padding bug: with an aggressive
    pad_multiple the tail runs many pad rows — none may appear in the
    output, and on device they must be zeros (never duplicated audio)."""
    chunks = _stream(21, 1)[0][1][0]
    pre = Preprocessor(cfg, plan="async", pad_multiple=8)
    res = pre(jnp.asarray(chunks))
    assert 0 < res.n_kept == res.cleaned.shape[0]
    ref = Preprocessor(cfg, plan="fused")(jnp.asarray(chunks))
    np.testing.assert_allclose(res.cleaned, ref.cleaned,
                               rtol=1e-4, atol=1e-5)
    # the on-device pad rows themselves: gather past the end -> zero rows
    det = pre.detect(jnp.asarray(chunks))
    keep = np.asarray(det.keep)
    idx, n_real = SCHED.survivor_indices(keep, 8, "pow2")
    assert len(idx) > n_real > 0                   # padding actually ran
    out = np.asarray(pre.graph.tail_indexed(det.wave5, jnp.asarray(idx)))
    np.testing.assert_array_equal(out[n_real:], 0.0)


# ------------------------------------------------- timings + donation

def test_timings_record_pipeline_and_boundary_bytes():
    stream = _stream(26, 4)
    pre = Preprocessor(cfg, plan="async", depth=4, pad_multiple=1)
    res = list(pre.run(stream))
    t = pre.plan.last_timings
    assert len(t) == 4 and [x["in_flight"] for x in t] == [1, 2, 3, 4]
    assert sum(1 for x in t if x["in_flight"] >= 2) >= 1
    for (_, (chunks, _)), x, r in zip(stream, t, res):
        for k in ("dispatch_s", "readback_s", "compact_s", "tail_s",
                  "emit_s"):
            assert x[k] >= 0.0
        assert x is r.timings
        # exact host-boundary accounting: the B-bool mask down, the padded
        # cleaned batch down; the host batch and the int32 index vector
        # up — nothing else
        want = int(np.asarray(r.det.keep).size)          # bool mask bytes
        if x["n_real"]:
            want += x["tail_rows"] * cfg.final_split_samples * 4
        assert x["d2h_bytes"] == want
        assert chunks.dtype == np.float32
        assert x["h2d_bytes"] == chunks.nbytes + 4 * x["tail_rows"]


def test_emit_wait_splits_into_tail_wait_and_copy_back():
    """Read back inline (three batches at depth 2 reach the reader only in
    the final drain, where it is never started), `emit_s` holds the tail
    program's finish (`tail_wait_s`) and the rest of the copy back
    (`d2h_wait_s`), recorded only for batches that ran a tail, and both
    reach the registry's stage histogram."""
    prev, reg = obs_metrics.get_registry(), obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    try:
        res = list(Preprocessor(cfg, plan="async", depth=2,
                                pad_multiple=1).run(_stream(25, 3)))
    finally:
        obs_metrics.set_registry(prev)
    tails = [r for r in res if r.n_kept]
    assert tails and len(tails) < len(res)      # seed 25: one batch all cut
    for r in res:
        x = r.timings
        if not r.n_kept:
            assert "tail_wait_s" not in x and "d2h_wait_s" not in x
            assert "emit_ready" not in x
            continue
        assert x["tail_wait_s"] >= 0.0 and x["d2h_wait_s"] >= 0.0
        assert x["tail_wait_s"] + x["d2h_wait_s"] <= x["emit_s"]
        assert x["emit_ready"] == 0.0
    hist = reg.histogram("plan_stage_seconds", "per-batch stage wall time",
                         ("plan", "stage"))
    for stage in ("tail_wait", "d2h_wait"):
        assert hist.labels(plan="async", stage=stage).value == len(tails)


def test_async_donate_forced_still_bit_identical():
    """donate=True (auto-on for non-CPU backends) must not change values;
    on CPU jax just declines the donation — warning suppressed."""
    stream = _stream(27, 3)
    ref = Preprocessor(cfg, plan="two_phase", pad_multiple=1)
    ref_cleaned = np.concatenate(
        [r.cleaned for r in sorted(ref.run(stream), key=lambda r: r.wid)])
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message=".*donated buffers.*")
        pre = Preprocessor(cfg, plan="async", depth=2, donate=True,
                           pad_multiple=1)
        got = np.concatenate([r.cleaned for r in pre.run(stream)])
    np.testing.assert_array_equal(got, ref_cleaned)


# ------------------------------------------- reader thread (emission)

def _readers():
    return [t for t in threading.enumerate()
            if t.name.startswith(READER_THREAD)]


@pytest.fixture(scope="module")
def long_stream():
    """8 batches, with the references the async plan must match bit for
    bit: StreamingPlan's run (inline emission) and per-batch __call__."""
    stream = _stream(28, 8)
    streaming = list(Preprocessor(cfg, plan="streaming",
                                  pad_multiple=1).run(stream))
    call = Preprocessor(cfg, plan="async", pad_multiple=1)
    calls = [call(jnp.asarray(chunks)) for _, (chunks, _) in stream]
    return stream, streaming, calls


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_reader_emission_in_order_and_bit_identical(long_stream, depth):
    """With the readback on the reader thread, run() still yields input
    order, bitwise equal to StreamingPlan and to per-batch __call__, and
    every batch that ran a tail says whether its rows were ready."""
    stream, streaming, calls = long_stream
    pre = Preprocessor(cfg, plan="async", depth=depth, pad_multiple=1)
    got, saw_reader = [], False
    for r in pre.run(stream):
        saw_reader |= bool(_readers())
        got.append(r)
    assert saw_reader, "the reader never started on an 8-batch stream"
    assert not _readers()
    assert [r.wid for r in got] == list(range(len(stream)))
    assert any(r.n_kept for r in got) and not all(r.n_kept for r in got)
    for r, s, c in zip(got, streaming, calls):
        for ref in (s, c):
            np.testing.assert_array_equal(np.asarray(r.det.keep),
                                          np.asarray(ref.det.keep))
            np.testing.assert_array_equal(r.cleaned, ref.cleaned)
        assert r.n_kept == r.cleaned.shape[0] == s.n_kept
        if r.n_kept:
            assert r.timings["emit_ready"] in (0.0, 1.0)
            assert r.timings["tail_wait_s"] >= 0.0
            assert r.timings["d2h_wait_s"] >= 0.0
        else:
            assert "emit_ready" not in r.timings


@pytest.mark.parametrize("emit_buffer", [1, 2])
def test_slow_readback_bounds_outstanding_tails(monkeypatch, emit_buffer):
    """A reader slower than the loop holds the loop back: no more than
    emit_buffer + 2 dispatched tails are ever outstanding."""
    plan = AsyncPlan(Preprocessor(cfg, pad_multiple=1).graph, depth=1,
                     emit_buffer=emit_buffer)
    readback, start_tail = plan._readback, plan._start_tail
    started, emitted, most = [0], [0], [0]

    def slow_readback(pend):
        time.sleep(0.3)
        return readback(pend)

    def counted_start_tail(*a):
        started[0] += 1
        most[0] = max(most[0], started[0] - emitted[0])
        return start_tail(*a)

    monkeypatch.setattr(plan, "_readback", slow_readback)
    monkeypatch.setattr(plan, "_start_tail", counted_start_tail)
    stream = _stream(28, 8)
    for r in plan.run(stream):
        assert r.wid == emitted[0]
        emitted[0] += 1
    assert emitted[0] == started[0] == len(stream)
    assert emit_buffer + 1 <= most[0] <= emit_buffer + 2


class _Boom(RuntimeError):
    pass


@pytest.mark.parametrize("stop", ["break", "error"])
def test_no_reader_thread_outlives_run(monkeypatch, stop):
    """Stopping early, or an error inside a readback, leaves no reader
    thread alive; the error surfaces from the next() for that batch."""
    pre = Preprocessor(cfg, plan="async", depth=1, pad_multiple=1)
    readback = pre.plan._readback

    def failing_readback(pend):
        if pend.wid == 3:
            raise _Boom("readback of batch 3")
        return readback(pend)

    monkeypatch.setattr(pre.plan, "_readback", failing_readback)
    it = pre.run(_stream(28, 8))
    got = [next(it).wid for _ in range(3)]
    assert got == [0, 1, 2] and _readers()
    if stop == "break":
        it.close()
    else:
        with pytest.raises(_Boom, match="batch 3"):
            next(it)
    assert not _readers() and not _shard_threads()


def _shard_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(SHARD_THREAD)]


class _RowSplit:
    """A tail's output as a mesh of four holds it, split by rows: what the
    plan reads back shard by shard. With `fail`, reading the third shard's
    rows raises."""
    is_fully_replicated = False

    def __init__(self, out, fail):
        self._out = out
        self.shape, self.dtype, self.nbytes = out.shape, out.dtype, out.nbytes
        n = out.shape[0] // 4
        self.addressable_shards = [
            SimpleNamespace(index=(slice(lo, lo + n), slice(None)),
                            data=_Rows(out, lo, lo + n, fail and lo == 2 * n))
            for lo in range(0, out.shape[0], n)]

    def block_until_ready(self):
        return self._out.block_until_ready()


class _Rows:
    """One shard's data: its rows of the whole output, on the host."""

    def __init__(self, out, lo, hi, fail):
        self.out, self.lo, self.hi, self.fail = out, lo, hi, fail

    def __array__(self, dtype=None, copy=None):
        if self.fail:
            raise _Boom("a shard's rows of batch 4")
        return np.asarray(self.out)[self.lo:self.hi]


@pytest.mark.parametrize("stop", ["break", "error", "shard_error"])
def test_no_shard_thread_outlives_run(monkeypatch, stop):
    """With outputs split by rows, each readback runs on the shard threads
    too; stopping early, an error inside a readback, or one inside a
    shard's read leaves none of them (and no reader) alive, and a shard's
    error surfaces from the next() for its batch."""
    pre = Preprocessor(cfg, plan="async", depth=1, pad_multiple=4)
    plan = pre.plan
    start_tail, readback = plan._start_tail, plan._readback
    bad = 4 if stop == "shard_error" else 3     # seed 28: batch 3 cut whole

    def split_start_tail(*a):
        pend = start_tail(*a)
        if pend.out is not None:
            pend.out = _RowSplit(pend.out, stop == "shard_error"
                                 and pend.wid == bad)
        return pend

    def failing_readback(pend):
        if pend.wid == bad:
            raise _Boom(f"readback of batch {bad}")
        return readback(pend)

    monkeypatch.setattr(plan, "_start_tail", split_start_tail)
    if stop == "error":
        monkeypatch.setattr(plan, "_readback", failing_readback)
    it = pre.run(_stream(28, 8))
    got = [next(it) for _ in range(bad)]
    assert [r.wid for r in got] == list(range(bad))
    assert _readers() and _shard_threads()
    for r in got:
        assert r.timings.get("d2h_shards", 0) == (4 if r.n_kept else 0)
    if stop == "break":
        it.close()
    else:
        with pytest.raises(_Boom, match=f"batch {bad}"):
            next(it)
    assert not _readers() and not _shard_threads()


@pytest.mark.parametrize("plan, n", [("async", 1), ("streaming", 8)])
def test_reader_never_starts(plan, n):
    """A one-batch run() (the serving pump) reaches only the final drain,
    and StreamingPlan reads every result back inline: neither starts the
    reader."""
    res = []
    for r in Preprocessor(cfg, plan=plan, pad_multiple=1).run(_stream(28, n)):
        assert not _readers()
        res.append(r)
    assert [r.wid for r in res] == list(range(n))
