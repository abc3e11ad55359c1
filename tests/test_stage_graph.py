"""The stage-graph API: registry round-trip, build-time geometry
validation, plan equivalence across pad multiples (fused == two_phase ==
streaming on survivors), compile-cache keying, and the serving glue."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core.graph import (GraphValidationError, PipelineGraph, STAGES)
from repro.core.plans import (CompileCache, JIT_CACHE, PLANS, Preprocessor,
                              TwoPhasePlan)
from repro.data.synthetic import generate_labelled
from repro.distributed.sharding import ShardingRules
from repro.kernels import backend


def _long_chunks(seed, n_long):
    audio, labels = generate_labelled(seed, n_long * 12, segment_s=5.0)
    S5 = audio.shape[-1]
    return (audio.reshape(n_long, 12, 2, S5).transpose(0, 2, 1, 3)
            .reshape(n_long, 2, 12 * S5)), labels


@pytest.fixture(scope="module")
def chunks():
    return _long_chunks(7, 4)[0]


# ----------------------------------------------------------- registry/graph

def _lowered_names(kind, *shapes):
    """(module name, {first two name scopes under the jit of each op}) of
    a phase lowered for `shapes`."""
    pre = Preprocessor(cfg, plan="async")
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(pre.phase_fn(kind)).lower(*args).as_text(debug_info=True)
    module = re.search(r"^module @(\S+)", text, re.M).group(1)
    scopes = {"/".join(m.split("/")[:2]) for m in re.findall(
        r'loc\("jit\(%s\)/([^"]*)"' % kind, text)}
    return module, scopes


def test_programs_and_stages_carry_their_names():
    """Each phase's program is named `jit_<phase>`, and its ops sit under
    the name scope of the stage that made them; the fused tail's kernel
    call and its XLA resynthesis sit apart."""
    long = ((1, 2, cfg.long_split_samples), jnp.float32)
    module, scopes = _lowered_names("detect", long)
    graph = PipelineGraph(cfg)
    assert module == "jit_detect"
    assert set(graph.names[:graph._cut()]) <= {s.split("/")[0]
                                                for s in scopes}
    rows = ((12, cfg.final_split_samples), jnp.float32)
    idx = ((8,), jnp.int32)
    module, scopes = _lowered_names("tail_idx", rows, idx)
    assert module == "jit_tail_idx" and "mmse" in {s.split("/")[0]
                                                   for s in scopes}
    with backend.use("interpret"):
        module, scopes = _lowered_names("tail_idx_fused", rows, idx)
    assert module == "jit_tail_idx_fused"
    assert {"fused_tail/kernel", "fused_tail/istft"} <= scopes


def test_stage_registry_round_trip():
    """The paper's order is config DATA: every declared stage resolves in
    the registry and the built graph reproduces the declared order."""
    graph = PipelineGraph(cfg)
    assert graph.names == cfg.stages
    assert all(n in STAGES for n in cfg.stages)
    assert [s.name for s in graph.stages] == list(cfg.stages)
    assert graph.has_removal_point
    # ablation by config edit, not driver fork: drop the final MMSE stage
    cfg2 = dataclasses.replace(cfg, stages=cfg.stages[:-1])
    assert PipelineGraph(cfg2).names == cfg.stages[:-1]
    # geometry propagated: 60 s stereo source -> 5 s mono @ 22.05 kHz
    assert graph.out_geom.split_s == cfg.final_split_s
    assert graph.out_geom.rate_hz == cfg.target_rate_hz
    assert graph.out_geom.channels == 1


@pytest.mark.parametrize("bad, match", [
    (("to_mono", "compress", "split_final", "split_detect"),
     "cannot split"),                       # 5 s chunks into 15 s chunks
    (("compress",), "mono"),                # stereo into the FIR
    (("to_mono", "compress", "cicada_bandstop"), "spec"),   # no STFT ran
    (("to_mono", "compress", "compress"), "Hz"),            # double compress
    (("to_mono", "nonexistent_stage"), "unknown stages"),
    (("to_mono", "compress", "split_detect", "stft", "detect_rain",
      "removal_point", "mmse", "detect_silence"), "power"),
    # ^ past a removal point only the waveform survives compaction
])
def test_graph_validation_rejects_bad_orders(bad, match):
    with pytest.raises(GraphValidationError, match=match):
        PipelineGraph(cfg, bad)


def test_detect_flux_drop_in_detector(chunks):
    """Registry extensibility: the spectral-flux energy detector swaps in
    for 'detect_silence' purely via the stage list — no executor changes —
    and keeps transient bird activity while removing silence and steady
    rain."""
    st = list(cfg.stages)
    st[st.index("detect_silence")] = "detect_flux"
    graph = PipelineGraph(cfg, tuple(st))
    assert graph.has_removal_point and "detect_flux" in graph.names
    chunks4, labels = _long_chunks(13, 4)
    pre = Preprocessor(cfg, plan="two_phase", stages=tuple(st))
    res = pre(jnp.asarray(chunks4))
    keep = np.asarray(res.det.keep)
    assert res.cleaned.shape[0] == keep.sum() == res.n_kept
    # flux keeps active chunks (bird=0, cicada=2), removes silence + rain
    active = np.isin(labels, (0, 2))
    assert (keep == active).mean() >= 0.9
    # flux also runs stacked WITH the SNR detector (masks OR together)
    both = tuple(cfg.stages[:-2] + ("detect_flux",) + cfg.stages[-2:])
    res2 = Preprocessor(cfg, plan="two_phase", stages=both)(
        jnp.asarray(chunks4))
    assert not (np.asarray(res2.det.keep) & ~keep).any()
    # and validation still guards it: flux needs power spectra upstream
    with pytest.raises(GraphValidationError, match="power"):
        PipelineGraph(cfg, ("to_mono", "compress", "detect_flux"))


def test_two_phase_requires_removal_point():
    graph = PipelineGraph(
        cfg, ("to_mono", "compress", "split_detect", "stft", "detect_rain",
              "cicada_bandstop", "istft", "split_final", "detect_silence",
              "mmse"))
    with pytest.raises(GraphValidationError, match="removal_point"):
        TwoPhasePlan(graph)


# ------------------------------------------------------- plan equivalence

@pytest.mark.parametrize("pad_multiple", [1, 2, 8])
def test_plan_equivalence(chunks, pad_multiple):
    """fused == two_phase == streaming on the survivor set, for every
    phase-B pad multiple (padding must never leak into results); the
    fused reference is finite, so a NaN in any plan fails the match."""
    x = jnp.asarray(chunks)
    ref = Preprocessor(cfg, plan="fused")(x)
    assert np.isfinite(ref.cleaned).all()
    two = Preprocessor(cfg, plan="two_phase", pad_multiple=pad_multiple)(x)
    np.testing.assert_array_equal(np.asarray(two.det.keep),
                                  np.asarray(ref.det.keep))
    np.testing.assert_allclose(two.cleaned, ref.cleaned,
                               rtol=1e-4, atol=1e-5)
    # streaming: same work as a 2-batch stream through run()
    pre_s = Preprocessor(cfg, plan="streaming", pad_multiple=pad_multiple)
    results = list(pre_s.run([(0, (chunks[:2], None)),
                              (1, (chunks[2:], None))]))
    assert [r.wid for r in results] == [0, 1]
    cat = np.concatenate([r.cleaned for r in results])
    np.testing.assert_allclose(cat, ref.cleaned, rtol=1e-4, atol=1e-5)


def test_all_removed_batch():
    """Every plan handles a batch with zero survivors cleanly."""
    chunks, _ = _long_chunks(3, 1)
    all_silent = dataclasses.replace(cfg, silence_snr_threshold=2.0)
    for name in sorted(PLANS):
        pre = Preprocessor(all_silent, plan=name, pad_multiple=4)
        results = list(pre.run([chunks]))
        assert len(results) == 1
        res = results[0]
        assert res.n_kept == 0
        assert res.cleaned.shape == (0, all_silent.final_split_samples)
        assert not np.asarray(res.det.keep).any()


# ------------------------------------------------------------ compile cache

def test_sharding_rules_fingerprint_is_stable():
    """The old cache keyed on id(rules): two logically-equal rules objects
    got separate entries and a GC'd id could alias. Fingerprints compare by
    value."""
    a, b = ShardingRules(None), ShardingRules(None)
    assert a is not b and a.fingerprint == b.fingerprint
    c = ShardingRules(None, overrides={"chunks": ("data",)})
    assert c.fingerprint != a.fingerprint


def test_compile_cache_shared_across_equal_rules(chunks):
    JIT_CACHE.clear()
    x = jnp.asarray(chunks[:1])
    det1 = Preprocessor(cfg, ShardingRules(None)).detect(x)
    n_after_first = len(JIT_CACHE)
    det2 = Preprocessor(cfg, ShardingRules(None)).detect(x)
    assert len(JIT_CACHE) == n_after_first == 1    # one shared compile
    np.testing.assert_array_equal(np.asarray(det1.keep),
                                  np.asarray(det2.keep))


def test_compile_cache_evicts_at_cap():
    cache = CompileCache(maxsize=3)
    for i in range(10):
        cache.get(("k", i), lambda i=i: i)
    assert len(cache) == 3
    assert ("k", 9) in cache and ("k", 0) not in cache
    # LRU: touching an old-but-live key keeps it resident
    cache.get(("k", 7), lambda: "rebuilt")
    cache.get(("k", 99), lambda: 99)
    assert ("k", 7) in cache


# ------------------------------------------------------------- serve glue

def test_preprocess_service_round_trip(chunks):
    from repro.serve.preprocess_service import PreprocessService
    svc = PreprocessService(cfg, batch_long_chunks=2, plan="two_phase")
    rids = [svc.submit(chunks[i]) for i in range(3)]
    served = []
    while len(served) < len(rids):
        served.extend(svc.pump())
    # cross-check against a direct facade run on the same stacked batch
    det = Preprocessor(cfg).detect(jnp.asarray(chunks[:3]))
    keep = np.asarray(det.keep)
    for j, rid in enumerate(rids):
        r = svc.result(rid)
        assert r is not None
        np.testing.assert_array_equal(r["keep"], keep[j * 12:(j + 1) * 12])
        assert r["cleaned"].shape[0] == int(r["keep"].sum())
        assert np.isfinite(r["cleaned"]).all()
