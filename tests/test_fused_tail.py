"""The fused survivor tail (kernels/fused_tail + the plan wiring):
staged-vs-fused identity across keep rates on the CPU auto path, staged
and fused kernels against the float32 reference per backend mode, the
zero-pad-row invariant, bucket-keyed fused compiles, donation value
identity, the non-canonical-stage-list fallback, and the autotuner's
VMEM feasibility across pow2 buckets.

Bitwise comparisons always pit JITTED against JITTED: XLA contracts
mul+add chains to FMA under jit but not in eager op-by-op dispatch, so a
jitted path and its eager twin legitimately differ in the last bit —
plans always run jitted, and so do these assertions.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core import scheduler as SCHED
from repro.core.graph import GraphValidationError, PipelineGraph
from repro.core.plans import JIT_CACHE, Preprocessor
from repro.data.loader import audio_batch_maker
from repro.kernels import backend
from repro.kernels.fused_tail import kernel as FTK
from repro.kernels.fused_tail import ops as FTO

_HPF_TAIL_STAGES = cfg.stages[:-1] + ("hpf", "mmse")
_ALL_KEPT_STAGES = ("to_mono", "compress", "split_detect", "stft",
                    "cicada_bandstop", "istft", "split_final",
                    "removal_point", "mmse")


def _stream(seed, n_batches, batch_long_chunks=1):
    make = audio_batch_maker(seed=seed,
                             batch_long_chunks=batch_long_chunks)
    return [(w, (make(w)[0], None)) for w in range(n_batches)]


def _small_wave(B=6, n_tiles=1, seed=0):
    """A (B, S) f32 batch with S one STFT tile — small enough that
    interpret-mode grid steps stay cheap."""
    S = n_tiles * 128 * cfg.stft_hop + cfg.stft_window
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(B, S).astype(np.float32) * 0.3)


# ------------------------------------------------- plan-level equivalence

@pytest.mark.parametrize("rate, mk", [
    ("0%", lambda: (dataclasses.replace(cfg, silence_snr_threshold=2.0),
                    None)),
    ("~37%", lambda: (cfg, None)),
    ("100%", lambda: (cfg, _ALL_KEPT_STAGES)),
])
def test_fused_plan_bit_identical_to_staged(rate, mk):
    """two_phase with the fused tail vs two_phase with the staged tail on
    the seed-25 stream: masks AND cleaned bit-identical at every keep-rate
    regime (auto backend = the ref path on CPU)."""
    c, stages = mk()
    stream = _stream(25 if rate == "~37%" else 21, 3)
    staged = Preprocessor(c, plan="two_phase", stages=stages,
                          pad_multiple=1, fuse_tail=False)
    fused = Preprocessor(c, plan="two_phase", stages=stages,
                         pad_multiple=1, fuse_tail=True)
    assert staged.plan.fuse_tail is False and fused.plan.fuse_tail is True
    for a, b in zip(staged.run(stream), fused.run(stream)):
        np.testing.assert_array_equal(np.asarray(a.det.keep),
                                      np.asarray(b.det.keep))
        np.testing.assert_array_equal(a.cleaned, b.cleaned)
        assert a.n_kept == b.n_kept


def test_fused_auto_engages_on_canonical_tail():
    assert Preprocessor(cfg, plan="two_phase").plan.fuse_tail is True
    assert Preprocessor(cfg, plan="async").plan.fuse_tail is True
    g = PipelineGraph(cfg)
    assert g.fused_tail_spec == {"hpf": False}
    assert PipelineGraph(cfg, _HPF_TAIL_STAGES).fused_tail_spec \
        == {"hpf": True}


# ------------------------------------------- tail-level mode equivalence

@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("hpf", [False, True])
def test_fused_tail_bit_identical_per_mode(mode, hpf):
    """jit(staged tail_indexed) and jit(fused tail_indexed_fused) in one
    backend mode, each against the float32 reference composition
    (`fused_tail_ref`) within backend.REF_TOL — on a small batch so
    interpret stays cheap. In ref mode both ARE that composition: bitwise
    equal."""
    from repro.kernels.fused_tail import ref as FTR
    stages = _HPF_TAIL_STAGES if hpf else None
    g = PipelineGraph(cfg, stages)
    wave = _small_wave(B=6)
    idx = jnp.asarray([4, 1, 3, 9, 9], jnp.int32)   # 2 pad slots
    staged = jax.jit(lambda w, i: g.tail_indexed(w, i))
    fused = jax.jit(lambda w, i: g.tail_indexed_fused(w, i))
    want = np.asarray(jax.jit(lambda w, i: FTR.fused_tail_ref(
        w, i, cfg, hpf=hpf))(wave, idx))
    with backend.use(mode):
        a, b = np.asarray(staged(wave, idx)), np.asarray(fused(wave, idx))
    np.testing.assert_allclose(a, want, **backend.REF_TOL)
    np.testing.assert_allclose(b, want, **backend.REF_TOL)
    assert not b[3:].any()                          # pad slots
    if mode == "ref":
        np.testing.assert_array_equal(a, b)


def test_fused_matmul_mode_matches_staged():
    g = PipelineGraph(cfg)
    wave = _small_wave(B=4, seed=2)
    idx = jnp.asarray([2, 0, 7], jnp.int32)
    staged = jax.jit(lambda w, i: g.tail_indexed(w, i))
    fused = jax.jit(lambda w, i: g.tail_indexed_fused(w, i))
    with backend.use("matmul"):
        a, b = staged(wave, idx), fused(wave, idx)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------- pad-row invariant

@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_fused_pad_rows_all_zero(mode):
    """Out-of-range survivor-index slots (the scheduler's pad convention)
    must come out as exactly-zero cleaned rows through the fused pass —
    fill-gather semantics preserved inside the kernel."""
    g = PipelineGraph(cfg)
    wave = _small_wave(B=6, seed=3)
    idx = jnp.asarray([3, 0, 99, 5, 1_000_000], jnp.int32)
    with backend.use(mode):
        out = jax.jit(lambda w, i: g.tail_indexed_fused(w, i))(wave, idx)
    out = np.asarray(out)
    assert not out[2].any() and not out[4].any()
    assert out[0].any() and out[1].any() and out[3].any()


# ------------------------------------------------ bucket-keyed compiles

def test_fused_tail_bucketed_compile_count():
    """With fusion auto-engaged, the async plan's tail compiles land under
    the 'tail_idx_fused' kind, one CompileCache entry per pow2 bucket —
    and NO staged 'tail_idx' entries exist."""
    stream = _stream(24, 4, batch_long_chunks=2)
    JIT_CACHE.clear()
    pre = Preprocessor(cfg, plan="async", depth=2, bucket="pow2",
                       pad_multiple=1)
    res = list(pre.run(stream))
    counts = [r.n_kept for r in res]
    cap = int(np.asarray(res[0].det.keep).size)
    expect = {SCHED.quantize_survivors(n, cap, 1, "pow2")
              for n in counts if n}
    kinds = {k[0] for k in JIT_CACHE.keys()}
    assert "tail_idx" not in kinds
    got = {k[-1] for k in JIT_CACHE.keys() if k[0] == "tail_idx_fused"}
    assert got == expect, counts


# -------------------------------------------------------- donation

def test_fused_donation_value_identity():
    """Forcing wave5 donation through the fused tail must not change a
    bit (CPU ignores donation with a warning; the VALUES contract is what
    this pins for real accelerators)."""
    stream = _stream(25, 2)
    plain = Preprocessor(cfg, plan="two_phase", pad_multiple=1,
                         donate=False)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*donated buffers.*")
        donated = Preprocessor(cfg, plan="two_phase", pad_multiple=1,
                               donate=True)
        assert donated.plan.fuse_tail is True
        for a, b in zip(plain.run(stream), donated.run(stream)):
            np.testing.assert_array_equal(a.cleaned, b.cleaned)


# ----------------------------------------------- non-canonical fallback

def test_non_canonical_tail_falls_back_to_staged():
    """A survivor chain that is not [hpf ->] mmse must keep the staged
    path: auto fuse_tail resolves False, fuse_tail=True raises, and the
    fused graph entry point refuses."""
    odd = cfg.stages[:-1] + ("hpf", "hpf", "mmse")
    g = PipelineGraph(cfg, odd)
    assert g.fused_tail_spec is None
    pre = Preprocessor(cfg, plan="two_phase", stages=odd, pad_multiple=1)
    assert pre.plan.fuse_tail is False
    with pytest.raises(GraphValidationError):
        Preprocessor(cfg, plan="two_phase", stages=odd, pad_multiple=1,
                     fuse_tail=True)
    with pytest.raises(GraphValidationError):
        g.tail_indexed_fused(_small_wave(B=2),
                             jnp.asarray([0, 1], jnp.int32))
    # ... and the odd graph still RUNS correctly through the staged path
    res = list(pre.run(_stream(25, 2)))
    assert sum(r.n_kept for r in res) > 0


# ------------------------------------------------------------ autotuner

def test_autotuner_feasible_for_every_pow2_bucket():
    """best_config returns a VMEM-feasible candidate for every pow2
    survivor bucket at the production chunk size, and a timed autotune
    pass caches a winner that best_config then returns."""
    S5 = cfg.final_split_samples
    cap = 36
    buckets = sorted({SCHED.quantize_survivors(n, cap, 1, "pow2")
                      for n in range(1, cap + 1)})
    for rows in buckets:
        tc = FTO.best_config(rows, S5, cfg)
        assert tc in FTO.CANDIDATES
        assert FTO.vmem_bytes(tc, S5, cfg.stft_window, cfg.stft_hop) \
            <= FTO.VMEM_BUDGET
    # timed probe on a small shape (ref backend: one probe, cached)
    FTO.clear_tuning()
    wave = _small_wave(B=8, seed=5)
    idx = jnp.asarray([0, 3, 5, 9], jnp.int32)
    with backend.use("ref"):
        tc = FTO.autotune(wave, idx, cfg, reps=1)
        assert tc in FTO.CANDIDATES
        assert FTO.best_config(4, wave.shape[1], cfg) == tc
    FTO.clear_tuning()


def test_vmem_model_monotone_in_frame_block():
    S5 = cfg.final_split_samples
    sizes = [FTO.vmem_bytes(FTO.TailConfig(fb), S5)
             for fb in (1, 2, 4, 8)]
    assert sizes == sorted(sizes)
    assert FTO.vmem_bytes(FTO.TailConfig(1), S5, hpf=True) \
        > FTO.vmem_bytes(FTO.TailConfig(1), S5, hpf=False)


def test_tail_geometry_matches_staged_padding():
    from repro.kernels.stft_dft import ops as SO
    for S in (cfg.final_split_samples, 16_640, 33_000):
        x = jnp.zeros((1, S), jnp.float32)
        n_tiles, S_pad, F, Fv = FTK.tail_geometry(S, cfg.stft_window,
                                                  cfg.stft_hop)
        assert SO.pad_for_stft(x, cfg.stft_window, cfg.stft_hop).shape[1] \
            == S_pad
        assert F == n_tiles * 128
        assert Fv == (S - cfg.stft_window) // cfg.stft_hop + 1
