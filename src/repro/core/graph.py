"""Composable stage graph for the preprocessing pipeline.

The paper derives ONE stage order from per-stage profiling; its own ablations
(stage reordering, split-length sweeps, per-stage on/off) perturb that order.
Here the order is *data*: `AudioPipelineConfig.stages` names a sequence of
registered stages, and `PipelineGraph` builds + shape-validates the chain at
construction time, long before any audio is traced.

Three layers:

  * `Stage` — a named, config-carrying transform over a `state` dict of
    batched arrays.  Each stage declares what fields it needs (wave / spec /
    power / masks) and how it transforms the chunk geometry
    (`ChunkGeom(split_s, rate_hz, channels)`), so an ill-typed order —
    splitting 5 s chunks into 15 s ones, running the band-stop without an
    STFT, MMSE on stereo — raises `GraphValidationError` at build time.
  * `STAGES` — the registry. `@register` adds a stage class under its name;
    configs refer to stages purely by name.
  * `PipelineGraph` — validates the chain, records `removal_point` markers
    (the early-exit candidates: the GRAPH, not the driver, decides where host
    compaction may occur), and exposes the three traced entry points the
    execution plans jit: `detection` (up to the first removal point),
    `tail` (after it — the survivor phase), and `fused` (straight through,
    masked output).

State fields carried between stages:
  wave            (B, S) mono — or (B, C, S) stereo before `to_mono`
  spec, power     (B, F, K) current-granularity spectra (power is
                  pre-band-stop, as in the paper: indices see raw spectra)
  indices         lazily computed acoustic-index dict, shared by detectors
  rain, silence   (B,) per-chunk removal masks (repeated across splits)
  cicada          (B,) detection-granularity cicada mask (diagnostic)
  keep            (B,) frozen at the removal point

Mask semantics follow the paper: cicada gates on ~rain, silence gates on
~rain, keep = ~rain & ~silence.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import detect as D
from repro.core import indices as I
from repro.core import stages as S
from repro.distributed.sharding import NULL_RULES
from repro.kernels.fused_tail import ops as fused_tail_ops


class GraphValidationError(ValueError):
    """A stage list that cannot execute: unknown stage, geometry mismatch,
    or a stage whose inputs are not produced upstream."""


@dataclass(frozen=True)
class ChunkGeom:
    """Chunk geometry flowing through the graph."""
    split_s: float      # seconds of audio per chunk
    rate_hz: int        # sample rate
    channels: int       # 2 = stereo source, 1 = mono


@dataclass(frozen=True)
class _ValidState:
    """Build-time twin of the runtime state dict: geometry + which state
    fields exist at this point in the chain."""
    geom: ChunkGeom
    has: frozenset


@jax.tree_util.register_dataclass
@dataclass
class PipelineOutput:
    wave5: jnp.ndarray          # (N5, S5) processed final chunks
    keep: jnp.ndarray           # (N5,) bool — survives to output
    rain: jnp.ndarray           # (N5,) bool
    silence: jnp.ndarray        # (N5,) bool
    cicada15: jnp.ndarray       # (N15,) bool — per detect chunk
    stats: dict


# --------------------------------------------------------------- registry

STAGES: dict[str, type] = {}


def register(cls):
    """Register a Stage class under its `name` for config-by-name lookup."""
    if cls.name in STAGES:
        raise ValueError(f"duplicate stage name {cls.name!r}")
    STAGES[cls.name] = cls
    return cls


class Stage:
    """One named pipeline transform. Subclasses set `name`, implement
    `check` (build-time: validate + advance the _ValidState) and `apply`
    (trace-time: transform the state dict)."""
    name: str = ""
    removal_point = False

    def __init__(self, cfg):
        self.cfg = cfg

    def _need(self, vs: _ValidState, *fields):
        missing = [f for f in fields if f not in vs.has]
        if missing:
            raise GraphValidationError(
                f"stage '{self.name}' needs {missing} which no upstream "
                f"stage provides (available: {sorted(vs.has)})")

    def check(self, vs: _ValidState) -> _ValidState:
        return vs

    def apply(self, state: dict, rules) -> dict:
        return state


def _indices(state, cfg):
    """Acoustic indices over the current power spectra, computed once and
    shared by every detector stage (the paper's 'FFT executed once' economy
    extends to the index vector)."""
    if "indices" not in state:
        state["indices"] = I.all_indices(state["power"], cfg)
    return state["indices"]


_MASK_KEYS = ("rain", "silence", "keep")
# per-chunk state fields a phase hands back (spectra and indices stay local)
_OUT_KEYS = ("wave", "cicada") + _MASK_KEYS


def chunk_split(rules):
    """(mesh axes, device count) that `rules` split the "chunks" axis
    over; (None, 1) without a mesh or a chunk rule."""
    axes = rules.spec("chunks")[0] if rules.mesh is not None else None
    if axes is None:
        return None, 1
    names = axes if isinstance(axes, tuple) else (axes,)
    return axes, math.prod(rules.mesh.shape[a] for a in names)


def check_split(n_chunks, rules):
    """Raise unless a batch of `n_chunks` splits evenly over the chunk
    axes of `rules` (pad the batch to a multiple: `pad_multiple=`)."""
    axes, n = chunk_split(rules)
    if n_chunks % n:
        raise ValueError(f"a batch of {n_chunks} chunks does not split over "
                         f"the {n} devices of the chunk axes {axes}")


def per_chunk(rules, fn, *args, replicated=()):
    """Run `fn` on every device's slice of the chunk batch.

    Without a mesh (or on one device) this is `fn(*args)`. When `rules`
    shard "chunks" over several devices, XLA cannot partition the Pallas
    kernels inside the stage chain, so the chain runs under `shard_map`:
    dim 0 of each argument is split over the chunk axes and every output
    leaf is reassembled along dim 0. An argument whose position is in
    `replicated` enters split like the others and is then gathered whole
    on every device, by one explicit all-gather under the `gather` scope,
    so the exchange has a name in the HLO and in a device trace. The
    stages then see NULL_RULES."""
    axes, n = chunk_split(rules)
    if n == 1:
        return fn(*args)
    for a in args:
        check_split(a.shape[0], rules)

    def local(*xs):
        with jax.named_scope("gather"):
            xs = [jax.lax.all_gather(x, axes, tiled=True)
                  if i in replicated else x for i, x in enumerate(xs)]
        return fn(*xs)
    return jax.shard_map(local, mesh=rules.mesh,
                         in_specs=(P(axes),) * len(args),
                         out_specs=P(axes), check_vma=False)(*args)


# ----------------------------------------------------------------- stages

@register
class ToMono(Stage):
    name = "to_mono"

    def check(self, vs):
        self._need(vs, "wave")
        if vs.geom.channels < 2:
            raise GraphValidationError(
                "stage 'to_mono' expects multi-channel input "
                f"(got {vs.geom.channels} channel)")
        return replace(vs, geom=replace(vs.geom, channels=1))

    def apply(self, state, rules):
        state["wave"] = rules.constrain(S.to_mono(state["wave"]),
                                        "chunks", None)
        return state


@register
class Compress(Stage):
    """Fused downsample + high-pass (the paper's 44.1 -> 22.05 kHz + 1 kHz
    HPF, one Pallas band-pass FIR)."""
    name = "compress"

    def check(self, vs):
        self._need(vs, "wave")
        if vs.geom.channels != 1:
            raise GraphValidationError(
                "stage 'compress' needs mono audio — add 'to_mono' first")
        if vs.geom.rate_hz != self.cfg.source_rate_hz:
            raise GraphValidationError(
                f"stage 'compress' expects {self.cfg.source_rate_hz} Hz "
                f"input, got {vs.geom.rate_hz} Hz (already compressed?)")
        return replace(vs, geom=replace(vs.geom,
                                        rate_hz=self.cfg.target_rate_hz))

    def apply(self, state, rules):
        state["wave"] = S.compress(state["wave"], self.cfg)
        return state


class _Split(Stage):
    """(B, S) -> (B*n, S/n). Repeats per-chunk masks, regroups the shared
    power spectra (the paper's 'files can only be split, not joined'), and
    drops the now-stale complex spectra + index vector."""
    target_split_s: float = 0.0

    def check(self, vs):
        self._need(vs, "wave")
        if vs.geom.channels != 1:
            raise GraphValidationError(
                f"stage '{self.name}' needs mono audio")
        factor = vs.geom.split_s / self.target_split_s
        if abs(factor - round(factor)) > 1e-9 or round(factor) < 1:
            raise GraphValidationError(
                f"stage '{self.name}' cannot split {vs.geom.split_s:g} s "
                f"chunks into {self.target_split_s:g} s chunks "
                f"(non-integer factor {factor:g})")
        self.n_sub = int(round(factor))
        return replace(vs, geom=replace(vs.geom,
                                        split_s=self.target_split_s),
                       has=vs.has - {"spec", "indices"})

    def apply(self, state, rules):
        n = self.n_sub
        pre_samples = state["wave"].shape[1]
        state["wave"] = rules.constrain(S.split(state["wave"], n),
                                        "chunks", None)
        for k in _MASK_KEYS:
            if k in state:
                state[k] = jnp.repeat(state[k], n)
        if "power" in state:
            state["power"] = S.group_frames(state["power"], n,
                                            pre_samples, self.cfg)
        state.pop("spec", None)
        state.pop("indices", None)
        return state


@register
class SplitDetect(_Split):
    name = "split_detect"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.target_split_s = cfg.detect_split_s


@register
class SplitFinal(_Split):
    name = "split_final"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.target_split_s = cfg.final_split_s


@register
class Stft(Stage):
    """STFT once per chunk; spectra are shared by every downstream detector."""
    name = "stft"

    def check(self, vs):
        self._need(vs, "wave")
        if vs.geom.channels != 1:
            raise GraphValidationError("stage 'stft' needs mono audio")
        return replace(vs, has=vs.has | {"spec", "power"})

    def apply(self, state, rules):
        spec, power = S.stft_chunks(state["wave"], self.cfg)
        state["spec"], state["power"] = spec, power
        state.pop("indices", None)
        return state


@register
class DetectRain(Stage):
    """Rain removal mask (C4.5-derived rule over acoustic indices)."""
    name = "detect_rain"

    def check(self, vs):
        self._need(vs, "power")
        return replace(vs, has=vs.has | {"rain"})

    def apply(self, state, rules):
        rain = D.detect_rain(_indices(state, self.cfg), self.cfg)
        prev = state.get("rain")
        state["rain"] = rain if prev is None else (prev | rain)
        return state


@register
class CicadaBandstop(Stage):
    """Cicada detection + band-stop around the chorus peak (gated on ~rain,
    as in the paper: rain chunks are deleted, not filtered)."""
    name = "cicada_bandstop"

    def check(self, vs):
        self._need(vs, "spec", "power")
        return replace(vs, has=vs.has | {"cicada"})

    def apply(self, state, rules):
        idx = _indices(state, self.cfg)
        cicada = D.detect_cicada(idx, self.cfg)
        if "rain" in state:
            cicada = cicada & ~state["rain"]
        state["cicada"] = cicada
        state["spec"] = S.remove_cicada_band(
            state["spec"], idx["cicada_peak_bin"], cicada, self.cfg)
        return state


@register
class Istft(Stage):
    name = "istft"

    def check(self, vs):
        self._need(vs, "wave", "spec")
        return vs

    def apply(self, state, rules):
        state["wave"] = S.istft_chunks(state["spec"],
                                       state["wave"].shape[1], self.cfg)
        return state


@register
class DetectSilence(Stage):
    """Silence removal mask: envelope SNR under the paper's 'lower
    threshold', gated on ~rain."""
    name = "detect_silence"

    def check(self, vs):
        self._need(vs, "power")
        return replace(vs, has=vs.has | {"silence"})

    def apply(self, state, rules):
        silence = I.snr_est(state["power"]) < \
            self.cfg.silence_snr_threshold
        if "rain" in state:
            silence = silence & ~state["rain"]
        prev = state.get("silence")
        state["silence"] = silence if prev is None else (prev | silence)
        return state


@register
class DetectFlux(Stage):
    """Spectral-flux energy detector (Stowell-style): chunks whose peak
    half-wave-rectified flux stays under `cfg.flux_threshold` carry no
    transient vocalisation and are marked for removal (folded into the
    silence mask, gated on ~rain like every removal detector). A drop-in
    alternative — or complement — to 'detect_silence', selectable purely
    via `cfg.stages` / the `stages=` override; no executor knows it
    exists."""
    name = "detect_flux"

    def check(self, vs):
        self._need(vs, "power")
        return replace(vs, has=vs.has | {"silence"})

    def apply(self, state, rules):
        idle = D.detect_no_activity(_indices(state, self.cfg), self.cfg)
        if "rain" in state:
            idle = idle & ~state["rain"]
        prev = state.get("silence")
        state["silence"] = idle if prev is None else (prev | idle)
        return state


@register
class RemovalPoint(Stage):
    """Marker: host compaction may occur HERE. Freezes keep = ~rain &
    ~silence; two-phase plans cut the graph at the first marker. Past a
    removal point only the waveform survives compaction, so downstream
    stages may depend on nothing else (enforced at build time)."""
    name = "removal_point"
    removal_point = True

    def check(self, vs):
        self._need(vs, "wave")
        return _ValidState(vs.geom, frozenset({"wave"}))

    def apply(self, state, rules):
        n = state["wave"].shape[0]
        zeros = jnp.zeros((n,), bool)
        state["keep"] = (~state.get("rain", zeros)
                         & ~state.get("silence", zeros))
        return state


@register
class Mmse(Stage):
    """MMSE-STSA denoise — the dominant stage, placed after the removal
    point so execution plans can run it on survivors only."""
    name = "mmse"

    def check(self, vs):
        self._need(vs, "wave")
        if vs.geom.channels != 1:
            raise GraphValidationError("stage 'mmse' needs mono audio")
        return vs

    def apply(self, state, rules):
        wave = rules.constrain(state["wave"], "chunks", None)
        state["wave"] = S.mmse_denoise(wave, self.cfg)
        return state


@register
class TailHighpass(Stage):
    """Stride-1 FIR high-pass on the survivor tail. The paper applies the
    HPF once at long splits (folded into `compress`); declaring this stage
    past the removal point re-sharpens survivors at the target rate and
    completes the canonical fused tail hpf -> stft -> mmse -> istft."""
    name = "hpf"

    def check(self, vs):
        self._need(vs, "wave")
        if vs.geom.channels != 1:
            raise GraphValidationError("stage 'hpf' needs mono audio")
        return vs

    def apply(self, state, rules):
        wave = rules.constrain(state["wave"], "chunks", None)
        state["wave"] = S.tail_highpass(wave, self.cfg)
        return state


# ------------------------------------------------------------------ graph

class PipelineGraph:
    """A validated stage chain built from a config-declared stage list.

    `stage_names` defaults to `cfg.stages` — the paper's order lives in the
    config as data, so ablations (reorder, drop a detector, move the removal
    point) are config edits, not driver forks.
    """

    def __init__(self, cfg, stage_names=None, source_channels=2):
        self.cfg = cfg
        self.names = tuple(stage_names if stage_names is not None
                           else cfg.stages)
        unknown = [n for n in self.names if n not in STAGES]
        if unknown:
            raise GraphValidationError(
                f"unknown stages {unknown}; registered: {sorted(STAGES)}")
        self.stages = [STAGES[n](cfg) for n in self.names]
        self.source_geom = ChunkGeom(cfg.long_split_s, cfg.source_rate_hz,
                                     source_channels)
        self.removal_indices: list[int] = []
        vs = _ValidState(self.source_geom, frozenset({"wave"}))
        for i, st in enumerate(self.stages):
            try:
                vs = st.check(vs)
            except GraphValidationError as e:
                raise GraphValidationError(
                    f"stage {i} ({st.name!r}): {e}") from None
            if st.removal_point:
                self.removal_indices.append(i)
        self.out_geom = vs.geom

    @property
    def fingerprint(self):
        """Stable hashable identity for compile-cache keying."""
        return (self.cfg, self.names, self.source_geom)

    @property
    def has_removal_point(self) -> bool:
        return bool(self.removal_indices)

    def _cut(self) -> int:
        """Index one past the first removal point (= len when none)."""
        if not self.removal_indices:
            return len(self.stages)
        return self.removal_indices[0] + 1

    def _run(self, stages, state, rules):
        for st in stages:
            with jax.named_scope(st.name):     # the stage's ops, by name
                state = st.apply(state, rules)
        return state

    def _run_sharded(self, stages, wave, rules):
        """`_run` from a bare wave, per device under `per_chunk`; returns
        the per-chunk fields of the final state."""
        def local(w):
            state = self._run(stages, {"wave": w}, NULL_RULES if
                              rules.mesh is not None else rules)
            return {k: state[k] for k in _OUT_KEYS if k in state}
        return per_chunk(rules, local, wave)

    def _outputs(self, state) -> PipelineOutput:
        wave = state["wave"]
        n = wave.shape[0]
        zeros = jnp.zeros((n,), bool)
        rain = state.get("rain", zeros)
        silence = state.get("silence", zeros)
        keep = state.get("keep", ~rain & ~silence)
        cicada = state.get("cicada", zeros)
        stats = {
            "n_chunks5": n,
            "frac_rain": jnp.mean(rain.astype(jnp.float32)),
            "frac_silence": jnp.mean(silence.astype(jnp.float32)),
            "frac_kept": jnp.mean(keep.astype(jnp.float32)),
            "frac_cicada15": jnp.mean(cicada.astype(jnp.float32)),
        }
        return PipelineOutput(wave5=wave, keep=keep, rain=rain,
                              silence=silence, cicada15=cicada, stats=stats)

    # Traced entry points (jit-able; plans own the jitting + caching).
    def detection(self, audio, rules=NULL_RULES) -> PipelineOutput:
        """Phase A: everything up to (and including) the first removal
        point — wave5 is not yet denoised. A graph that declares NO
        removal point has no phase split: this runs the whole chain
        (including any denoise stages)."""
        return self._outputs(self._run_sharded(self.stages[:self._cut()],
                                               audio, rules))

    def tail(self, wave, rules=NULL_RULES):
        """Phase B: the survivor stages past the first removal point,
        applied to a (compacted) chunk batch."""
        return self._run_sharded(self.stages[self._cut():], wave,
                                 rules)["wave"]

    def tail_indexed(self, wave, idx, rules=NULL_RULES):
        """Phase B with DEVICE-RESIDENT compaction: gather the survivor
        rows `idx` (padded int32, static shape) out of the full
        pre-denoise batch on device, then run the survivor stages. The
        host only ever supplies the tiny index vector — the waveform never
        round-trips. Out-of-range indices (the pad convention of
        `scheduler.survivor_indices`) become all-zero rows via the fill
        gather, so padding never duplicates real audio."""
        batch = jnp.take(wave, idx, axis=0, mode="fill", fill_value=0.0)
        return self.tail(batch, rules)

    @property
    def fused_tail_spec(self):
        """`{"hpf": bool}` when the post-removal stage list is the
        canonical fused tail — `("mmse",)` or `("hpf", "mmse")`, i.e.
        [HPF ->] STFT -> MMSE gain -> iSTFT on survivors only — else
        None. Plans consult this to decide whether `tail_indexed_fused`
        may replace `tail_indexed` (any other survivor chain falls back
        to the staged path)."""
        if not self.removal_indices:
            return None
        post = self.names[self._cut():]
        if post == ("mmse",):
            return {"hpf": False}
        if post == ("hpf", "mmse"):
            return {"hpf": True}
        return None

    def tail_indexed_fused(self, wave, idx, rules=NULL_RULES):
        """`tail_indexed` through the single fused Pallas pass
        (kernels/fused_tail): gather-compact + [HPF] + STFT + MMSE gain
        happen in one VMEM-resident kernel, with only the iSTFT outside.
        Equal to `tail_indexed` within float32 rounding (bitwise in ref
        mode); only valid when `fused_tail_spec` is not None."""
        spec = self.fused_tail_spec
        if spec is None:
            raise GraphValidationError(
                f"post-removal stages {self.names[self._cut():]} are not "
                "the canonical fused tail; use tail_indexed")
        # on a mesh every device cleans an equal share of the survivor
        # list out of the whole batch (all-gathered to each device once,
        # `fused_tail/gather`), so survivor imbalance between the devices'
        # own chunks costs nothing. Scoped `fused_tail`; inside it the
        # kernel path scopes the Pallas call (`kernel`) apart from the XLA
        # resynthesis (`istft`).
        with jax.named_scope("fused_tail"):
            return per_chunk(
                rules, lambda w, i: fused_tail_ops.fused_tail(
                    w, i, self.cfg, hpf=spec["hpf"]),
                wave, idx, replicated=(0,))

    def fused(self, audio, rules=NULL_RULES) -> PipelineOutput:
        """Single-trace mode: the whole chain, removed chunks masked but
        still computed (the paper's no-early-exit baseline)."""
        out = self._outputs(self._run_sharded(self.stages, audio, rules))
        masked = jnp.where(out.keep[:, None], out.wave5, 0.0)
        return PipelineOutput(wave5=masked, keep=out.keep, rain=out.rain,
                              silence=out.silence, cicada15=out.cicada15,
                              stats=out.stats)
