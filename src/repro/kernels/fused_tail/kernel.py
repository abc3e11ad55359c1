"""Pallas TPU kernel: the fused survivor tail in one VMEM-resident pass.

One `pallas_call` over grid (survivor_rows,) performs, per grid step:

  gather-compact   the padded survivor-index vector rides as a SCALAR
                   PREFETCH argument (`pltpu.PrefetchScalarGridSpec`), so
                   the input BlockSpec's index_map DMAs exactly the one
                   survivor row this step needs straight out of the full
                   pre-denoise batch — the compacted batch is never
                   materialised in HBM. Out-of-range pad indices are
                   clamped for the DMA and their output is written as
                   zeros without computing, preserving
                   `jnp.take(mode="fill")`'s zero-row pad convention.
  FIR high-pass    (optional) fir_hpf's banded-matmul formulation at
                   stride 1, chunk by chunk into a VMEM scratch row.
  STFT             stft_dft's 50%-overlap framing (rows f and f + 1 of
                   the 128-lane row view) and windowed matmul-DFT,
                   `frame_block` FRAME_TILE tiles of frames per dispatch.
  MMSE-STSA        the sequential-over-frames decision-directed recurrence
                   of mmse_stsa's kernel (`frame_gain`), one (1, KP) frame
                   row of every bin per step.
  gain apply       the filtered spectrum re*g / im*g is written in place
                   into the output block; power, noise and gain never
                   leave VMEM.

Layout: the row of S samples is viewed as (F + 8, 128) — the STFT's padded
length plus one 8-row block, so frame f + 1's rows exist for every frame —
and every value in the kernel is 2-D with 128-lane-aligned slices. The
spectrum is packed as [re | im], each half padded from `bins` to KP lanes
(KP = bins rounded up to 128), so both halves start on a lane tile and a
frame row is read and written whole (Mosaic has no dynamic-row load of a
lane slice).

Only the inverse-DFT overlap-add resynthesis stays OUTSIDE the kernel
(`finish`, the irfft-based istft the staged tail runs), so the kernel hands
the one (rows, F, 2 * KP) filtered spectrum across the HBM boundary instead
of the gathered wave, the raw spectrum, the power, the noise and the gain
arrays the staged tail streams between its dispatches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fir_hpf.kernel import LANES, prev_rows, toeplitz_taps
from repro.kernels.fir_hpf.ref import highpass_taps
from repro.kernels.mmse_stsa.kernel import frame_gain
from repro.kernels.stft_dft.kernel import FRAME_TILE, HIGHEST, dft_basis_np
from repro.kernels.stft_dft.ref import istft_ref


def tail_geometry(S, window=256, hop=128):
    """(n_tiles, S_pad, F, Fv) for an S-sample row: the tiling that
    pad_for_stft + stft_pallas produce, shared by kernel and autotuner."""
    tile_span = FRAME_TILE * hop
    tail = window - hop
    n_tiles = max(1, -(-(S - tail) // tile_span))
    return (n_tiles, n_tiles * tile_span + tail, n_tiles * FRAME_TILE,
            (S - window) // hop + 1)


def spectrum_lanes(bins):
    """KP: the lanes each of the re / im halves of the packed spectrum
    takes — `bins` rounded up to whole 128-lane tiles."""
    return -(-bins // LANES) * LANES


def split_basis(window, kp):
    """(window, 2 * kp) windowed DFT basis: [cos | pad | -sin | pad]."""
    bins = window // 2 + 1
    b = dft_basis_np(window)
    out = np.zeros((window, 2 * kp), np.float32)
    out[:, :bins] = b[:, :bins]
    out[:, kp:kp + bins] = b[:, bins:2 * bins]
    return jnp.asarray(out)


def _fused_tail_kernel(idx_ref, x_ref, basis_ref, *rest, n_rows_in, S, bins,
                       F, Fv, kp, alpha, gain_floor, noise_frames, hpf,
                       block_rows):
    if hpf:
        mc_ref, mp_ref, o_ref, pow_ref, sig_ref = rest
    else:
        o_ref, pow_ref = rest
        sig_ref = x_ref
    valid = idx_ref[pl.program_id(0)] < n_rows_in

    @pl.when(jnp.logical_not(valid))
    def _pad_slot():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(valid)
    def _survivor():
        n_rows = F + 8
        if hpf:
            # causal stride-1 FIR in banded-matmul form; positions past S
            # are zeroed, as the staged tail zero-pads the FILTERED row
            for a in range(0, n_rows, block_rows):
                m = min(block_rows, n_rows - a)
                cur = x_ref[a:a + m, :]
                first = (jnp.zeros((1, LANES), jnp.float32) if a == 0
                         else x_ref[a - 1:a, :])
                y = (jnp.dot(cur, mc_ref[...], precision=HIGHEST,
                             preferred_element_type=jnp.float32)
                     + jnp.dot(prev_rows(cur, first), mp_ref[...],
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32))
                pos = ((a + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0))
                       * LANES
                       + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1))
                sig_ref[a:a + m, :] = jnp.where(pos < S, y, 0.0)

        # framing + windowed DFT: frame f is rows f and f + 1
        basis = basis_ref[...]
        hop = LANES
        for a in range(0, F, block_rows):
            m = min(block_rows, F - a)
            blk = sig_ref[a:a + m + 8, :]
            nxt = pltpu.roll(blk, m + 7, 0)[:m]
            packed = (jnp.dot(blk[:m], basis[:hop], precision=HIGHEST,
                              preferred_element_type=jnp.float32)
                      + jnp.dot(nxt, basis[hop:], precision=HIGHEST,
                                preferred_element_type=jnp.float32))
            o_ref[a:a + m, :] = packed
            re, im = packed[:, :kp], packed[:, kp:]
            pow_ref[a:a + m, :] = re * re + im * im

        # noise PSD: mean power of the first frames; pad lanes get 1.0
        nf = min(noise_frames, Fv)
        head = pow_ref[0:-(-nf // 8) * 8, :]
        row = jax.lax.broadcasted_iota(jnp.int32, head.shape, 0)
        noise = jnp.sum(jnp.where(row < nf, head, 0.0), axis=0,
                        keepdims=True) / nf
        lane = jax.lax.broadcasted_iota(jnp.int32, noise.shape, 1)
        noise = jnp.where(lane < bins, noise, 1.0)

        # decision-directed recurrence over the valid frames, the gain
        # applied in place to both halves of the packed spectrum row
        inv_lam = 1.0 / jnp.maximum(noise, 1e-10)

        def step(t, a2_prev):
            g, a2 = frame_gain(pow_ref[pl.ds(t, 1), :], inv_lam, a2_prev,
                               alpha)
            g = jnp.maximum(g, gain_floor)
            o_ref[pl.ds(t, 1), :] = (o_ref[pl.ds(t, 1), :]
                                     * jnp.concatenate([g, g], axis=1))
            return a2

        jax.lax.fori_loop(0, Fv, step, jnp.ones((1, kp), jnp.float32))


def fused_tail_pallas(wave, idx, cfg, hpf=False, frame_block=2,
                      interpret=False, vmem_limit_bytes=None):
    """wave: (B, S) f32 pre-denoise batch; idx: (R,) padded int32 survivor
    indices. Returns the packed gain-filtered spectrum (R, F, 2 * KP) —
    feed to `finish` for the overlap-add resynthesis."""
    B, S = wave.shape
    R = idx.shape[0]
    window, hop = cfg.stft_window, cfg.stft_hop
    if hop * 2 != window or hop != LANES:
        raise ValueError("the fused tail needs 50% overlap and a "
                         f"{LANES}-sample hop (window={window}, hop={hop})")
    bins = window // 2 + 1
    _, _, F, Fv = tail_geometry(S, window, hop)
    kp = spectrum_lanes(bins)
    n_rows = F + 8
    xr = jnp.pad(wave.astype(jnp.float32),
                 ((0, 0), (0, n_rows * hop - S))).reshape(B, n_rows, hop)
    kernel = functools.partial(
        _fused_tail_kernel, n_rows_in=B, S=S, bins=bins, F=F, Fv=Fv, kp=kp,
        alpha=cfg.mmse_alpha, gain_floor=cfg.mmse_gain_floor,
        noise_frames=cfg.noise_est_frames, hpf=hpf,
        block_rows=int(frame_block) * FRAME_TILE)
    fixed = lambda r, idx_ref: (0, 0)
    in_specs = [
        # the gather: this step's survivor row, clamped for the DMA
        # (pad slots write zeros without reading it)
        pl.BlockSpec((None, n_rows, hop),
                     lambda r, idx_ref: (jnp.minimum(idx_ref[r], B - 1),
                                         0, 0)),
        pl.BlockSpec((window, 2 * kp), fixed),
    ]
    args = [xr, split_basis(window, kp)]
    scratch = [pltpu.VMEM((F, kp), jnp.float32)]
    if hpf:
        mc, mp = toeplitz_taps(highpass_taps(
            cfg.hpf_cutoff_hz, cfg.target_rate_hz, cfg.hpf_taps), 1)
        in_specs += [pl.BlockSpec((LANES, LANES), fixed)] * 2
        args += [mc, mp]
        scratch.append(pltpu.VMEM((n_rows, hop), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, F, 2 * kp),
                               lambda r, idx_ref: (r, 0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, F, 2 * kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="fused_tail",
    )(idx.astype(jnp.int32), *args)


def finish(packed, S, cfg):
    """Inverse-DFT overlap-add resynthesis of the kernel's packed filtered
    spectrum: complexify, slice the valid frames, irfft-OLA — the same
    istft_ref every staged non-matmul mode runs."""
    bins = cfg.stft_window // 2 + 1
    kp = packed.shape[-1] // 2
    Fv = (S - cfg.stft_window) // cfg.stft_hop + 1
    z = jax.lax.complex(packed[..., :bins], packed[..., kp:kp + bins])
    return istft_ref(z[:, :Fv], S, cfg.stft_window, cfg.stft_hop)
