"""Pure-jnp oracle for the STFT kernel (framing + Hamming + real DFT).

Uses jnp.fft.rfft — deliberately a different computational path than the
kernel's matmul-DFT, so the allclose sweep is a real cross-check.
"""
import jax
import jax.numpy as jnp
import numpy as np


def hamming(n):
    return 0.54 - 0.46 * np.cos(2.0 * np.pi * np.arange(n) / (n - 1))


def num_frames(n_samples, window, hop):
    return (n_samples - window) // hop + 1


def frame(x, window, hop):
    """x: (..., S) -> (..., F, window).

    For the 50%-overlap case the even/odd frames are two CONTIGUOUS
    reshapes interleaved — no gather. This matters under GSPMD: a gather
    over the sharded chunk-batch dim made XLA all-gather entire
    spectrogram-sized tensors (EXPERIMENTS.md §Perf, pipeline iter 1);
    reshapes/slices stay local."""
    F = num_frames(x.shape[-1], window, hop)
    if 2 * hop == window:
        lead = x.shape[:-1]
        n_even = (F + 1) // 2
        n_odd = F - n_even
        even = x[..., :n_even * window].reshape(*lead, n_even, window)
        odd = x[..., hop:hop + n_odd * window].reshape(*lead, n_odd, window)
        if n_odd < n_even:
            odd = jnp.concatenate(
                [odd, jnp.zeros((*lead, 1, window), x.dtype)], axis=-2)
        out = jnp.stack([even, odd], axis=-2).reshape(*lead, -1, window)
        return out[..., :F, :]
    idx = np.arange(F)[:, None] * hop + np.arange(window)[None, :]
    return x[..., idx]


def stft_ref(x, window=256, hop=128):
    """x: (B, S) f32 -> (B, F, window//2+1) complex64."""
    frames = frame(x, window, hop) * jnp.asarray(hamming(window), x.dtype)
    return jnp.fft.rfft(frames, axis=-1)


# --------------------------------------------------------- matmul-DFT path
# The TPU target computes the DFT as a matmul on the MXU (kernel.py). These
# pure-jnp equivalents run the SAME computation shape without pallas — used
# by the dry-run (backend mode "matmul") both because they mirror the TPU
# cost profile and because XLA's FFT op is NOT SPMD-partitionable (GSPMD
# all-gathers its operands; EXPERIMENTS.md §Perf pipeline iter 1).
def _fwd_basis(window):
    from repro.kernels.stft_dft.kernel import dft_basis, PAD_OUT
    return dft_basis(window), PAD_OUT


def _inv_basis(window):
    bins = window // 2 + 1
    m_re = np.fft.irfft(np.eye(bins), n=window)
    m_im = np.fft.irfft(1j * np.eye(bins), n=window)
    return jnp.asarray(np.concatenate([m_re, m_im], 0).astype(np.float32))


MATMUL_DTYPE = jnp.bfloat16   # halves the dominant DFT stream bytes
#                               (pipeline §Perf iter 3); detector indices are
#                               ratio-based and tolerate it (test_pipeline).


def stft_matmul(x, window=256, hop=128):
    """frame + windowed-DFT-as-matmul; matches stft_ref to ~1e-6 (f32)."""
    bins = window // 2 + 1
    basis, _ = _fwd_basis(window)
    frames = frame(x, window, hop)
    packed = jnp.einsum("bfw,wk->bfk", frames.astype(MATMUL_DTYPE),
                        basis.astype(MATMUL_DTYPE),
                        preferred_element_type=jnp.float32)
    return jax.lax.complex(packed[..., :bins], packed[..., bins:2 * bins])


def istft_matmul(z, n_samples, window=256, hop=128):
    """OLA inverse with the inverse DFT as a matmul (irfft-free)."""
    assert 2 * hop == window
    w = jnp.asarray(hamming(window), jnp.float32)
    ib = _inv_basis(window)                       # (2*bins, window)
    packed = jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1)
    frames = jnp.einsum("bfk,kw->bfw", packed.astype(MATMUL_DTYPE),
                        ib.astype(MATMUL_DTYPE),
                        preferred_element_type=jnp.float32) * w
    B, F, _ = frames.shape
    n_even = (F + 1) // 2
    n_odd = F - n_even
    even = frames[:, 0::2].reshape(B, -1)
    odd = frames[:, 1::2].reshape(B, -1)
    L = n_even * window + hop
    out = jnp.zeros((B, L), jnp.float32)
    out = out.at[:, :n_even * window].set(even)
    out = out.at[:, hop:hop + n_odd * window].add(odd)
    wn = (hamming(window) ** 2).astype(np.float32)
    norm = np.zeros(L, np.float32)
    norm[:n_even * window] += np.tile(wn, n_even)
    norm[hop:hop + n_odd * window] += np.tile(wn, n_odd)
    out = out[:, :n_samples]
    if L < n_samples:
        out = jnp.pad(out, ((0, 0), (0, n_samples - L)))
        norm = np.pad(norm, (0, n_samples - L))
    return out / jnp.maximum(jnp.asarray(norm[:n_samples]), 1e-8)[None, :]


def istft_ref(z, n_samples, window=256, hop=128):
    """Inverse STFT by windowed overlap-add (50% overlap COLA for Hamming
    needs window-squared normalization).

    Gather/scatter-free for hop == window/2: even and odd frame sets each
    tile the timeline contiguously, so overlap-add is two reshapes and one
    shifted add — local under chunk-batch sharding (see frame())."""
    assert 2 * hop == window, "istft_ref implements the 50%-overlap case"
    w = jnp.asarray(hamming(window), jnp.float32)
    frames = jnp.fft.irfft(z, n=window, axis=-1) * w
    B, F, _ = frames.shape
    n_even = (F + 1) // 2
    n_odd = F - n_even
    even = frames[:, 0::2].reshape(B, -1)          # covers [0, n_even*W)
    odd = frames[:, 1::2].reshape(B, -1)           # covers [hop, ...)
    L = n_even * window + hop
    out = jnp.zeros((B, L), jnp.float32)
    out = out.at[:, :n_even * window].set(even)
    out = out.at[:, hop:hop + n_odd * window].add(odd)
    # per-position window^2 normalization (host-precomputed constant)
    wn = (hamming(window) ** 2).astype(np.float32)
    norm = np.zeros(L, np.float32)
    norm[:n_even * window] += np.tile(wn, n_even)
    norm[hop:hop + n_odd * window] += np.tile(wn, n_odd)
    out = out[:, :n_samples]
    if L < n_samples:
        out = jnp.pad(out, ((0, 0), (0, n_samples - L)))
        norm = np.pad(norm, (0, n_samples - L))
    return out / jnp.maximum(jnp.asarray(norm[:n_samples]), 1e-8)[None, :]
