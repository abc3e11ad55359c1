"""Public wrapper for the STFT kernel.

Backend dispatch (repro.kernels.backend): compiled Pallas on TPU, jnp-FFT ref
on CPU, interpret-mode Pallas for kernel correctness tests. Functions are
plain (not jit'd) — they compose inside the pipeline's jit regions.
"""
import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.stft_dft import kernel as K
from repro.kernels.stft_dft import ref as R


def pad_for_stft(x, window=256, hop=128):
    """Right-pad (B,S) so the kernel's frame count is tile-aligned."""
    B, S = x.shape
    tile_span = K.FRAME_TILE * hop
    tail = window - hop
    n_tiles = max(1, -(-(S - tail) // tile_span))
    target = n_tiles * tile_span + tail
    if target > S:
        x = jnp.pad(x, ((0, 0), (0, target - S)))
    return x


def stft(x, window=256, hop=128):
    """x: (B,S) -> complex (B,F,bins). S must satisfy the kernel tiling
    (use pad_for_stft)."""
    use_pallas, interp = backend.resolve()
    if backend.matmul_dft():
        return R.stft_matmul(x, window, hop)
    if not use_pallas:
        return R.stft_ref(x, window, hop)
    bins = window // 2 + 1
    packed = K.stft_pallas(x, window, hop, interpret=interp)
    return jax.lax.complex(packed[..., :bins], packed[..., bins:2 * bins])


def istft(z, n_samples, window=256, hop=128):
    """Inverse STFT (overlap-add; matmul inverse-DFT under mode "matmul")."""
    if backend.matmul_dft():
        return R.istft_matmul(z, n_samples, window, hop)
    return R.istft_ref(z, n_samples, window, hop)
