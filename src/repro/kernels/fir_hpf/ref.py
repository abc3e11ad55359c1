"""Pure-jnp oracle for the fused FIR filter + decimation kernel.

Tap design: windowed-sinc. The pipeline's "downsample then high-pass" pair
(two SoX passes in the paper) is fused into ONE band-pass FIR applied at the
source rate with stride-2 decimation: h = lowpass(f_nyq_target) - lowpass(f_hp).
"""
import jax
import jax.numpy as jnp
import numpy as np


def _lowpass_taps(cutoff_norm, n_taps):
    """Windowed-sinc lowpass; cutoff_norm = f_c / f_s (0..0.5)."""
    m = np.arange(n_taps) - (n_taps - 1) / 2.0
    h = 2.0 * cutoff_norm * np.sinc(2.0 * cutoff_norm * m)
    h *= np.hamming(n_taps)
    return h / h.sum()


def highpass_taps(cutoff_hz, rate_hz, n_taps=129):
    """Spectral-inversion highpass (delta - lowpass)."""
    h = -_lowpass_taps(cutoff_hz / rate_hz, n_taps)
    h[(n_taps - 1) // 2] += 1.0
    return np.asarray(h, np.float32)


def bandpass_decimate_taps(f_lo_hz, f_hi_hz, rate_hz, n_taps=129):
    """Band-pass taps for fused HPF + anti-alias decimation (at source rate)."""
    h = _lowpass_taps(f_hi_hz / rate_hz, n_taps) - _lowpass_taps(
        f_lo_hz / rate_hz, n_taps)
    return np.asarray(h, np.float32)


def fir_ref(x, taps, stride=1):
    """Causal FIR + decimation oracle. x: (B,S) -> (B, S//stride).

    y[n] = sum_k h[k] * x[n*stride - k]  (x zero-padded on the left)."""
    taps = jnp.asarray(taps, jnp.float32)
    T = taps.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (T - 1, 0)))
    out = jax.lax.conv_general_dilated(
        xp[:, None, :], jnp.flip(taps)[None, None, :],
        window_strides=(stride,), padding="VALID",
        dimension_numbers=("NCH", "OIH", "NCH"),
        precision=jax.lax.Precision.HIGHEST)   # float32 on every backend
    return out[:, 0, :x.shape[1] // stride]
