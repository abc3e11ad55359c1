"""Acoustic indices over STFT power spectra (Bedoya et al. 2017 style).

All functions take `power`: (B, F, K) f32 — F frames, K bins — and return
per-chunk (B,) indices. `freqs(k) = k * rate / window`.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

EPS = 1e-10


def bin_freqs(window=256, rate_hz=22_050):
    return np.arange(window // 2 + 1) * rate_hz / window


def psd_mean(power):
    """Broadband mean power spectral density (log-compressed)."""
    return jnp.log1p(jnp.mean(power, axis=(1, 2)))


def frame_energy(power):
    """Per-frame energy envelope: (B,F)."""
    return jnp.sum(power, axis=-1)


def snr_est(power):
    """Estimated SNR in [0,1): 1 - mean(envelope)/peak(envelope).

    The paper's silence measure: 'peak volume to average volume'. Silence and
    steady rain have flat envelopes (-> ~0); bird calls are peaky (-> ~1)."""
    env = frame_energy(power)
    return jnp.clip(1.0 - jnp.mean(env, axis=1) / (jnp.max(env, axis=1)
                                                   + EPS), 0.0, 1.0)


def spectral_flatness(power):
    """Wiener entropy averaged over frames: geometric/arithmetic mean ratio.
    White-ish noise (rain) -> ~1; tonal signals -> ~0."""
    p = power + EPS
    geo = jnp.exp(jnp.mean(jnp.log(p), axis=-1))
    arith = jnp.mean(p, axis=-1)
    return jnp.mean(geo / arith, axis=1)


def band_energy_ratio(power, lo_hz, hi_hz, window=256, rate_hz=22_050):
    """Fraction of total energy inside [lo_hz, hi_hz]."""
    f = bin_freqs(window, rate_hz)
    band = jnp.asarray((f >= lo_hz) & (f <= hi_hz), power.dtype)
    total = jnp.sum(power, axis=(1, 2)) + EPS
    return jnp.sum(power * band, axis=(1, 2)) / total


def band_peakiness(power, lo_hz, hi_hz, window=256, rate_hz=22_050):
    """Peak-bin to median-bin mean-PSD ratio within a band, plus the peak bin.

    Cicada choruses put sustained narrowband energy in 2.5-8 kHz: high
    peakiness for long fractions of the chunk."""
    f = bin_freqs(window, rate_hz)
    sel = (f >= lo_hz) & (f <= hi_hz)
    psd = jnp.mean(power, axis=1)                    # (B,K)
    band_psd = psd[:, sel]
    peak = jnp.max(band_psd, axis=1)
    med = jnp.median(psd, axis=1) + EPS
    peak_bin = jnp.argmax(band_psd, axis=1) + int(np.argmax(sel))
    return peak / med, peak_bin


def temporal_persistence(power, lo_hz, hi_hz, window=256, rate_hz=22_050,
                         frac=0.5):
    """Fraction of frames whose band energy exceeds frac * broadband energy —
    separates sustained choruses (cicada/rain) from transient calls."""
    f = bin_freqs(window, rate_hz)
    band = jnp.asarray((f >= lo_hz) & (f <= hi_hz), power.dtype)
    be = jnp.sum(power * band, axis=-1)              # (B,F)
    te = jnp.sum(power, axis=-1) + EPS
    return jnp.mean((be / te) > frac, axis=1)


def spectral_flux(power):
    """Onset strength via half-wave-rectified spectral flux (Stowell-style
    energy detection): per frame, sum the positive per-bin power rises from
    the previous frame; report the chunk's PEAK flux relative to its mean
    envelope energy. Transient bird calls spike it (>2); silence, steady
    rain, and sustained choruses keep near-flat spectra (<1)."""
    rise = jnp.maximum(power[:, 1:] - power[:, :-1], 0.0)   # (B, F-1, K)
    peak = jnp.max(jnp.sum(rise, axis=-1), axis=1)
    return peak / (jnp.mean(frame_energy(power), axis=1) + EPS)


def all_indices(power, cfg):
    """The index vector used by the rule classifiers."""
    pk, peak_bin = band_peakiness(power, *cfg.cicada_band_hz,
                                  cfg.stft_window, cfg.target_rate_hz)
    return {
        "psd": psd_mean(power),
        "snr": snr_est(power),
        "flux": spectral_flux(power),
        "flatness": spectral_flatness(power),
        "rain_band": band_energy_ratio(power, *cfg.rain_low_band_hz,
                                       cfg.stft_window, cfg.target_rate_hz),
        "cicada_band": band_energy_ratio(power, *cfg.cicada_band_hz,
                                         cfg.stft_window, cfg.target_rate_hz),
        "cicada_peakiness": pk,
        "cicada_peak_bin": peak_bin,
        "cicada_persistence": temporal_persistence(
            power, *cfg.cicada_band_hz, cfg.stft_window, cfg.target_rate_hz),
    }
