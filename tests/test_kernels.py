"""Per-kernel allclose sweeps: interpret-mode pallas_call vs the pure-jnp
ref.py oracle, across shapes and parameter settings (per the brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend
from repro.kernels.stft_dft import kernel as SK, ref as SR, ops as SO
from repro.kernels.mmse_stsa import kernel as MK, ref as MR, ops as MO
from repro.kernels.fir_hpf import kernel as FK, ref as FR, ops as FO


# ------------------------------------------------------------------- STFT
@pytest.mark.parametrize("B,n_tiles", [(1, 1), (2, 2), (3, 1)])
def test_stft_kernel_vs_fft_oracle(B, n_tiles):
    rng = np.random.RandomState(B * 7 + n_tiles)
    S = n_tiles * SK.FRAME_TILE * 128 + 128
    x = jnp.asarray(rng.randn(B, S).astype(np.float32))
    got = SK.stft_pallas(x, interpret=True)
    bins = 129
    z = jax.lax.complex(got[..., :bins], got[..., bins:2 * bins])
    want = SR.stft_ref(x)
    np.testing.assert_allclose(np.asarray(z), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_stft_pad_and_slice_matches_ref():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 50_000).astype(np.float32))
    xp = SO.pad_for_stft(x)
    with backend.use("interpret"):
        z = SO.stft(xp)
    np.testing.assert_allclose(np.asarray(z), np.asarray(SR.stft_ref(xp)),
                               rtol=2e-4, atol=2e-4)


def test_istft_roundtrip():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 33_000).astype(np.float32))
    xp = SO.pad_for_stft(x)
    z = SR.stft_ref(xp)
    xr = SO.istft(z, xp.shape[1])
    cov = SR.num_frames(xp.shape[1], 256, 128) * 128 + 128
    np.testing.assert_allclose(np.asarray(xr[:, :cov]),
                               np.asarray(xp[:, :cov]), atol=1e-4)


# ------------------------------------------------------------------- MMSE
@pytest.mark.parametrize("B,F,K", [(1, 32, 128), (2, 64, 129), (1, 16, 256)])
def test_mmse_kernel_vs_bessel_oracle(B, F, K):
    rng = np.random.RandomState(B + F + K)
    power = jnp.asarray(rng.exponential(1.0, (B, F, K)).astype(np.float32))
    power = power.at[:, F // 4:F // 2, : K // 3].add(40.0)
    noise = MR.estimate_noise_psd(power, 8)
    with backend.use("interpret"):
        got = MO.mmse_gain(power, noise)
    want = MR.mmse_stsa_gain_ref(power, noise)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_mmse_gain_bounds_and_signal_behaviour():
    rng = np.random.RandomState(11)
    power = jnp.asarray(rng.exponential(1.0, (1, 64, 129)).astype(np.float32))
    power = power.at[:, 32:, 40:50].set(500.0)       # strong tonal signal
    noise = MR.estimate_noise_psd(power, 8)
    g = MR.mmse_stsa_gain_ref(power, noise, gain_floor=0.1)
    g = np.asarray(g)
    assert (g >= 0.1 - 1e-6).all() and (g <= 10.0).all()
    assert g[:, 40:, 40:50].mean() > 0.9      # signal region passed through
    assert g[:, 10:30, 60:].mean() < 0.45     # noise-only region attenuated


def test_bessel_polynomials_match_scipy_jax():
    x = jnp.asarray(np.linspace(0.0, 60.0, 500, dtype=np.float32))
    np.testing.assert_allclose(np.asarray(MK.i0e_poly(x)),
                               np.asarray(jax.scipy.special.i0e(x)),
                               rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(MK.i1e_poly(x)),
                               np.asarray(jax.scipy.special.i1e(x)),
                               rtol=3e-5, atol=6e-6)


# -------------------------------------------------------------------- FIR
@pytest.mark.parametrize("stride,S,taps", [(1, 5000, 129), (2, 10_000, 129),
                                           (2, 8193, 65), (3, 9001, 33)])
def test_fir_kernel_vs_conv_oracle(stride, S, taps):
    rng = np.random.RandomState(stride * S % 97)
    x = jnp.asarray(rng.randn(2, S).astype(np.float32))
    h = FR.bandpass_decimate_taps(1000.0, 11_025.0, 44_100, taps)
    got = FK.fir_pallas(x, h, stride=stride, interpret=True)
    want = FR.fir_ref(x, h, stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_fir_frequency_response():
    t = np.arange(44_100).astype(np.float32) / 44_100
    for f0, passband in [(400.0, False), (4000.0, True), (13_000.0, False)]:
        tone = jnp.asarray(np.sin(2 * np.pi * f0 * t))[None]
        out = np.asarray(FO.bandpass_decimate(tone))
        ratio = np.sqrt((out[:, 1000:] ** 2).mean()) / np.sqrt(0.5)
        assert (ratio > 0.9) == passband, (f0, ratio)


# ------------------------------------------------------------- fused tail
@pytest.mark.parametrize("hpf", [False, True])
@pytest.mark.parametrize("n_tiles", [1, 2])
def test_fused_tail_kernel_vs_composed_oracle(hpf, n_tiles):
    """Interpret-mode fused pass vs the composed per-stage ref oracle —
    the same allclose contract every per-kernel sweep above uses (bitwise
    staged-vs-fused identity per mode lives in test_fused_tail.py)."""
    from repro.configs import SERF_AUDIO as cfg
    from repro.kernels.fused_tail import kernel as FTK, ref as FTR
    rng = np.random.RandomState(10 * n_tiles + hpf)
    S = n_tiles * SK.FRAME_TILE * 128 + 256
    wave = jnp.asarray(rng.randn(5, S).astype(np.float32) * 0.3)
    idx = jnp.asarray([3, 0, 4, 7], jnp.int32)      # one pad slot
    packed = FTK.fused_tail_pallas(wave, idx, cfg, hpf=hpf,
                                   interpret=True)
    got = FTK.finish(packed, S, cfg)
    want = FTR.fused_tail_ref(wave, idx, cfg, hpf=hpf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert not np.asarray(got[3]).any()             # pad row exactly zero
