"""Public wrapper for the MMSE-STSA gain kernel (+ bin padding).

Backend dispatch per repro.kernels.backend; plain functions, composable
inside jit.
"""
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.mmse_stsa import kernel as K
from repro.kernels.mmse_stsa import ref as R


def mmse_gain(power, noise_psd, alpha=0.98, gain_floor=0.1):
    """power: (B,F,K) |Y|^2; noise_psd: (B,K) -> gains (B,F,K)."""
    use_pallas, interp = backend.resolve()
    if not use_pallas:
        return R.mmse_stsa_gain_ref(power, noise_psd, alpha, gain_floor)
    B, F, Kbins = power.shape
    pad = (-Kbins) % K.BIN_TILE
    if pad:
        power = jnp.pad(power, ((0, 0), (0, 0), (0, pad)))
        noise_psd = jnp.pad(noise_psd, ((0, 0), (0, pad)),
                            constant_values=1.0)
    g = K.mmse_gain_pallas(power, noise_psd, alpha, gain_floor,
                           interpret=interp)
    return g[..., :Kbins]
