"""Pure-jnp oracle for the MMSE-STSA gain (Ephraim & Malah 1984).

Uses jax.scipy.special.i0e/i1e (the kernel hand-rolls A&S polynomial
approximations — independent code paths for the allclose sweep).

Per frame t, bin k (decision-directed a-priori SNR):
  gamma = |Y|^2 / lambda_noise                    (a-posteriori SNR)
  xi    = alpha * A^2_{t-1}/lambda + (1-alpha) * max(gamma-1, 0)
  v     = xi * gamma / (1 + xi)
  G     = (sqrt(pi)/2) * (sqrt(v)/gamma) * [(1+v) i0e(v/2) + v i1e(v/2)]
  A     = G * |Y|
The exponentially-scaled Bessels absorb exp(-v/2) (stable for large v).
"""
import jax
import jax.numpy as jnp

XI_MIN = 10.0 ** (-25.0 / 10.0)       # a-priori SNR floor (-25 dB)
GAMMA_MAX = 10.0 ** (40.0 / 10.0)     # a-posteriori SNR ceiling (40 dB)
SQRTPI_2 = 0.8862269254527580         # sqrt(pi)/2


def gain_fn(v, gamma):
    """MMSE-STSA gain from v and gamma (elementwise, f32)."""
    v = jnp.maximum(v, 1e-8)
    g = (SQRTPI_2 * jnp.sqrt(v) / gamma
         * ((1.0 + v) * jax.scipy.special.i0e(v / 2.0)
            + v * jax.scipy.special.i1e(v / 2.0)))
    # large-v asymptote is xi/(1+xi) == v/gamma; the scaled-Bessel form
    # converges there numerically, but clip for safety
    return jnp.clip(g, 0.0, 10.0)


def mmse_stsa_gain_ref(power, noise_psd, alpha=0.98, gain_floor=0.1):
    """power: (B,F,K) |Y|^2; noise_psd: (B,K) -> gains (B,F,K) f32."""
    power = power.astype(jnp.float32)
    lam = jnp.maximum(noise_psd.astype(jnp.float32), 1e-10)[:, None, :]
    gamma = jnp.clip(power / lam, 1e-8, GAMMA_MAX)              # (B,F,K)

    def step(a2_prev, gamma_t):
        xi = alpha * a2_prev + (1.0 - alpha) * jnp.maximum(gamma_t - 1.0, 0.0)
        xi = jnp.maximum(xi, XI_MIN)
        v = xi * gamma_t / (1.0 + xi)
        g = gain_fn(v, gamma_t)
        a2 = (g * g) * gamma_t          # A^2/lambda for the next frame
        return a2, jnp.maximum(g, gain_floor)

    a2_0 = jnp.ones_like(gamma[:, 0, :])
    _, gains = jax.lax.scan(step, a2_0, jnp.moveaxis(gamma, 1, 0))
    return jnp.moveaxis(gains, 0, 1)


def estimate_noise_psd(power, n_frames=16):
    """Initial-segment noise PSD estimate: mean of the first n_frames."""
    return jnp.mean(power[:, :n_frames, :], axis=1)
