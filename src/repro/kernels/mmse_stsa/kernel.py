"""Pallas TPU kernel: fused MMSE-STSA spectral gain (the paper's dominant
cost — 923-1020 s of a ~1300 s serial pipeline, Table 1).

Design for TPU:
  * The decision-directed recurrence is sequential over FRAMES but parallel
    over BINS. Grid = (batch, bin_tiles); each grid step walks all frames for
    its 128-bin lane tile with a fori_loop, carrying A^2/lambda in registers.
    128-wide rows map directly onto the VPU lanes.
  * exp(-v/2)*I0/I1(v/2) are computed as exponentially-scaled Bessels i0e/i1e
    via Abramowitz-Stegun 9.8.1-9.8.8 polynomials — no table lookups, no
    overflow for large v (loud signal bins).

Every value in the kernel is 2-D: a frame is a (1, 128) row read and
written with a dynamic one-row slice, and the noise PSD is carried as
(B, 1, K) so its block (1, 128) spans the whole sublane dim.

VMEM per grid step (F frames, 128-bin tile, f32):
  power block (F, 128) + gain block (F, 128)  ~ F=896: 2 x 448 KiB
  noise block (1, 128)                         ~ 4 KiB (one sublane tile)
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mmse_stsa.ref import XI_MIN, GAMMA_MAX, SQRTPI_2

BIN_TILE = 128

_I0_SMALL = (1.0, 3.5156229, 3.0899424, 1.2067492, 0.2659732, 0.0360768,
             0.0045813)
_I0_LARGE = (0.39894228, 0.01328592, 0.00225319, -0.00157565, 0.00916281,
             -0.02057706, 0.02635537, -0.01647633, 0.00392377)
_I1_SMALL = (0.5, 0.87890594, 0.51498869, 0.15084934, 0.02658733, 0.00301532,
             0.00032411)
_I1_LARGE = (0.39894228, -0.03988024, -0.00362018, 0.00163801, -0.01031555,
             0.02282967, -0.02895312, 0.01787654, -0.00420059)


def _poly(coeffs, t):
    acc = jnp.full_like(t, coeffs[-1])
    for c in reversed(coeffs[:-1]):
        acc = acc * t + c
    return acc


def i0e_poly(x):
    """exp(-x) * I0(x) for x >= 0 (A&S 9.8.1 / 9.8.2)."""
    t2 = (x / 3.75) ** 2
    small = _poly(_I0_SMALL, t2) * jnp.exp(-x)
    ti = 3.75 / jnp.maximum(x, 3.75)
    large = _poly(_I0_LARGE, ti) / jnp.sqrt(jnp.maximum(x, 1e-8))
    return jnp.where(x <= 3.75, small, large)


def i1e_poly(x):
    """exp(-x) * I1(x) for x >= 0 (A&S 9.8.3 / 9.8.4)."""
    t2 = (x / 3.75) ** 2
    small = x * _poly(_I1_SMALL, t2) * jnp.exp(-x)
    ti = 3.75 / jnp.maximum(x, 3.75)
    large = _poly(_I1_LARGE, ti) / jnp.sqrt(jnp.maximum(x, 1e-8))
    return jnp.where(x <= 3.75, small, large)


def frame_gain(p, inv_lam, a2_prev, alpha):
    """One decision-directed MMSE-STSA frame: power row p, 1/noise, and the
    previous frame's A^2/lambda -> (unfloored gain, A^2/lambda carry)."""
    gamma = jnp.clip(p * inv_lam, 1e-8, GAMMA_MAX)
    xi = alpha * a2_prev + (1.0 - alpha) * jnp.maximum(gamma - 1.0, 0.0)
    xi = jnp.maximum(xi, XI_MIN)
    v = jnp.maximum(xi * gamma / (1.0 + xi), 1e-8)
    g = (SQRTPI_2 * jnp.sqrt(v) / gamma
         * ((1.0 + v) * i0e_poly(v / 2.0) + v * i1e_poly(v / 2.0)))
    g = jnp.clip(g, 0.0, 10.0)
    return g, (g * g) * gamma


def _mmse_kernel(power_ref, noise_ref, gain_ref, *, alpha, gain_floor,
                 n_frames):
    lam = jnp.maximum(noise_ref[...], 1e-10)         # (1, BIN_TILE)
    inv_lam = 1.0 / lam

    def frame_step(t, a2_prev):
        p = power_ref[pl.ds(t, 1), :]                 # (1, BIN_TILE)
        g, a2 = frame_gain(p, inv_lam, a2_prev, alpha)
        gain_ref[pl.ds(t, 1), :] = jnp.maximum(g, gain_floor)
        return a2

    jax.lax.fori_loop(0, n_frames, frame_step,
                      jnp.ones((1, BIN_TILE), jnp.float32))


def mmse_gain_pallas(power, noise_psd, alpha=0.98, gain_floor=0.1,
                     interpret=False):
    """power: (B,F,K) f32, K a multiple of BIN_TILE (ops.py pads);
    noise_psd: (B,K). Returns gains (B,F,K) f32."""
    B, F, K = power.shape
    assert K % BIN_TILE == 0, f"bins {K} not a multiple of {BIN_TILE}"
    kernel = functools.partial(_mmse_kernel, alpha=alpha,
                               gain_floor=gain_floor, n_frames=F)
    return pl.pallas_call(
        kernel,
        grid=(B, K // BIN_TILE),
        in_specs=[
            pl.BlockSpec((None, F, BIN_TILE), lambda b, k: (b, 0, k)),
            pl.BlockSpec((None, 1, BIN_TILE), lambda b, k: (b, 0, k)),
        ],
        out_specs=pl.BlockSpec((None, F, BIN_TILE), lambda b, k: (b, 0, k)),
        out_shape=jax.ShapeDtypeStruct((B, F, K), jnp.float32),
        interpret=interpret,
        name="mmse_stsa",
    )(power.astype(jnp.float32),
      noise_psd.astype(jnp.float32).reshape(B, 1, K))
