"""Pallas TPU kernel: fused FIR filter + decimation (high-pass / band-pass).

Replaces the paper's two SoX passes (downsample, then 1 kHz high-pass) with a
single band-pass FIR applied at the source rate with stride-2 decimation —
the kernel-launch analogue of the paper's Fig-2 "two-split" trick (fewer
passes over the data, no intermediate 22.05 kHz buffer in HBM).

Layout: a row of S samples is viewed as (S / W, W) with W = stride * 128,
so row r of the input holds exactly the samples that output row r (128
outputs) decimates from. A causal FIR with at most 128 history samples
(T <= 129 taps) then needs only input row r and the last 128 lanes of row
r - 1, and the whole filter + decimation is two banded (Toeplitz) matmuls
on the MXU:

    y[r] = x[r] @ Mc + x[r-1][W-128:] @ Mp

with Mc (W, 128) and Mp (128, 128) built from the taps on the host. Every
BlockSpec is (rows, W) with rows a multiple of 8 and W a multiple of 128;
row r - 1 comes from a sublane roll of the tile, and the tile's first row
takes its history from an aligned 8-row halo block ending just before the
tile.

Grid: (batch, row_tiles). VMEM per step (f32, 496-row tile, stride 2):
  tile 496 KiB + halo 8 KiB + Mc 128 KiB + Mp 64 KiB + out 248 KiB,
  double-buffered by the pipeline.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_TILE_ROWS = 512    # output rows (x 128 samples) per grid step
HIGHEST = jax.lax.Precision.HIGHEST


def toeplitz_taps(taps, stride):
    """(Mc, Mp): the banded matrices of a causal FIR + decimation.

    Output lane j of row r is y[128 r + j] = sum_k h[k] x[W r + s j - k];
    input lane m of row r is x[W r + m] (k = s j - m) and lane p of the
    previous row's last 128 lanes is x[W r - 128 + p] (k = s j + 128 - p).
    """
    h = np.asarray(taps, np.float64)
    T = h.shape[0]
    if T - 1 > LANES:
        raise ValueError(f"{T} taps need {T - 1} history samples; the "
                         f"kernel carries at most {LANES}")
    W = stride * LANES
    j = np.arange(LANES)[None, :]

    def band(k):
        ok = (k >= 0) & (k < T)
        return np.where(ok, h[np.clip(k, 0, T - 1)], 0.0).astype(np.float32)

    mc = band(stride * j - np.arange(W)[:, None])
    mp = band(stride * j + LANES - np.arange(LANES)[:, None])
    return jnp.asarray(mc), jnp.asarray(mp)


def prev_rows(cur, first_prev):
    """Row i of the result is cur[i - 1]; row 0 is `first_prev` (1, W)."""
    rolled = pltpu.roll(cur, 1, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    return jnp.where(row == 0, first_prev, rolled)


def _fir_kernel(x_ref, halo_ref, mc_ref, mp_ref, o_ref):
    cur = x_ref[...]                                      # (TR, W)
    W = cur.shape[1]
    # the halo block is the 8 rows before this tile (zeros before row 0)
    first = jnp.where(pl.program_id(1) == 0, 0.0, halo_ref[7:8, :])
    prev = prev_rows(cur, first)[:, W - LANES:]           # (TR, 128)
    o_ref[...] = (
        jnp.dot(cur, mc_ref[...], precision=HIGHEST,
                preferred_element_type=jnp.float32)
        + jnp.dot(prev, mp_ref[...], precision=HIGHEST,
                  preferred_element_type=jnp.float32))


def row_tiling(n_rows, max_rows=MAX_TILE_ROWS):
    """(tile_rows, n_tiles): the fewest tiles of <= max_rows rows, each a
    multiple of 8 rows, covering n_rows with the least padding."""
    n_tiles = max(1, -(-n_rows // max_rows))
    tile = -(-n_rows // n_tiles)
    tile = -(-tile // 8) * 8
    return tile, n_tiles


def fir_pallas(x, taps, stride=1, interpret=False):
    """x: (B,S); taps: (T,) np/jnp, T <= 129. Returns (B, S//stride).

    Causal: y[n] = sum_k taps[k] * x[n*stride - k] (left zero-pad)."""
    B, S = x.shape
    W = stride * LANES
    out_len = S // stride
    tile, n_tiles = row_tiling(-(-out_len // LANES))
    n_rows = tile * n_tiles
    # samples past out_len * stride feed no output (the filter is causal)
    x = x[:, :min(S, n_rows * W)].astype(jnp.float32)
    xr = jnp.pad(x, ((0, 0), (0, n_rows * W - x.shape[1]))).reshape(
        B, n_rows, W)
    mc, mp = toeplitz_taps(taps, stride)
    blocks = tile // 8
    out = pl.pallas_call(
        _fir_kernel,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((None, tile, W), lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, 8, W),
                         lambda b, t: (b, jnp.maximum(t * blocks - 1, 0), 0)),
            pl.BlockSpec((W, LANES), lambda b, t: (0, 0)),
            pl.BlockSpec((LANES, LANES), lambda b, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tile, LANES), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_rows, LANES), jnp.float32),
        interpret=interpret,
        name="fir_hpf",
    )(xr, xr, mc, mp)
    return out.reshape(B, n_rows * LANES)[:, :out_len]
