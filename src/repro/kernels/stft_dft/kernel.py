"""Pallas TPU kernel: fused framing + Hamming window + real-DFT-as-matmul.

TPU adaptation of the paper's FFT stage (Apache Commons radix FFT on CPU):
a 256-point real DFT is a (frames x 256) @ (256 x 2*bins) matmul — MXU-native,
no butterfly/bit-reversal (which would serialize on a systolic array).

Layout: with 50% overlap (window = 2 * hop, hop a multiple of 128) the
signal viewed as rows of `hop` samples makes frame f the concatenation of
rows f and f + 1, so the windowed DFT is

    packed[f] = x[f] @ basis[:hop] + x[f + 1] @ basis[hop:]

— two matmuls over the same row tile, no gathers and no in-kernel reshape.
Row f + 1 comes from a sublane roll of the tile; the tile's last frame
takes its second half from the first row of an aligned 8-row halo block
that starts just after the tile.

Grid: (batch, frame_tiles). VMEM per step (FRAME_TILE=128, hop=128, f32):
  rows (128,128) 64 KiB + halo (8,128) 4 KiB + basis (256,384) 384 KiB
  + out (128,384) 192 KiB, double-buffered by the pipeline.
MXU alignment: contraction dim 128 and padded output dim 384 are multiples
of the 128-lane tiling.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stft_dft.ref import hamming

FRAME_TILE = 128   # block-shape hillclimb: 256 -> 128 cuts pad waste ~4.5%
                   # and halves the per-step VMEM footprint (EXPERIMENTS §Perf)
PAD_OUT = 384          # 2*(128+1) = 258 -> padded to 3*128
HIGHEST = jax.lax.Precision.HIGHEST


def dft_basis_np(window=256, windowed=True):
    """Packed real-DFT basis (window, PAD_OUT) as numpy: [cos | -sin |
    zero-pad]. With windowed=True the Hamming window is folded into the
    basis rows (diag(w) @ basis), fusing the windowing into the DFT
    matmul."""
    bins = window // 2 + 1
    n = np.arange(window)[:, None]
    k = np.arange(bins)[None, :]
    ang = 2.0 * np.pi * n * k / window
    basis = np.zeros((window, PAD_OUT), np.float32)
    basis[:, :bins] = np.cos(ang)
    basis[:, bins:2 * bins] = -np.sin(ang)
    if windowed:
        basis *= hamming(window)[:, None]
    return basis


def dft_basis(window=256, dtype=jnp.float32, windowed=True):
    """`dft_basis_np` as a device constant of `dtype`."""
    return jnp.asarray(dft_basis_np(window, windowed), dtype)


def next_rows(cur, last_next):
    """Row i of the result is cur[i + 1]; the last row is `last_next`."""
    n = cur.shape[0]
    rolled = pltpu.roll(cur, n - 1, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    return jnp.where(row == n - 1, last_next, rolled)


def windowed_dft(cur, nxt, basis):
    """Frames (cur | nxt) @ basis with the 50%-overlap halves split."""
    hop = cur.shape[1]
    return (jnp.dot(cur, basis[:hop], precision=HIGHEST,
                    preferred_element_type=jnp.float32)
            + jnp.dot(nxt, basis[hop:], precision=HIGHEST,
                      preferred_element_type=jnp.float32))


def _stft_kernel(x_ref, halo_ref, basis_ref, o_ref):
    cur = x_ref[...]                                      # (FT, hop)
    nxt = next_rows(cur, halo_ref[0:1, :])
    o_ref[...] = windowed_dft(cur, nxt, basis_ref[...])


def stft_pallas(x, window=256, hop=128, interpret=False):
    """x: (B, S) f32, S = n_tiles*FRAME_TILE*hop + (window-hop)
    -> (B, F, PAD_OUT) packed [re | im | pad], F = n_tiles*FRAME_TILE."""
    if hop * 2 != window or hop % 128:
        raise ValueError("the kernel needs 50% overlap and a hop that is a "
                         f"multiple of 128 (window={window}, hop={hop})")
    B, S = x.shape
    tile_span = FRAME_TILE * hop
    tail_len = window - hop
    if (S - tail_len) % tile_span:
        raise ValueError(f"S={S} must be n*{tile_span}+{tail_len} "
                         "(ops.pad_for_stft pads)")
    n_tiles = (S - tail_len) // tile_span
    F = n_tiles * FRAME_TILE
    # rows of hop samples, padded to whole 8-row halo blocks past the end
    n_rows = F + 8
    xr = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (0, n_rows * hop - S))).reshape(B, n_rows, hop)
    blocks = FRAME_TILE // 8
    return pl.pallas_call(
        _stft_kernel,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((None, FRAME_TILE, hop), lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, 8, hop),
                         lambda b, t: (b, (t + 1) * blocks, 0)),
            pl.BlockSpec((window, PAD_OUT), lambda b, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, FRAME_TILE, PAD_OUT),
                               lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, F, PAD_OUT), jnp.float32),
        interpret=interpret,
        name="stft_dft",
    )(xr, xr, dft_basis(window, jnp.float32))
