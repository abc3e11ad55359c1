"""Operations and bytes each kernel's algorithm needs, counted from shapes.

These are the counts of the algorithm, not of a kernel's layout: a real DFT
of `window // 2 + 1` bins per frame over the valid frames only, `2 * bins`
output floats per frame, one multiply-add per FIR tap, and every input row
read and every output row written once in float32. Padding a kernel adds
for its tiling (frame tiles, lane padding of the spectrum, halo rows) is
not work, so a kernel that drops padding reads a higher roofline share on
the same work and never one above 100%. A multiply-add is 2 operations.

The MMSE-STSA recurrence inside the fused tail is elementwise work on the
vector unit, which has no published peak; it is not counted, so the fused
tail's bound is its DFT on the matrix unit or its bytes, whichever is the
larger.
"""
from __future__ import annotations

F32 = 4


def frames(samples, window, hop):
    return (samples - window) // hop + 1


def stft_dft(rows, samples, window, hop):
    """The detection STFT over `rows` chunks of `samples` samples:
    (operations, bytes)."""
    f = frames(samples, window, hop)
    out = 2 * (window // 2 + 1)
    return (2 * rows * f * window * out,
            F32 * rows * (samples + f * out))


def fir_hpf(rows, samples_in, taps, stride):
    """A causal FIR of `taps` taps with `stride`:1 decimation."""
    n_out = samples_in // stride
    return 2 * rows * n_out * taps, F32 * rows * (samples_in + n_out)


def fused_tail(rows, samples, window, hop, taps=0):
    """The survivor tail of `rows` real survivors of `samples` samples:
    the optional stride-1 FIR of `taps` taps, the windowed DFT, and the
    gain-filtered spectrum written out (the kernel's output; the inverse
    DFT runs outside it)."""
    f = frames(samples, window, hop)
    out = 2 * (window // 2 + 1)
    ops = 2 * rows * (f * window * out + samples * taps)
    return ops, F32 * rows * (samples + f * out)


def roofline_share(ops, nbytes, seconds, peaks):
    """Percent of the least time the chip could take (the larger of ops
    over the bf16 peak and bytes over HBM bandwidth) in `seconds` of
    kernel time. None when the kernel did not run."""
    if seconds <= 0 or (ops <= 0 and nbytes <= 0):
        return None
    bound = max(ops / peaks["flops_per_s_bf16"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
