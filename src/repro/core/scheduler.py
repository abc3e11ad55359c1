"""Work distribution for the early-exit pipeline — the paper's master-slave
file management, made TPU-native.

The paper's master tracks which files are deleted and never dispatches them
to the expensive MMSE stage. On TPU the same economy comes from COMPACTION:
survivors are packed dense (global stable argsort — XLA lowers the cross-
device movement to all-to-alls), the host reads one scalar (survivor count)
and dispatches the MMSE phase on a minimally-padded survivor batch. No
central master owns the data path: the "master" role shrinks to a scalar
readback + shape choice, removing the paper's single point of failure.

Also provides the load-balance metrics reported in the paper (Figs 14-18),
and the `Rebalancer` that owns the detection -> MMSE handoff for the
multi-shard `ShardedPlan`: heterogeneous noise regimes leave shards with
skewed survivor counts (Lostanlen-style sensor networks), so survivors are
re-assigned across shards between the phases instead of staying where
detection left them.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


def compact(chunks, keep):
    """Pack surviving chunks to the front (stable order preserved).

    chunks: (N, ...); keep: (N,) bool. Returns (packed chunks, packed keep,
    survivor count)."""
    order = jnp.argsort(~keep, stable=True)
    return jnp.take(chunks, order, axis=0), keep[order], jnp.sum(keep)


def shard_load(keep, n_shards):
    """Per-shard surviving-chunk counts: the paper's files-per-slave
    measurement. N not divisible by n_shards is padded with removed
    (False) chunks — the trailing shard just holds fewer real chunks."""
    n = keep.shape[0]
    pad = (-n) % n_shards
    if pad:
        keep = jnp.concatenate(
            [keep, jnp.zeros((pad,), keep.dtype)])
    return jnp.sum(keep.reshape(n_shards, -1), axis=1)


def balance_stats(keep, n_shards):
    """Load-balance metrics (paper Figs 14-16: 'each slave processes almost
    the same number of files').

    'before' = survivors stay where detection left them (mask-only early
    exit); 'after' = survivors are compacted AND re-sliced into a dense
    batch of ceil(n/k) per shard (what survivor_batch dispatches) — the
    residual imbalance is only the ceil-vs-mean padding."""
    loads = shard_load(keep, n_shards)
    mean = jnp.mean(loads.astype(jnp.float32))
    imb = jnp.max(loads) / jnp.maximum(mean, 1e-9)
    n = jnp.sum(keep)
    per_shard_after = jnp.ceil(n / n_shards)
    imb_after = per_shard_after / jnp.maximum(n / n_shards, 1e-9)
    return {"loads": loads, "imbalance": imb,
            "imbalance_after_compact": imb_after}


def quantize_survivors(n, cap, pad_multiple=1, bucket="pow2"):
    """Padded tail-batch size for `n` survivors out of a `cap`-row batch.

    'linear' is the historical quantization — the next multiple of
    pad_multiple — which retraces the tail jit once per distinct survivor
    count when pad_multiple is small. 'pow2' rounds up to the next
    pad_multiple-aligned power-of-two bucket (clipped at the padded cap),
    so a B-row batch compiles O(log B) tail variants total, whatever the
    survivor counts of the stream."""
    n = int(n)
    m = max(1, int(pad_multiple))
    lin = -(-n // m) * m
    if bucket == "linear":
        return lin
    if bucket != "pow2":
        raise ValueError(f"unknown bucket mode {bucket!r} "
                         "(expected 'pow2' or 'linear')")
    hi = max(lin, -(-int(cap) // m) * m)
    size = m
    while size < n:
        size *= 2
    return min(size, hi)


def survivor_indices(keep_np, pad_multiple=1, bucket="pow2"):
    """Device-compaction bookkeeping: the host reads ONLY the keep mask and
    answers with a padded int32 gather-index vector; the tail jit compacts
    on device (`jnp.take(..., mode='fill')`), so the full pre-denoise
    waveform never round-trips through the host.

    Pad slots hold the out-of-range index `len(keep_np)`, which the fill
    gather turns into all-zero rows — never a repeat of real audio, so
    padding costs deterministic zero-row flops and can never leak a
    duplicated chunk into output. Returns (idx, n_real); idx is None when
    nothing survived."""
    idx = np.flatnonzero(keep_np)
    n = len(idx)
    if n == 0:
        return None, 0
    size = quantize_survivors(n, keep_np.size, pad_multiple, bucket)
    out = np.full(size, keep_np.size, np.int32)
    out[:n] = idx
    return out, n


def kept_skew(keep_np, parts):
    """Survivor imbalance over `parts` devices that each hold an equal
    run of the batch's chunks: the largest part's count of kept chunks
    over the mean count. 1.0 on one device or when nothing is kept."""
    counts = np.asarray(keep_np).reshape(parts, -1).sum(1)
    mean = counts.mean()
    return float(counts.max() / mean) if mean else 1.0


def survivor_batch(chunks_np, keep_np, pad_multiple):
    """Host-side ("master") re-batching of survivors for the MMSE phase:
    pad survivor count up to a multiple of the device count so the phase-B
    jit shards evenly. Returns (batch, n_real). This is the host fallback
    of the device-compaction path (`survivor_indices` + `graph.
    tail_indexed`), kept for host-side consumers and reference tests."""
    idx = np.nonzero(keep_np)[0]
    n = len(idx)
    if n == 0:
        return None, 0
    return pad_batch(chunks_np[idx], pad_multiple)


def pad_batch(rows_np, pad_multiple):
    """Pad an already-packed survivor batch up to a multiple of
    pad_multiple with ZERO rows. (It used to repeat the last row — wasted
    MMSE flops on real audio, and a latent duplicate-output hazard if a
    consumer ever forgot to slice [:n_real].) Returns (batch, n_real)."""
    n = rows_np.shape[0]
    if n == 0:
        return None, 0
    n_pad = -(-n // pad_multiple) * pad_multiple
    if n_pad == n:
        return rows_np, n
    pad = np.zeros((n_pad - n,) + rows_np.shape[1:], rows_np.dtype)
    return np.concatenate([rows_np, pad]), n


# ------------------------------------------------------------- rebalancing

@dataclass
class ShardAssignment:
    """One detection -> MMSE handoff decision: how the packed global
    survivor order (source shards concatenated in slot order) is re-sliced
    across the destination shards."""
    counts_before: np.ndarray   # survivors detected per source shard
    counts_after: np.ndarray    # survivors assigned per destination shard
    bounds: np.ndarray          # (k+1,) prefix offsets into the packed order
    moved: int                  # survivors whose shard changed

    @staticmethod
    def _ratio(counts):
        """max/min shard load; an empty or fully-starved shard counts as
        load 1 so the ratio stays finite (a 0-load shard reads as 'max x
        worse than idle')."""
        if counts.size == 0 or counts.max() == 0:
            return 1.0
        return float(counts.max()) / float(max(counts.min(), 1))

    def stats(self):
        """max/min shard-load ratios before/after the re-shard (the paper's
        Figs 14-16 'each slave processes almost the same number of files'
        claim, measured)."""
        return {
            "loads_before": self.counts_before,
            "loads_after": self.counts_after,
            "max_min_before": self._ratio(self.counts_before),
            "max_min_after": self._ratio(self.counts_after),
            "moved": self.moved,
        }


class Rebalancer:
    """Owns the survivor re-shard decision between detection and MMSE.

    The paper's master re-assigns files so 'each slave processes almost the
    same number of files' even after deletion (Figs 14-16). Here the mask
    readback happens once per round: each source shard reports its keep
    masks, survivors are packed in (shard, item) order, and the packed run
    is re-sliced into near-even contiguous spans — floor(n/k) or
    floor(n/k)+1 per destination shard, so the residual imbalance is the
    +-1 of integer division, never the noise skew of the input stream."""

    def __init__(self, n_shards, pad_multiple=1):
        self.n_shards = int(n_shards)
        self.pad_multiple = max(1, int(pad_multiple))

    def assign(self, keeps, out_shards=None) -> ShardAssignment:
        """keeps: one 1-D bool mask per source shard (its detected items'
        masks, concatenated). out_shards: destination shard count (defaults
        to n_shards; fewer when shards died mid-round)."""
        k = self.n_shards if out_shards is None else int(out_shards)
        if k < 1:
            raise ValueError("rebalance needs at least one live shard")
        counts_before = np.array([int(np.sum(m)) for m in keeps], np.int64)
        n = int(counts_before.sum())
        counts_after = n // k + (np.arange(k) < n % k).astype(np.int64)
        bounds = np.concatenate([[0], np.cumsum(counts_after)])
        src = np.repeat(np.arange(len(keeps)), counts_before)
        dst = np.repeat(np.arange(k), counts_after)
        moved = int(np.sum(src != dst))
        return ShardAssignment(counts_before, counts_after, bounds, moved)

    def split(self, survivors_np, asg: ShardAssignment):
        """Slice the packed (n, S) survivor array per the assignment into
        per-shard padded MMSE batches. Yields (shard_slot, batch, n_real)
        for non-empty slots only."""
        for j in range(len(asg.counts_after)):
            lo, hi = int(asg.bounds[j]), int(asg.bounds[j + 1])
            if hi == lo:
                continue
            batch, n_real = pad_batch(survivors_np[lo:hi], self.pad_multiple)
            yield j, batch, n_real
