"""Overlapped all-gather matmul ("collective matmul", Wang et al. 2023) via
shard_map + ppermute — a hillclimb lever for collective-bound cells.

Standard GSPMD lowering of  y = x @ W  with W column-sharded and x needing an
all-gather serializes: all-gather(x) THEN matmul. The collective-matmul form
pipelines: each of the N steps matmuls the locally-held x shard while
ppermuting the next shard around the ring — communication hides behind
compute whenever per-step matmul time >= per-step permute time.

Used by the hillclimbed sharding profile for decode MLP/logits layers
(EXPERIMENTS.md §Perf) — correctness is covered by tests/test_distributed.py
against the plain einsum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def ag_matmul(x, w, mesh, axis="model"):
    """y = x @ w with x row-sharded on `axis` (dim 0 blocks), w replicated
    per-shard column block; gathers x shards ring-wise, overlapping each hop
    with the local partial matmul.

    x: (M, K) sharded (axis, None); w: (K, N) sharded (None, axis).
    Returns y: (M, N) sharded (None, axis)."""
    n = mesh.shape[axis]

    def local(x_blk, w_blk):
        # x_blk: (M/n, K); w_blk: (K, N/n)
        idx = jax.lax.axis_index(axis)
        M_blk = x_blk.shape[0]
        # pvary marks the accumulator varying over the ring axis
        out = jax.lax.pvary(
            jnp.zeros((M_blk * n, w_blk.shape[1]), x_blk.dtype), (axis,))

        def body(i, carry):
            out, cur = carry
            src_idx = (idx - i) % n          # whose shard we now hold
            out = jax.lax.dynamic_update_slice(
                out, cur @ w_blk, (src_idx * M_blk, 0))
            nxt = jax.lax.ppermute(
                cur, axis, [(j, (j + 1) % n) for j in range(n)])
            return (out, nxt)

        out, _ = jax.lax.fori_loop(0, n, body, (out, x_blk))
        return out

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis, None), P(None, axis)),
                     out_specs=P(None, axis))(x, w)
