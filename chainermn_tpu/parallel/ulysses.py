"""Ulysses-style sequence parallelism — all_to_all head exchange.

Reference status: **absent** in ChainerMN (SURVEY.md §2.6); SURVEY §5
names the differentiable ``alltoall`` as the Ulysses-shaped primitive.

The sequence axis is sharded across ranks; for attention, an
``all_to_all`` re-shards from sequence-split [B, H, T/n, D] to head-split
[B, H/n, T, D], full attention runs per local head group over the whole
sequence, and a reverse ``all_to_all`` restores sequence sharding.  Two
collectives per attention layer, each moving activations once — the
bandwidth-optimal exchange when H ≥ n.

The per-head-group attention runs through the blockwise primitive
(Pallas flash kernels on TPU — forward and the FUSED one-pass backward
of ISSUE 4), and ``all_to_all`` is self-transposing, so the whole layer
differentiates through the fused kernel path.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..observability import role
from ..ops.flash_attention import blockwise_attention

__all__ = ["ulysses_attention", "seq_to_head_shard", "head_to_seq_shard"]


def seq_to_head_shard(comm, x):
    """[B, H, T_local, D] (sequence-sharded) → [B, H/n, T, D] (head-sharded)."""
    size = comm.size
    B, H, Tl, D = x.shape
    if H % size != 0:
        raise ValueError(f"head count {H} not divisible by axis size {size}")
    return lax.all_to_all(x, comm.axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def head_to_seq_shard(comm, x):
    """[B, H/n, T, D] (head-sharded) → [B, H, T_local, D] (sequence-sharded)."""
    return lax.all_to_all(x, comm.axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


@role("attn")
def ulysses_attention(comm, q, k, v, causal=False, scale=None):
    """Exact attention with Ulysses sequence parallelism.

    Inputs rank-local [B, H, T_local, D] sequence shards; output the same.
    Identical math to full attention on the gathered sequence.  The
    per-head-group attention over the full sequence runs through the
    blockwise primitive (Pallas flash kernel on TPU, blockwise jnp scan
    elsewhere) — the [T, T] score matrix is never materialized, so
    long-context memory is O(T · block), not O(T²).
    """
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qh = seq_to_head_shard(comm, q)
    kh = seq_to_head_shard(comm, k)
    vh = seq_to_head_shard(comm, v)
    out = blockwise_attention(qh, kh, vh, causal=causal, scale=scale)
    return head_to_seq_shard(comm, out)
