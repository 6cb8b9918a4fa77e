"""Paged single-query decode attention — the serving hot loop's kernel.

The decode step of the serving engine (``chainermn_tpu.serving``) is the
byte-bound roofline of PR 3 all over again: per generated token it must
read every cached K/V byte of every running sequence exactly once, and
nothing else matters.  The cache lives in a PAGED pool — fixed-size
blocks in one preallocated array (`serving.kv_cache`), with each
sequence owning a list of pages (its *block table*) — so the attention
step gathers K/V **through the block table** instead of assuming a
contiguous per-sequence buffer:

    k_pages = k_pool[block_table]        # ONE gather per pool
    scores  = q · k_pages (per page block, online softmax)

Two lowerings, selected by ``CHAINERMN_TPU_PAGED_ATTN``:

* ``paged`` (default): one gather per pool, then a **page-blockwise
  online softmax** (the flash-attention recurrence over the page axis:
  running max / normalizer, score width bounded at ``page_size``) — the
  numerics and memory shape a future Pallas paged kernel drops into.
* ``dense``: the escape hatch and parity reference — the same single
  gather, flattened to a dense ``[B, T, H, D]`` view, one full-width
  masked softmax.  Greedy decode trajectories are identical (pinned by
  ``tests/serving_tests/test_decode_parity.py``); per-logit deltas are
  fp32 rounding only.

Neither lowering ever forms a ``[Tq, Tk]`` score matrix — the query is
one token per sequence, so scores are ``[B, H, T]`` rows.  The serving
budget census (`tools/serving_census.py`) pins both facts tier-1: one
gather per pool per layer, zero full-T score dots.

Dtype discipline (PR 3): pages are stored bf16 by default and enter the
dots in their storage dtype (the MXU's native bf16 path); accumulators
and the softmax state are fp32 via ``preferred_element_type``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["paged_decode_attention", "paged_prefill_attention",
           "paged_verify_attention", "paged_latent_attention",
           "paged_attn_mode", "head_sharding"]


def paged_attn_mode(mode=None):
    """Resolve the decode-attention lowering: explicit argument wins,
    else the ``CHAINERMN_TPU_PAGED_ATTN`` env knob (``paged`` default,
    ``dense`` = the reference escape hatch).  Read at call time so tests
    can flip it, but jit caches are NOT keyed on the env — the serving
    engine resolves the mode ONCE at construction and threads it
    explicitly, so a mid-process env flip cannot desync a cached decode
    program from a fresh prefill trace."""
    if mode is None:
        mode = os.environ.get("CHAINERMN_TPU_PAGED_ATTN", "paged")
    if mode not in ("paged", "dense"):
        raise ValueError(
            f"CHAINERMN_TPU_PAGED_ATTN={mode!r} invalid (paged|dense)")
    return mode


def head_sharding(mesh, ndim, head_dim, axis="tp"):
    """``NamedSharding`` pinning the HEAD dimension of an ``ndim``-rank
    array to the ``tp`` mesh axis (the tensor-parallel decode layout:
    heads shard like the ulysses path, every other dim replicated).
    Used by the serving engine to place the KV pools per shard and by
    :func:`paged_decode_attention` to constrain the gathered pages."""
    from jax.sharding import NamedSharding, PartitionSpec
    spec = [None] * ndim
    spec[head_dim] = axis
    return NamedSharding(mesh, PartitionSpec(*spec))


def _constrain_heads(x, head_dim, tp_mesh, tp_axis):
    """Pin ``x``'s head dimension to the tp axis (no-op without a
    mesh).  Keeps GSPMD from re-replicating the pool gathers — the
    whole point of tp decode is that each shard reads only ITS heads'
    cache bytes."""
    if tp_mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, head_sharding(tp_mesh, x.ndim, head_dim, tp_axis))


def _masked_softmax_stats(s, valid):
    """NaN-free masked softmax pieces shared by both lowerings: masked
    scores -> (p, l) with all-masked rows yielding p == 0 (an idle batch
    lane must produce zeros, not NaN)."""
    s = jnp.where(valid, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p, l


def _dense_decode(q, k, v, ctx_len, scale):
    """Dense reference: q [B, H, D] over contiguous k/v [B, T, H, D]
    with positions >= ctx_len masked.  One full-width softmax."""
    s = jnp.einsum("bhd,bthd->bht", q, k,
                   preferred_element_type=jnp.float32) * scale
    T = k.shape[1]
    kpos = lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
    p, l = _masked_softmax_stats(s, kpos < ctx_len[:, None, None])
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("bht,bthd->bhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_prefill_attention(q, k_pool, v_pool, block_table_row, start,
                            true_len, scale=None):
    """Suffix attention for a PREFIX-SHARED prefill (round 14).

    ``q``: ``[T, H, D]`` — the suffix's queries, query ``t`` sitting at
    absolute position ``start + t`` (``start`` = matched prefix
    length).  The suffix's own K/V must already be WRITTEN into the
    pools (``write_prompt_kv_at`` runs first), so ONE gather per pool
    through ``block_table_row`` covers the whole context — shared
    prefix pages and fresh suffix pages alike — and **zero flash
    kernels ever touch the shared pages** (the committed
    ``prefix_prefill`` census config pins this).  One masked softmax:
    query ``t`` sees positions ``<= start + t`` (causality subsumes the
    written-context bound since ``t < true_len``).  Scores are
    ``[H, T, N·S]`` — suffix-length by context, never ``[T_ctx,
    T_ctx]``: the FLOP saving IS the prefix hit.  Returns ``[T, H, D]``
    in ``q.dtype``.
    """
    T, H, D = q.shape
    S = k_pool.shape[1]
    N = block_table_row.shape[0]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    k = k_pool[block_table_row].reshape(N * S, H, D)
    v = v_pool[block_table_row].reshape(N * S, H, D)
    s = jnp.einsum("thd,khd->htk", q, k,
                   preferred_element_type=jnp.float32) * scale
    kpos = lax.broadcasted_iota(jnp.int32, (1, 1, N * S), 2)
    qpos = start + lax.broadcasted_iota(jnp.int32, (1, T, 1), 1)
    p, l = _masked_softmax_stats(s, kpos <= qpos)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("htk,khd->thd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_verify_attention(q, k_pool, v_pool, block_table, start,
                           scale=None, tp_mesh=None, tp_axis="tp"):
    """Multi-query verify attention for SPECULATIVE decoding (round 20).

    ``q``: ``[B, K1, H, D]`` — ``K1 = K + 1`` query tokens per sequence
    (the pending token plus K draft tokens), query ``j`` of lane ``b``
    sitting at absolute position ``start[b] + j``.  The speculated
    K/V must already be WRITTEN into the pools (``write_span_kv`` runs
    first), so ONE gather per pool through ``block_table`` (``[B, N]``)
    covers the whole context, and the per-query causal mask ``kpos <=
    start + j`` makes query ``j`` score exactly the trajectory prefix
    it would have seen in a vanilla decode step — which is what makes
    greedy accept/reject bit-identical to one-token-at-a-time decode.
    ``start[b] < 0`` marks an idle lane (all queries masked, output
    zeros).  Scores are ``[B, H, K1, N·S]`` — K1 stays a small
    constant, never the context length, so no ``[T, T]`` score matrix
    ever forms (the committed ``spec_verify`` census config pins this
    and the one-gather-per-pool fact).  Returns ``[B, K1, H, D]`` in
    ``q.dtype``.

    This is the whole speculative bargain in one shape: the dense-side
    cost of scoring K extra tokens rides the SAME cache-byte reads the
    single-query step already pays, so accepted tokens are (HBM-wise)
    free — dispatch count per emitted token drops by ``1/(1 +
    accepted)``.
    """
    B, K1, H, D = q.shape
    S = k_pool.shape[1]
    N = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    q = _constrain_heads(q, 2, tp_mesh, tp_axis)
    k = _constrain_heads(k_pool[block_table], 3, tp_mesh, tp_axis)
    v = _constrain_heads(v_pool[block_table], 3, tp_mesh, tp_axis)
    k = k.reshape(B, N * S, H, D)
    v = v.reshape(B, N * S, H, D)
    s = jnp.einsum("bjhd,bkhd->bhjk", q, k,
                   preferred_element_type=jnp.float32) * scale
    kpos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, N * S), 3)
    st = start[:, None, None, None]
    qpos = st + lax.broadcasted_iota(jnp.int32, (1, 1, K1, 1), 2)
    # idle lanes (start < 0) mask EVERY query — start + j crosses zero
    # for j >= |start|, so causality alone would leak
    p, l = _masked_softmax_stats(s, (kpos <= qpos) & (st >= 0))
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhjk,bkhd->bjhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return _constrain_heads(out.astype(q.dtype), 2, tp_mesh, tp_axis)


def paged_decode_attention(q, k_pool, v_pool, block_table, ctx_len,
                           scale=None, mode=None, tp_mesh=None,
                           tp_axis="tp"):
    """One decode step of attention for a batch of cached sequences.

    q: ``[B, H, D]`` — ONE query token per sequence (the just-appended
    position).  ``k_pool``/``v_pool``: ``[P, S, H, D]`` page pools
    (``P`` pages of ``S`` token slots).  ``block_table``: ``[B, N]``
    int32 page ids — sequence ``b``'s token ``t`` lives in page
    ``block_table[b, t // S]`` at slot ``t % S``; entries past the live
    prefix may hold any valid page id (their positions are masked by
    ``ctx_len``).  ``ctx_len``: ``[B]`` int32 valid context lengths
    (``0`` = idle lane, output is zeros).  Returns ``[B, H, D]`` in
    ``q.dtype``.

    ``tp_mesh``/``tp_axis``: tensor-parallel decode — the pools arrive
    sharded over heads (``head_sharding``), and the constraints below
    keep the gathers and the attention output sharded the same way, so
    each shard reads only its own heads' cache bytes; the head axis is
    elementwise throughout, so no collective fires inside this op (the
    projection that consumes the output pays the one psum).
    """
    B, H, D = q.shape
    P, S = k_pool.shape[0], k_pool.shape[1]
    N = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    mode = paged_attn_mode(mode)
    q = _constrain_heads(q, 1, tp_mesh, tp_axis)

    # the gather: every cached byte of the batch's context, exactly once,
    # addressed through the block table (pages, not contiguous buffers)
    k_pages = _constrain_heads(k_pool[block_table], 3, tp_mesh, tp_axis)
    v_pages = _constrain_heads(v_pool[block_table], 3, tp_mesh, tp_axis)

    if mode == "dense":
        k = k_pages.reshape(B, N * S, H, D)
        v = v_pages.reshape(B, N * S, H, D)
        return _constrain_heads(_dense_decode(q, k, v, ctx_len, scale),
                                1, tp_mesh, tp_axis)

    # page-blockwise online softmax: scan the page axis with the flash
    # recurrence — score width bounded at S, fp32 running (m, l, acc)
    ks = jnp.moveaxis(k_pages, 1, 0)       # [N, B, S, H, D]
    vs = jnp.moveaxis(v_pages, 1, 0)
    ctx = ctx_len[:, None, None]

    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, i = blk
        s = jnp.einsum("bhd,bshd->bhs", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        kpos = (i * S + lax.broadcasted_iota(jnp.int32, (1, 1, S), 2))
        s = jnp.where(kpos < ctx, s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhs,bshd->bhd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, D), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, acc0),
                              (ks, vs, jnp.arange(N)))
    out = acc / jnp.maximum(l, 1e-30)
    return _constrain_heads(out.astype(q.dtype), 1, tp_mesh, tp_axis)


def paged_latent_attention(q, pool, block_table, qpos, rank, scale,
                           layer=None):
    """Attention over a pool of LATENTS, in the absorbed form of
    multi-head latent attention: one cached vector a token serves every
    head as key (all of it) and as value (its first ``rank`` entries).

    ``q``: ``[B, Tq, H, C]`` absorbed queries (the key half of the
    expansion already applied, then the rotary part); ``pool``: ``[P, S,
    C]`` one layer's pages, or with a (static) ``layer`` the whole ``[L,
    P, S, C]`` pool, gathered from at that layer with no slice taken
    out first; ``block_table``: ``[B, N]``; ``qpos``:
    ``[B, Tq]`` the queries' absolute positions, ``< 0`` for a query
    that sees nothing (an idle lane: its output is zeros).  The queries'
    own latents must already be WRITTEN, so ONE gather through the block
    table covers the whole context, and query ``(b, j)`` sees positions
    ``<= qpos[b, j]``.  The one-token decode is ``Tq = 1``; the suffix
    of a prefix hit is ``B = 1``.  Scores are ``[B, Tq, H, N·S]``
    float32, one full-width masked softmax.  Returns ``o_lat [B, Tq, H,
    rank]`` in ``q.dtype``, to go through the value half of the
    expansion."""
    B, Tq, H, C = q.shape
    S = pool.shape[-2]
    N = block_table.shape[1]
    lat = (pool[block_table] if layer is None
           else pool[layer, block_table]).reshape(B, N * S, C)
    # queries and heads as ONE row axis: both products are then plain
    # batched matrix products over the lanes
    s = jnp.einsum("bmc,bkc->bmk", q.reshape(B, Tq * H, C), lat,
                   preferred_element_type=jnp.float32) * scale
    kpos = lax.broadcasted_iota(jnp.int32, (1, 1, 1, N * S), 3)
    p, l = _masked_softmax_stats(s.reshape(B, Tq, H, N * S),
                                 kpos <= qpos[:, :, None, None])
    p = (p / jnp.maximum(l, 1e-30)).reshape(B, Tq * H, N * S)
    out = jnp.einsum("bmk,bkc->bmc", p.astype(lat.dtype),
                     lat[..., :rank], preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, H, rank).astype(q.dtype)
