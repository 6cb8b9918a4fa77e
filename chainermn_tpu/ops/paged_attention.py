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

import functools
import numbers
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import role
from .flash_attention import _on_tpu

__all__ = ["paged_decode_attention", "paged_decode_kernel",
           "paged_prefill_attention",
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


def _page_slots(pool, layer):
    """``S`` of one layer's ``[P, S, ...]`` pool, or of the whole ``[L,
    P, S, ...]`` pool addressed at ``layer``."""
    return pool.shape[1 if layer is None else 2]


def _gather_pages(pool, layer, block_table):
    """The pages a block table names: from one layer's ``[P, S, ...]``
    pool, or (a ``layer``, a Python number or a traced scalar: the cache
    layer inside a device loop) from that layer of the whole ``[L, P, S,
    ...]`` pool with no slice taken out first."""
    return pool[block_table] if layer is None else pool[layer, block_table]


def _window_entries(block_table, first_pos, n):
    """The ``n`` block-table entries from the one that holds position
    ``first_pos`` on (``block_table [..., N]``, ``first_pos [...]``):
    ``(entries [..., n], the position of their first slot [...])``.  An
    entry past the table's end reads the last one; its positions, taken
    from where it would lie, are beyond every query and masked."""
    N = block_table.shape[-1]
    lo = jnp.maximum(first_pos, 0)
    idx = lo[..., None] + jnp.arange(n, dtype=jnp.int32)
    return jnp.take_along_axis(block_table, jnp.minimum(idx, N - 1),
                               axis=-1), lo


#: float32 scores a block of suffix queries may hold at once (256 MB):
#: 48 heads x 2048 queries x 10752 keys would be 4.2 GB
_PREFILL_SCORE_ELEMS = 1 << 26


def _grouped_attention(q, k, v, qpos, kpos, scale, window):
    """Masked attention of queries ``q [T, H, D]`` at positions ``qpos
    [T]`` over keys ``k``, ``v`` ``[K, G, D]`` at ``kpos [K]``, query
    head ``a`` reading K/V head ``a // (H // G)``: key j is seen where
    ``kpos[j] <= qpos[i]`` and, under a ``window``, ``qpos[i] - kpos[j] <
    window``.  The queries go in blocks (one full-width softmax each),
    so the float32 scores stay under :data:`_PREFILL_SCORE_ELEMS`."""
    T, H, D = q.shape
    K, G = k.shape[0], k.shape[1]
    r = H // G
    kt, vt = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)    # [G, K, D]
    qb = T
    while qb > 16 and qb % 2 == 0 and qb * H * K > _PREFILL_SCORE_ELEMS:
        qb //= 2

    def block(operands):
        q_blk, pos = operands                   # [qb, G, r, D], [qb]
        s = jnp.einsum("qgrd,gkd->grqk", q_blk, kt,
                       preferred_element_type=jnp.float32) * scale
        seen = kpos[None, :] <= pos[:, None]
        if window is not None:
            seen &= pos[:, None] - kpos[None, :] < window
        p, l = _masked_softmax_stats(s, seen[None, None])
        p = p / jnp.maximum(l, 1e-30)
        return jnp.einsum("grqk,gkd->qgrd", p.astype(vt.dtype), vt,
                          preferred_element_type=jnp.float32)

    out = lax.map(block, (q.reshape(T // qb, qb, G, r, D),
                          qpos.reshape(T // qb, qb)))
    return out.reshape(T, H, D).astype(q.dtype)


@role("attn")
def paged_prefill_attention(q, k_pool, v_pool, block_table_row, start,
                            true_len, scale=None, window=None, layer=None,
                            kv_heads=None):
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

    ``kv_heads=G`` (dividing ``H``): grouped K/V heads over ONE pool
    ``[P, S, 2 · G · D]`` given as ``k_pool`` (``v_pool`` is ``None``; as
    :func:`paged_decode_attention`'s): query head ``a``
    reads head ``a // (H // G)``, the queries go in blocks, and under a
    ``window`` (a multiple of the page size) query t sees positions
    ``(start + t - window, start + t]`` and the gather takes the
    ``(window + T) / S + 1`` entries from the one holding ``start -
    window + 1`` on, not the whole row.  ``layer``: as
    :func:`paged_latent_attention`'s.
    """
    T, H, D = q.shape
    S = _page_slots(k_pool, layer)
    N = block_table_row.shape[0]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if kv_heads is not None:
        G = kv_heads
        bt, first = block_table_row, jnp.int32(0)
        if window is not None:
            bt, first = _window_entries(
                bt, (start - window + 1) // S, (window + T) // S + 1)
        K = bt.shape[0] * S
        kpos = first * S + jnp.arange(K, dtype=jnp.int32)
        qpos = start + jnp.arange(T, dtype=jnp.int32)
        kv = _gather_pages(k_pool, layer, bt)
        return _grouped_attention(q, kv[..., :G * D].reshape(K, G, D),
                                  kv[..., G * D:].reshape(K, G, D), qpos,
                                  kpos, scale, window)
    k = _gather_pages(k_pool, layer, block_table_row).reshape(N * S, H, D)
    v = _gather_pages(v_pool, layer, block_table_row).reshape(N * S, H, D)
    s = jnp.einsum("thd,khd->htk", q, k,
                   preferred_element_type=jnp.float32) * scale
    kpos = lax.broadcasted_iota(jnp.int32, (1, 1, N * S), 2)
    qpos = start + lax.broadcasted_iota(jnp.int32, (1, T, 1), 1)
    p, l = _masked_softmax_stats(s, kpos <= qpos)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("htk,khd->thd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@role("attn")
def paged_verify_attention(q, k_pool, v_pool, block_table, start,
                           scale=None, tp_mesh=None, tp_axis="tp",
                           layer=None):
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
    ``q.dtype``.  The pools and ``layer``: as
    :func:`paged_decode_attention`'s.

    This is the whole speculative bargain in one shape: the dense-side
    cost of scoring K extra tokens rides the SAME cache-byte reads the
    single-query step already pays, so accepted tokens are (HBM-wise)
    free — dispatch count per emitted token drops by ``1/(1 +
    accepted)``.
    """
    B, K1, H, D = q.shape
    S = _page_slots(k_pool, layer)
    N = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    q = _constrain_heads(q, 2, tp_mesh, tp_axis)
    k, v = (_constrain_heads(
        _gather_pages(pool, layer, block_table).reshape(B, N * S, H, D),
        2, tp_mesh, tp_axis) for pool in (k_pool, v_pool))
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


def _grouped_decode(q, pool, block_table, ctx_len, scale, window, layer,
                    kv_heads):
    """One query a lane over grouped K/V heads: ``q [B, H, D]``, the
    pool ``[(L,) P, S, 2 · G · D]`` (a token's K then its V, each its
    ``G`` heads side by side in the lanes).  One full-width masked
    softmax a K/V head over what ONE gather brings: the whole block
    table, or under a ``window`` the ``window / S + 1`` entries that end
    at the lane's position, so a window layer's reads do not grow with
    the context.  Each K/V head's queries contract against its own ``D``
    lanes of the gathered pages (slices of whole lane tiles, taken from
    the one gathered array): no head is repeated, nothing is transposed
    and the K and V halves are never made."""
    B, H, D = q.shape
    S, G = pool.shape[-2], kv_heads
    r = H // G
    qpos = ctx_len - 1
    bt, first = block_table, jnp.zeros(B, jnp.int32)
    if window is not None:
        bt, first = _window_entries(bt, qpos // S - window // S,
                                    window // S + 1)
    K = bt.shape[1] * S
    kv = _gather_pages(pool, layer, bt).reshape(B, K, 2 * G * D)
    kpos = first[:, None] * S + jnp.arange(K, dtype=jnp.int32)
    seen = kpos <= qpos[:, None]
    if window is not None:
        seen &= qpos[:, None] - kpos < window
    out = []
    for g in range(G):
        s = jnp.einsum("brd,bkd->brk", q[:, g * r:(g + 1) * r],
                       kv[..., g * D:(g + 1) * D],
                       preferred_element_type=jnp.float32) * scale
        p, l = _masked_softmax_stats(s, seen[:, None, :])
        p = p / jnp.maximum(l, 1e-30)
        out.append(jnp.einsum(
            "brk,bkd->brd", p.astype(kv.dtype),
            kv[..., (G + g) * D:(G + g + 1) * D],
            preferred_element_type=jnp.float32))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


#: Pages a step of the decode kernel brings in at once (16 pages of 16
#: tokens: 1 MB of K and V), two such buffers in flight.
_DECODE_CHUNK_PAGES = 16


def _paged_decode_kernel(*refs, layer, page, width, chunk, window, scale,
                         n_entries):
    """One lane's query heads over its pages, READ IN PLACE: the pages
    its block table names are copied from the pool in HBM straight into
    one of two VMEM buffers, ``chunk`` pages at a time, the next chunk
    in flight while this one is scored (online softmax across chunks).
    Nothing is gathered into a temporary first, a lane stops at its own
    context (not at the block table's length), and under a ``window``
    it starts at the first page the window touches.

    ``refs``: ``bt_ref, ctx_ref, q_ref, pool_ref, o_ref, buf, sem``
    behind a static ``layer``; with ``layer=None`` the pool's layer is a
    scalar the caller prefetched (a loop's cache layer, known on the
    device alone), ``layer_ref [1]`` ahead of the rest.

    ``q_ref [Hp, width]``: every query head as a row of ALL ``width = G
    · D`` key lanes, zero outside its own K/V head's ``D``, so the chunk
    is scored by ONE product against its K half and weighed by one
    against its V half, whatever the grouping (the caller keeps each
    head's own ``D`` lanes of ``o_ref [Hp, width]``)."""
    if layer is None:
        layer_ref, *refs = refs
        layer = layer_ref[0]
    bt_ref, ctx_ref, q_ref, pool_ref, o_ref, buf, sem = refs
    b = pl.program_id(0)
    ctx = ctx_ref[b]
    qpos = ctx - 1
    last = jnp.maximum(qpos, 0) // page
    first = 0 if window is None else jnp.maximum(last - window // page, 0)
    n_chunks = jnp.where(ctx > 0, (last - first) // chunk + 1, 0)
    q = q_ref[...]

    def copies(c, slot):
        out = []
        for j in range(chunk):
            entry = jnp.minimum(first + c * chunk + j, n_entries - 1)
            out.append(pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[b, entry]],
                buf.at[slot, pl.ds(j * page, page)], sem.at[slot]))
        return out

    @pl.when(n_chunks > 0)
    def _():
        for dma in copies(0, 0):
            dma.start()

    def body(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            for dma in copies(c + 1, 1 - slot):
                dma.start()
        for dma in copies(c, slot):
            dma.wait()
        kv = buf[slot]
        s = lax.dot_general(q, kv[:, :width], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        # positions as the entries WOULD lie: one past the table's end
        # re-reads the last page under a mask that hides all of it
        kpos = (first + c * chunk) * page + lax.broadcasted_iota(
            jnp.int32, (1, chunk * page), 1)
        seen = kpos <= qpos
        if window is not None:
            seen &= qpos - kpos < window
        s = jnp.where(seen, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + lax.dot_general(
            p.astype(kv.dtype), kv[:, width:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    rows = q.shape[0]
    _, l, acc = lax.fori_loop(
        0, n_chunks, body,
        (jnp.full((rows, 1), -jnp.inf, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, width), jnp.float32)))
    o_ref[...] = acc / jnp.maximum(l, 1e-30)


@role("attn")
def paged_decode_kernel(q, pool, block_table, ctx_len, *, kv_heads, layer,
                        window=None, scale=None, interpret=False):
    """The grouped decode step as a Pallas kernel over ONE pool ``[L, P,
    S, 2 · G · D]`` (a token's K then its V): what
    :func:`paged_decode_attention` runs on a TPU for ``kv_heads=`` and
    ``v_pool=None``; the arguments are its.  ``layer`` is a Python
    number baked into the kernel, or a traced scalar (the cache layer
    inside a device loop over passes) that the kernel takes as one more
    prefetched scalar.  An idle lane (``ctx_len`` 0) reads nothing and
    gives zeros."""
    B, H, D = q.shape
    G, r = kv_heads, q.shape[1] // kv_heads
    page, width = pool.shape[-2], kv_heads * D
    rows = -(-H // 16) * 16                     # whole bfloat16 tiles
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    own = (jnp.arange(H)[:, None] // r == jnp.arange(G)[None, :])
    qx = (q[:, :, None, :] * own[None, :, :, None].astype(q.dtype)) \
        .reshape(B, H, width)
    qx = jnp.pad(qx, ((0, 0), (0, rows - H), (0, 0)))
    prefetch = (block_table, ctx_len)
    if isinstance(layer, numbers.Integral):
        layer = int(layer)
    else:
        prefetch = (jnp.reshape(layer, (1,)).astype(jnp.int32),) + prefetch
        layer = None
    kernel = functools.partial(
        _paged_decode_kernel, layer=layer, page=page, width=width,
        chunk=_DECODE_CHUNK_PAGES, window=window, scale=scale,
        n_entries=block_table.shape[1])
    out = pl.pallas_call(
        kernel,
        name="_paged_decode_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B,),
            in_specs=[pl.BlockSpec((None, rows, width),
                                   lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, rows, width),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, _DECODE_CHUNK_PAGES * page, 2 * width),
                           pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, rows, width), jnp.float32),
        interpret=interpret,
    )(*prefetch, qx, pool)
    # each head's own D lanes of its row
    out = out[:, :H].reshape(B, G, r, G, D)
    return jnp.einsum("bgrgd->bgrd", out).reshape(B, H, D).astype(q.dtype)


@role("attn")
def paged_decode_attention(q, k_pool, v_pool, block_table, ctx_len,
                           scale=None, mode=None, tp_mesh=None,
                           tp_axis="tp", window=None, layer=None,
                           kv_heads=None):
    """One decode step of attention for a batch of cached sequences.

    q: ``[B, H, D]`` — ONE query token per sequence (the just-appended
    position).  ``k_pool``/``v_pool``: ``[P, S, H · D]`` page pools
    (``P`` pages of ``S`` token slots, a token's heads side by side in
    the lanes; ``[P, S, H, D]`` reads the same), or with a ``layer`` the
    whole ``[L, P, S, H · D]`` pools, gathered from at that layer with
    no slice taken out first.  ``block_table``: ``[B, N]``
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

    ``kv_heads=G`` (dividing ``H``): GROUPED K/V heads over ONE pool
    ``[P, S, 2 · G · D]`` given as ``k_pool`` (``v_pool`` is ``None``):
    a token's K then its V, each its heads side by side in the lanes
    (one pool, so that one read brings both; and with four axes after the layer's, a pool of a minor ``[8, 128]`` is
    relaid whole by a program that wants the heads elsewhere: a one-lane
    decode copied the 0.7 GB window pool into 11 GB of padding on the
    chip, PR 31); ``window`` (a multiple of the page size) then bounds
    both the mask and the reads; ``layer``: as
    :func:`paged_latent_attention`'s.  That form has the one lowering
    a backend, and no head axis is sharded for it: on a TPU, over one
    pool at a ``layer``, the Pallas kernel that reads the pages in place
    (:func:`paged_decode_kernel`: an XLA gather of 21504 pages of 32 KB
    ran at a tenth of the chip's bandwidth, PR 31); elsewhere one gather
    a pool and a masked softmax (:func:`_grouped_decode`).
    """
    B, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if kv_heads is not None:
        if layer is not None and _on_tpu():
            return paged_decode_kernel(
                q, k_pool, block_table, ctx_len, kv_heads=kv_heads,
                layer=layer, window=window, scale=scale)
        return _grouped_decode(q, k_pool, block_table, ctx_len, scale,
                               window, layer, kv_heads)
    S = _page_slots(k_pool, layer)
    N = block_table.shape[1]
    mode = paged_attn_mode(mode)
    q = _constrain_heads(q, 1, tp_mesh, tp_axis)

    # the gather: every cached byte of the batch's context, exactly once,
    # addressed through the block table (pages, not contiguous buffers);
    # the heads are split on what it brought, never on the pool
    k_pages, v_pages = (_constrain_heads(
        _gather_pages(pool, layer, block_table).reshape(B, N, S, H, D),
        3, tp_mesh, tp_axis) for pool in (k_pool, v_pool))

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


@role("attn")
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
