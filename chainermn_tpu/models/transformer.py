"""Transformer language model with sequence-parallel attention.

Beyond-reference model family (ChainerMN predates transformers; SURVEY.md
§5 long-context note prescribes ring/Ulysses layers as the rebuild's
long-context story).  TPU-first: pre-norm blocks whose FLOPs are three
fused GEMMs (qkv, attention output, MLP), ``ops.attention`` dispatching
to the Pallas flash kernel on TPU, and a ``sequence_parallel`` mode that
shards the sequence over a communicator axis — attention runs as ring
attention (ppermute KV rotation) or Ulysses (all_to_all head exchange)
while every other op stays position-local, so the same weights serve
single-chip and sequence-parallel execution bit-compatibly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.link import Chain, ChainList
from ..core import reporter
from ..nn import functions as F
from ..nn import links as L
from ..observability import role
from ..ops import attention as fused_attention
from ..ops import merge_heads, self_attention, split_heads
from ..ops.paged_attention import (head_sharding, paged_decode_attention,
                                   paged_prefill_attention,
                                   paged_verify_attention)
from ..serving.kv_cache import (write_prompt_kv, write_prompt_kv_at,
                                write_span_kv, write_token_kv)

__all__ = ["MultiHeadAttention", "TransformerBlock", "TransformerLM"]


def _axis_bound(comm):
    # a hierarchical communicator's axis_name is a (dcn, ici) TUPLE and
    # ALL of its axes must be bound — a bare axis_exists(tuple) probe is
    # False, which used to silently drop parallel layers (the MoE block
    # fell back to dense routing on a two-level mesh; ISSUE 12 guard
    # rail).  Communicators own the multi-axis form of this query.
    if comm is None or comm.axis_name is None:
        return False
    check = getattr(comm, "axis_in_scope", None)
    if check is not None:
        return check()
    from jax._src.core import get_axis_env
    names = comm.axis_name if isinstance(comm.axis_name, (tuple, list)) \
        else (comm.axis_name,)
    return all(get_axis_env().axis_exists(n) for n in names)


class MultiHeadAttention(Chain):
    def __init__(self, d_model, n_heads, seed=0, sp_comm=None,
                 sp_mode="ring"):
        super().__init__()
        assert d_model % n_heads == 0
        self.n_heads = n_heads
        self.d_head = d_model // n_heads
        self.sp_comm = sp_comm
        self.sp_mode = sp_mode
        with self.init_scope():
            self.qkv = L.Linear(d_model, 3 * d_model, seed=seed)
            self.proj = L.Linear(d_model, d_model, seed=seed + 1)

    def forward(self, x, causal=True):
        B, T, D = x.shape
        qkv = self.qkv(x.reshape(B * T, D)).reshape(B, T, 3 * D)
        if _axis_bound(self.sp_comm):
            # ring and Ulysses exchange KV blocks or heads: heads first
            q, k, v = split_heads(qkv, self.n_heads)
            if self.sp_mode in ("ring", "zigzag"):
                from ..parallel import ring_self_attention
                schedule = "zigzag" if self.sp_mode == "zigzag" else "naive"
                out = ring_self_attention(self.sp_comm, q, k, v,
                                          causal=causal, schedule=schedule)
            else:
                from ..parallel import ulysses_attention
                out = ulysses_attention(self.sp_comm, q, k, v,
                                        causal=causal)
            out = merge_heads(out)
        else:
            # the kernels read these rows as they lie where they can,
            # else split the heads as above
            out = self_attention(qkv, self.n_heads, causal=causal)
        return self.proj(out.reshape(B * T, D)).reshape(B, T, D)


class TransformerBlock(Chain):
    def __init__(self, d_model, n_heads, d_ff=None, seed=0, sp_comm=None,
                 sp_mode="ring"):
        super().__init__()
        d_ff = d_ff or 4 * d_model
        with self.init_scope():
            self.ln1 = L.LayerNormalization(d_model)
            self.attn = MultiHeadAttention(d_model, n_heads, seed=seed,
                                           sp_comm=sp_comm, sp_mode=sp_mode)
            self.ln2 = L.LayerNormalization(d_model)
            self.fc1 = L.Linear(d_model, d_ff, seed=seed + 10)
            self.fc2 = L.Linear(d_ff, d_model, seed=seed + 11)

    def forward(self, x, causal=True):
        B, T, D = x.shape
        with role("norm"):
            a = self.ln1(x)
        with role("attn_proj"):     # the kernels inside open ``attn``
            h = x + self.attn(a, causal=causal)
        with role("norm"):
            m = self.ln2(h)
        with role("mlp"):
            m = self.fc2(F.gelu(self.fc1(m.reshape(B * T, D))))
            return h + m.reshape(B, T, D)


def _remat_policy(remat):
    """Map the ``remat`` knob to a ``jax.checkpoint`` policy.

    ``True``/``"full"`` — save nothing (maximal memory saving, full
    recompute; the plain long-context lever).  ``"dots"`` — save
    weight-GEMM outputs, recompute elementwise/attention
    (``dots_with_no_batch_dims_saveable``: the transformer-standard
    trade — backward skips re-running the big MXU GEMMs at a modest
    activation-memory cost, typically better MFU at long sequence than
    full remat).  Any other string resolves as an attribute of
    ``jax.checkpoint_policies``."""
    if remat in (True, "full"):
        return None
    if remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    policy = getattr(jax.checkpoint_policies, str(remat), None)
    if policy is None:
        raise ValueError(
            f"unknown remat policy {remat!r}; use True/'full', 'dots', "
            "or a jax.checkpoint_policies attribute name")
    return policy


# -- the serving interface (ServingEngine; docs/serving.md) -------------------

class _ServingMixin:
    """What :class:`~chainermn_tpu.serving.ServingEngine` asks of a model:
    the cache entry a token leaves in a layer, the context limit, and
    the block's prefill / suffix prefill / decode (and, here, the
    speculative verify) over the whole page pools, written and read in
    place at each layer.  Each
    runs with the parameters bound (``bind_state``) inside one of the
    engine's compiled programs and returns ``(pools, logits, extras)``,
    ``extras`` a tuple of further device values for the engine's spans
    (none here)."""

    #: the dtype the engine holds the parameters in (``None``: as loaded)
    serve_param_dtype = None

    @property
    def serve_max_context(self):
        """Learned positions: the table's rows."""
        return self.pos_embed.W.shape[0]

    @property
    def serve_cache_layers(self):
        return len(self.blocks)

    @property
    def serve_page_dtype(self):
        return self.compute_dtype or jnp.float32

    def serve_cache_entry(self):
        """K and V of ``[H · D]`` a token a layer, the heads side by side
        in the lanes: two pools, each stored as the programs compute on
        it (heads of 64 as a minor axis of their own are half a lane
        tile, and the chip converted each whole pool between two layouts
        twice a step: PR 43)."""
        attn = self.blocks[0].attn
        return ((attn.n_heads * attn.d_head,),) * 2

    def serve_pool_sharding(self, mesh):
        """Tensor-parallel decode: the pools shard over their lanes by
        whole HEADS (the ulysses layout), so the mesh must divide the
        heads; and where a token's row is whole lane tiles a shard's
        must be too, or each shard is back to the layout this entry
        exists to avoid."""
        attn = self.blocks[0].attn
        row = attn.n_heads * attn.d_head
        if attn.n_heads % mesh.size:
            raise ValueError(f"tp={mesh.size} must divide n_heads="
                             f"{attn.n_heads}")
        if row % 128 == 0 and (row // mesh.size) % 128:
            raise ValueError(
                f"tp={mesh.size} leaves a shard {row // mesh.size} of a "
                f"token's {row} lanes: not whole tiles of 128")
        return head_sharding(mesh, 4, 3)

    def _serve_embed(self, toks, positions):
        """Token + position embeddings cast to the model's compute dtype
        (the ``hidden`` discipline: params fp32, block compute in
        ``compute_dtype``)."""
        with role("embed"):
            h = self.embed(toks) + self.pos_embed(positions)
            if self.compute_dtype is not None:
                h = h.astype(self.compute_dtype)
        return h

    def _serve_mlp(self, block, h):
        """The block's second half over ``h [..., D]``: norm, the MLP
        and the residual add, each under its role."""
        with role("norm"):
            m = block.ln2(h).reshape(-1, h.shape[-1])
        with role("mlp"):
            return h + block.fc2(F.gelu(block.fc1(m))).reshape(h.shape)

    def _serve_last_logits(self, h, true_len):
        """The fp32 ``[V]`` logits row of a prefill at position
        ``true_len - 1`` of ``h [1, T, D]``."""
        with role("head"):
            h_last = jax.lax.dynamic_slice_in_dim(
                h[0], jnp.maximum(true_len - 1, 0), 1, axis=0)
            return self.head(self.ln_f(h_last))[0].astype(jnp.float32)

    def serve_prefill(self, pools, tokens, true_len, bt_row):
        """Full causal forward over the (padded) prompt ``tokens [1,
        Tb]`` (positions ``>= true_len`` are padding — their K/V writes
        drop, and causality keeps them out of every valid position's
        attention).  ``logits``: the fp32 ``[V]`` row at position
        ``true_len - 1``."""
        k_pool, v_pool = pools
        B, T = tokens.shape
        with role("embed"):
            pos = jax.lax.broadcasted_iota(jnp.int32, (B, T), 1)
        h = self._serve_embed(tokens, pos)
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                with role("norm"):
                    x = block.ln1(h)
                with role("attn_proj"):
                    # a token's K and V rows as the GEMM leaves them are
                    # what the cache holds
                    rows = jnp.split(
                        block.attn.qkv(x.reshape(B * T, -1)), 3, axis=-1)
                    q, k, v = [jnp.moveaxis(r.reshape(
                        B, T, block.attn.n_heads, block.attn.d_head), 1, 2)
                        for r in rows]
                # the flash dispatcher: Pallas forward on TPU (no backward
                # is ever traced — inference), XLA/interpret elsewhere
                att = fused_attention(q, k, v, causal=True)
                with role("attn_proj"):
                    att = jnp.moveaxis(att, 2, 1).reshape(B * T, -1)
                    h = h + block.attn.proj(att).reshape(B, T, -1)
                h = self._serve_mlp(block, h)
                k_pool = write_prompt_kv(k_pool, rows[1], bt_row, true_len,
                                         layer=li)
                v_pool = write_prompt_kv(v_pool, rows[2], bt_row, true_len,
                                         layer=li)
        return (k_pool, v_pool), self._serve_last_logits(h, true_len), ()

    def serve_suffix_prefill(self, pools, tokens, true_len, start, bt_row):
        """SUFFIX prefill for a prefix-shared request (round 14), and
        one chunk of a chunked one.  Suffix index ``t`` sits at absolute
        position ``start + t``; ``bt_row`` covers the WHOLE context
        (shared prefix pages + the request's fresh suffix pages).  Per
        layer the suffix's K/V scatter through the offset writer FIRST,
        then one gather per pool reads the whole context back and the
        suffix queries run one masked softmax against it
        (:func:`~chainermn_tpu.ops.paged_attention.paged_prefill_attention`)
        — ZERO flash kernels touch the shared pages, and the score
        matrix is suffix-by-context, never context-by-context: skipping
        the matched prefix's O(L²) attention and O(L·d²) projections is
        the FLOP saving the prefix hit buys."""
        k_pool, v_pool = pools
        B, T = tokens.shape
        with role("embed"):
            pos = start + jax.lax.broadcasted_iota(jnp.int32, (B, T), 1)
        h = self._serve_embed(tokens, pos)
        scale = 1.0 / (self.blocks[0].attn.d_head ** 0.5)
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                with role("norm"):
                    x = block.ln1(h)
                with role("attn_proj"):
                    q, k, v = jnp.split(
                        block.attn.qkv(x.reshape(B * T, -1)), 3, axis=-1)
                    q = q.reshape(T, block.attn.n_heads, block.attn.d_head)
                k_pool = write_prompt_kv_at(k_pool, k, bt_row, start,
                                            true_len, layer=li)
                v_pool = write_prompt_kv_at(v_pool, v, bt_row, start,
                                            true_len, layer=li)
                att = paged_prefill_attention(
                    q, k_pool, v_pool, bt_row, start, true_len,
                    scale=scale, layer=li)
                with role("attn_proj"):
                    h = h + block.attn.proj(att.reshape(B * T, -1)) \
                        .reshape(B, T, -1)
                h = self._serve_mlp(block, h)
        return (k_pool, v_pool), self._serve_last_logits(h, true_len), ()

    def serve_decode(self, pools, toks, pos, bts, mode=None, tp_mesh=None):
        """One token per batch lane (``pos < 0`` marks an idle padding
        lane: its K/V write drops and its attention context is empty).
        Writes each lane's K/V at ``pos`` then attends over ``[0, pos]``
        through the block table.  ``tp_mesh``: the tensor-parallel mesh
        — pools arrive head-sharded and the attention op constrains its
        gathers to stay that way.  ``logits``: ``[Bb, V]`` fp32."""
        k_pool, v_pool = pools
        Bb = toks.shape[0]
        with role("embed"):
            safe_pos = jnp.maximum(pos, 0)
        h = self._serve_embed(toks, safe_pos)
        with role("attn"):
            ctx_len = jnp.where(pos >= 0, pos + 1, 0)
        scale = 1.0 / (self.blocks[0].attn.d_head ** 0.5)
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                with role("norm"):
                    x = block.ln1(h)
                with role("attn_proj"):
                    q, k, v = jnp.split(block.attn.qkv(x), 3, axis=-1)
                    q = q.reshape(Bb, block.attn.n_heads, block.attn.d_head)
                k_pool = write_token_kv(k_pool, k, bts, pos, layer=li)
                v_pool = write_token_kv(v_pool, v, bts, pos, layer=li)
                att = paged_decode_attention(
                    q, k_pool, v_pool, bts, ctx_len, scale=scale,
                    mode=mode, tp_mesh=tp_mesh, layer=li)
                with role("attn_proj"):
                    h = h + block.attn.proj(att.reshape(Bb, -1))
                h = self._serve_mlp(block, h)
        with role("head"):
            logits = self.head(self.ln_f(h)).astype(jnp.float32)
        return (k_pool, v_pool), logits, ()

    def serve_verify(self, pools, toks, start, n_valid, bts, tp_mesh=None):
        """Speculative VERIFY: score K+1 tokens per lane in one dispatch
        (round 20).  ``toks``: ``[Bb, K1]`` — lane ``b``'s pending token
        followed by its K draft proposals; token ``j`` sits at absolute
        position ``start[b] + j`` (``start < 0`` = idle lane); only the
        first ``n_valid[b]`` span slots write K/V.  Per layer: ONE
        drop-fenced span scatter per pool (``write_span_kv``), then ONE
        gather per pool and a multi-query masked softmax over the block
        tables (``paged_verify_attention``) — query ``j`` sees exactly
        positions ``<= start + j``, the context a vanilla decode step at
        that position would see.  ``logits``: ``[Bb, K1, V]`` fp32."""
        k_pool, v_pool = pools
        Bb, K1 = toks.shape
        with role("embed"):
            safe_start = jnp.maximum(start, 0)
            pos = safe_start[:, None] \
                + jnp.arange(K1, dtype=jnp.int32)[None]
        h = self._serve_embed(toks, pos)
        scale = 1.0 / (self.blocks[0].attn.d_head ** 0.5)
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                with role("norm"):
                    x = block.ln1(h)
                with role("attn_proj"):
                    q, k, v = jnp.split(
                        block.attn.qkv(x.reshape(Bb * K1, -1)).reshape(
                            Bb, K1, -1), 3, axis=-1)
                    q = q.reshape(Bb, K1, block.attn.n_heads,
                                  block.attn.d_head)
                k_pool = write_span_kv(k_pool, k, bts, start, n_valid,
                                       layer=li)
                v_pool = write_span_kv(v_pool, v, bts, start, n_valid,
                                       layer=li)
                att = paged_verify_attention(
                    q, k_pool, v_pool, bts, start, scale=scale,
                    tp_mesh=tp_mesh, layer=li)
                with role("attn_proj"):
                    h = h + block.attn.proj(att.reshape(Bb * K1, -1)) \
                        .reshape(Bb, K1, -1)
                h = self._serve_mlp(block, h)
        with role("head"):
            logits = self.head(self.ln_f(h.reshape(Bb * K1, -1))) \
                .reshape(Bb, K1, -1).astype(jnp.float32)
        return (k_pool, v_pool), logits, ()


class TransformerLM(Chain, _ServingMixin):
    """Causal LM.  ``sequence_parallel``: pass ``sp_comm`` and call inside
    a program sharding the T dimension over its axis.  Position ids are
    supplied automatically when the axis is bound: contiguous offsets for
    ``sp_mode="ring"``/``"ulysses"`` (rank · T_local), the two-half-chunk
    layout for ``sp_mode="zigzag"`` (the balanced causal ring — shard
    inputs/targets with ``parallel.zigzag_shard`` along T).

    ``remat``: ``False`` | ``True``/``"full"`` | ``"dots"`` | any
    ``jax.checkpoint_policies`` name — see :func:`_remat_policy`."""

    def __init__(self, n_vocab, d_model=128, n_heads=4, n_layers=2,
                 max_len=2048, seed=0, sp_comm=None, sp_mode="ring",
                 remat=False, compute_dtype=None):
        super().__init__()
        self.sp_comm = sp_comm
        self.sp_mode = sp_mode
        self.remat = remat
        self.compute_dtype = compute_dtype
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.pos_embed = L.EmbedID(max_len, d_model, seed=seed + 1)
            self.blocks = ChainList(*[
                TransformerBlock(d_model, n_heads, seed=seed + 100 * (i + 1),
                                 sp_comm=sp_comm, sp_mode=sp_mode)
                for i in range(n_layers)])
            self.ln_f = L.LayerNormalization(d_model)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    def hidden(self, x):
        with role("embed"):
            h = self._embed(x)
        with jax.named_scope("blocks"):
            h = self._run_blocks(h)
        with role("head"):
            return self.ln_f(h)

    def _embed(self, x):
        B, T = x.shape
        if _axis_bound(self.sp_comm) and self.sp_mode == "zigzag":
            # zigzag layout: rank i holds global half-chunks i and
            # 2n−1−i, so its positions are two disjoint ranges
            n = self.sp_comm.size
            i = jax.lax.axis_index(self.sp_comm.axis_name)
            h = T // 2
            local = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            pos = jnp.where(local < h,
                            i * h + local,
                            (2 * n - 1 - i) * h + (local - h))
        else:
            offset = 0
            if _axis_bound(self.sp_comm):
                offset = jax.lax.axis_index(self.sp_comm.axis_name) * T
            pos = offset + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        h = self.embed(x) + self.pos_embed(jnp.broadcast_to(pos, (B, T)))
        if self.compute_dtype is not None:
            # params stay fp32; all block compute (matmuls, attention,
            # residual stream) runs in the compute dtype — LN/softmax
            # statistics are fp32 internally (nn.functions discipline)
            h = h.astype(self.compute_dtype)
        return h

    def _run_blocks(self, h):
        for block in self.blocks:
            if self.remat:
                # per-block rematerialization: backward recomputes the
                # block, trading FLOPs for activation memory — the lever
                # for long contexts (blocks hold no persistent state, so
                # closing over bound params is safe).  The policy decides
                # WHAT to recompute (see _remat_policy): "dots" keeps the
                # GEMM outputs so the backward re-runs only the cheap
                # elementwise/attention tail.
                h = jax.checkpoint(lambda hh, blk=block: blk(hh),
                                   policy=_remat_policy(self.remat))(h)
            else:
                h = block(h)
        return h

    def logits(self, x):
        B, T = x.shape
        h = self.hidden(x)
        with role("head"):
            return self.head(h.reshape(B * T, -1)).reshape(B, T, -1)

    def forward(self, x, t):
        """LM loss with ignore_label=-1 padding."""
        logits = self.logits(x)
        with role("loss"):
            loss = F.softmax_cross_entropy(
                logits.reshape(-1, logits.shape[-1]), t.reshape(-1),
                ignore_label=-1)
        reporter.report({"loss": loss}, self)
        return loss


# -- incremental decoding (KV cache) ----------------------------------------

def _attend_cached(q, k_cache, v_cache, pos, scale):
    """q: [B,H,1,D]; caches [B,H,Tmax,D]; attend over positions ≤ pos."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    Tmax = k_cache.shape[2]
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, Tmax), 3)
    s = jnp.where(kpos <= pos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v_cache.astype(jnp.float32))


class _GenerationMixin:
    """Greedy / temperature sampling with per-layer KV caches."""

    def init_cache(self, batch, max_len):
        H = self.blocks[0].attn.n_heads
        D = self.blocks[0].attn.d_head
        n = len(self.blocks)
        shape = (n, 2, batch, H, max_len, D)
        return jnp.zeros(shape, jnp.float32)

    def _prefill(self, prompt, cache):
        """Full-forward pass over the prompt, capturing per-layer K/V into
        the cache; returns (cache, last-position logits)."""
        B, T0 = prompt.shape
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, T0), 1)
        h = self.embed(prompt) + self.pos_embed(
            jnp.broadcast_to(pos, (B, T0)))
        for i, block in enumerate(self.blocks):
            x = block.ln1(h)
            qkv = block.attn.qkv(x.reshape(B * T0, -1)).reshape(
                B, T0, 3, block.attn.n_heads, block.attn.d_head)
            q, k, v = [jnp.moveaxis(qkv[:, :, j], 1, 2) for j in range(3)]
            cache = cache.at[i, 0, :, :, :T0].set(k.astype(jnp.float32))
            cache = cache.at[i, 1, :, :, :T0].set(v.astype(jnp.float32))
            from ..ops import xla_attention
            att = xla_attention(q, k, v, causal=True)
            att = jnp.moveaxis(att, 2, 1).reshape(B * T0, -1)
            h = h + block.attn.proj(att).reshape(B, T0, -1)
            m = block.fc2(F.gelu(block.fc1(
                block.ln2(h).reshape(B * T0, -1))))
            h = h + m.reshape(B, T0, -1)
        h = self.ln_f(h)
        logits = self.head(h[:, -1])
        return cache, logits

    def _step_logits(self, tok, pos, cache):
        """One-token forward through all blocks using/updating the cache."""
        B = tok.shape[0]
        h = self.embed(tok)[:, None] + self.pos_embed(
            jnp.full((B, 1), pos))
        new_cache = cache
        for i, block in enumerate(self.blocks):
            x = block.ln1(h)
            qkv = block.attn.qkv(x.reshape(B, -1)).reshape(
                B, 1, 3, block.attn.n_heads, block.attn.d_head)
            q, k, v = [jnp.moveaxis(qkv[:, :, j], 1, 2) for j in range(3)]
            k_cache = jax.lax.dynamic_update_slice(
                new_cache[i, 0], k.astype(jnp.float32), (0, 0, pos, 0))
            v_cache = jax.lax.dynamic_update_slice(
                new_cache[i, 1], v.astype(jnp.float32), (0, 0, pos, 0))
            new_cache = new_cache.at[i, 0].set(k_cache).at[i, 1].set(v_cache)
            scale = 1.0 / (block.attn.d_head ** 0.5)
            att = _attend_cached(q, k_cache, v_cache, pos, scale)
            att = jnp.moveaxis(att, 2, 1).reshape(B, 1, -1)
            h = h + block.attn.proj(att.reshape(B, -1))[:, None]
            m = block.fc2(F.gelu(block.fc1(block.ln2(h).reshape(B, -1))))
            h = h + m[:, None]
        h = self.ln_f(h)
        logits = self.head(h.reshape(B, -1))
        return logits, new_cache

    def generate(self, prompt, max_new_tokens, temperature=0.0, key=None):
        """Autoregressive continuation as one compiled scan.

        ``prompt``: int [B, T0].  ``temperature=0`` → greedy; otherwise
        requires ``key``.  Returns [B, max_new_tokens].
        """
        B, T0 = prompt.shape
        max_len = T0 + max_new_tokens
        cache = self.init_cache(B, max_len)
        # batched prefill: one full forward over the prompt fills every
        # layer's K/V cache (MXU-sized GEMMs instead of T0 tiny steps)
        cache, logits = self._prefill(prompt, cache)

        def pick(logits, k):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                k, logits / temperature, axis=-1).astype(jnp.int32)

        key = key if key is not None else jax.random.PRNGKey(0)

        def step(carry, i):
            cache, logits, key = carry
            key, sub = jax.random.split(key)
            tok = pick(logits, sub)
            new_logits, cache = self._step_logits(tok, T0 + i, cache)
            return (cache, new_logits, key), tok

        (_, _, _), toks = jax.lax.scan(
            step, (cache, logits, key), jnp.arange(max_new_tokens))
        return jnp.swapaxes(toks, 0, 1)


# graft generation onto the LM (kept separate for readability)
TransformerLM.init_cache = _GenerationMixin.init_cache
TransformerLM._prefill = _GenerationMixin._prefill
TransformerLM._step_logits = _GenerationMixin._step_logits
TransformerLM.generate = _GenerationMixin.generate
