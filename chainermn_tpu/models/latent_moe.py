"""Latent-attention language model with a share of routed experts.

The DeepSeek-V3 block (also ``kimi_k2``): pre-norm RMSNorm, multi-head
latent attention (MLA: queries and keys/values through low-rank
bottlenecks, one rotary key shared by all heads, YaRN-scaled rotary
positions), a leading dense SwiGLU layer, then layers of sigmoid-routed
SwiGLU experts beside one shared expert, a final RMSNorm and an untied
head.  The expert layer is :class:`~chainermn_tpu.parallel.moe.HeldExperts`:
the router scores every expert, this chip computes the ones it holds.

What is cached for a token, per layer, is the latent the keys and values
are expanded from: ``c_kv`` after its norm and the shared key after
rotation, ``kv_rank + rope_dim`` values.  A whole prompt attends in the
EXPANDED form (per-head keys and values through the flash dispatcher);
anything that reads the cache back (the one-token decode, the suffix of a
prefix hit) attends in the ABSORBED form, where the key half of ``W_kvb``
moves onto the query and the value half onto the output, so the scores
and the weighted sum are taken over the latents themselves
(:func:`~chainermn_tpu.ops.paged_attention.paged_latent_attention`).
The two are the same mathematics; ``tests/models_tests`` holds them
together.

The class serves through :class:`~chainermn_tpu.serving.ServingEngine`
by the model-side interface every served model has (``serve_*``, see
docs/serving.md); it has no speculative verify and no head axis to shard,
and the engine refuses those for it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.link import Chain, ChainList
from ..nn import links as L
from ..observability import role
from ..ops import attention as flash_attention_op
from ..ops.paged_attention import paged_latent_attention
from ..parallel.moe import HeldExperts
from ..serving.kv_cache import (write_prompt_kv, write_prompt_kv_at,
                                write_token_kv)

__all__ = ["LatentAttention", "SwiGLU", "LatentMoEBlock", "LatentMoELM",
           "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature (Peng et al. 2023, as DeepSeek-V3's
    code has it): ``0.1 · mscale · ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow):
    """Inverse rotary frequencies ``[dim / 2]`` under YaRN: the plain
    ``base^(-2i/dim)`` where a dimension turns more than ``beta_fast``
    times over the original context, the same divided by ``factor``
    where it turns fewer than ``beta_slow`` times, and a linear blend
    between the two correction dimensions."""
    def correction_dim(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / base ** (i / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rotate(x, pos, inv_freq):
    """Rotary embedding over the last axis of ``x`` in adjacent pairs
    ``(2i, 2i+1)``.  ``pos`` has ``x``'s leading axes up to where it
    stops (``x``: ``[..., d]`` or ``[..., H, d]`` against ``pos``
    ``[...]``); angles and the rotation are float32."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    while ang.ndim < x.ndim:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@role("head")
def _last_row(h, true_len):
    """The row of ``h [T, d]`` at position ``true_len - 1``."""
    return jax.lax.dynamic_slice_in_dim(
        h, jnp.maximum(true_len - 1, 0), 1, axis=0)


class LatentAttention(Chain):
    """Multi-head latent attention's projections.  ``latents`` gives what
    one layer caches and the queries that go with it; the two attention
    forms are the model's, since they differ in what they read."""

    def __init__(self, d_model, n_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, inv_freq, eps=1e-5, seed=0):
        super().__init__()
        self.n_heads, self.kv_rank = n_heads, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.inv_freq = np.asarray(inv_freq, np.float32)
        with self.init_scope():
            self.q_a = L.Linear(d_model, q_rank, nobias=True, seed=seed)
            self.q_norm = L.RMSNorm(q_rank, eps)
            self.q_b = L.Linear(q_rank, n_heads * (nope_dim + rope_dim),
                                nobias=True, seed=seed + 1)
            self.kv_a = L.Linear(d_model, kv_rank + rope_dim, nobias=True,
                                 seed=seed + 2)
            self.kv_norm = L.RMSNorm(kv_rank, eps)
            self.kv_b = L.Linear(kv_rank, n_heads * (nope_dim + v_dim),
                                 nobias=True, seed=seed + 3)
            self.o = L.Linear(n_heads * v_dim, d_model, nobias=True,
                              seed=seed + 4)

    @role("attn_proj")
    def latents(self, x, pos):
        """``x``: ``[T, d]`` normed hidden states at positions ``pos``
        ``[T]``.  Returns ``(q_nope [T, H, nope], q_rope [T, H, rope]
        rotated, latent [T, kv_rank + rope])``, the latent being the
        cache entry: ``c_kv`` after its norm, then the rotated shared
        key."""
        T, H = x.shape[0], self.n_heads
        q = self.q_b(self.q_norm(self.q_a(x))).reshape(
            T, H, self.nope_dim + self.rope_dim)
        q_nope, q_rope = q[..., :self.nope_dim], q[..., self.nope_dim:]
        kv = self.kv_a(x)
        c_kv = self.kv_norm(kv[:, :self.kv_rank])
        k_rope = _rotate(kv[:, self.kv_rank:], pos, self.inv_freq)
        return (q_nope, _rotate(q_rope, pos, self.inv_freq),
                jnp.concatenate([c_kv, k_rope], axis=-1))

    def kv_b_halves(self):
        """``W_kvb`` as ``(W_k [H, nope, rank], W_v [H, v, rank])``."""
        w = self.kv_b.W.array.reshape(
            self.n_heads, self.nope_dim + self.v_dim, self.kv_rank)
        return w[:, :self.nope_dim], w[:, self.nope_dim:]

    @role("attn_proj")
    def expanded(self, q_nope, q_rope, latent, scale):
        """Causal attention of one whole sequence over its own latents,
        keys and values expanded per head: ``[T, H · v]``."""
        T, H = latent.shape[0], self.n_heads
        kv = self.kv_b(latent[:, :self.kv_rank]).reshape(
            T, H, self.nope_dim + self.v_dim)
        k_rope = jnp.broadcast_to(latent[:, None, self.kv_rank:],
                                  (T, H, self.rope_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :self.nope_dim], k_rope], axis=-1)
        v = kv[..., self.nope_dim:]
        # the flash kernels take one head size for q, k and v, in whole
        # lane tiles: pad each with zeros (scores and the kept columns
        # of the output are unchanged) and cut the output back
        d = -(-max(q.shape[-1], self.v_dim) // 128) * 128

        def heads_first(a):
            a = jnp.pad(a, ((0, 0), (0, 0), (0, d - a.shape[-1])))
            return jnp.moveaxis(a, 0, 1)[None]
        out = flash_attention_op(heads_first(q), heads_first(k),
                                 heads_first(v), causal=True, scale=scale)
        return jnp.moveaxis(out[0, :, :, :self.v_dim], 0, 1).reshape(T, -1)

    @role("attn_proj")
    def absorb_query(self, q_nope, q_rope):
        """The absorbed query ``[..., H, rank + rope]``: the key half of
        ``W_kvb`` applied to ``q_nope``, then ``q_rope``."""
        w_k, _ = self.kv_b_halves()
        q_lat = jnp.einsum("...hn,hnc->...hc", q_nope, w_k)
        return jnp.concatenate([q_lat, q_rope], axis=-1)

    @role("attn_proj")
    def unabsorb_output(self, o_lat):
        """``o_lat [..., H, rank]`` through the value half of ``W_kvb``
        and the output projection's input layout: ``[..., H · v]``."""
        _, w_v = self.kv_b_halves()
        o = jnp.einsum("...hc,hvc->...hv", o_lat, w_v)
        return o.reshape(o.shape[:-2] + (-1,))


class SwiGLU(Chain):
    """``down(silu(gate x) * up x)``, no biases."""

    def __init__(self, d_model, d_ff, seed=0):
        super().__init__()
        with self.init_scope():
            self.gate = L.Linear(d_model, d_ff, nobias=True, seed=seed)
            self.up = L.Linear(d_model, d_ff, nobias=True, seed=seed + 1)
            self.down = L.Linear(d_ff, d_model, nobias=True, seed=seed + 2)

    def forward(self, x):
        return self.down(jax.nn.silu(self.gate(x)) * self.up(x))


class LatentMoEBlock(Chain):
    """One pre-norm block.  ``experts=None`` makes the feed-forward one
    dense SwiGLU of width ``d_ff``; otherwise ``experts`` is the
    ``HeldExperts`` arguments ``(d_expert, n_experts, held, k, scale)``
    and a shared expert of the same width runs beside them."""

    def __init__(self, d_model, attn, d_ff=None, experts=None, eps=1e-5,
                 seed=0):
        super().__init__()
        with self.init_scope():
            self.ln1 = L.RMSNorm(d_model, eps)
            self.attn = LatentAttention(d_model, seed=seed, eps=eps, **attn)
            self.ln2 = L.RMSNorm(d_model, eps)
            if experts is None:
                self.mlp = SwiGLU(d_model, d_ff, seed=seed + 10)
            else:
                d_expert, n_experts, held, k, scale = experts
                self.experts = HeldExperts(d_model, d_expert, n_experts,
                                           held, k, routed_scale=scale)
                self.shared = SwiGLU(d_model, d_expert, seed=seed + 10)
        self.routed = experts is not None

    def ffn(self, x, valid=None):
        """``x``: ``[T, d]`` after ``ln2``.  ``(y, counts)``: ``counts``
        is the held experts' copy counts ``[held]``, or ``None`` for a
        dense layer."""
        if not self.routed:
            with role("mlp"):
                return self.mlp(x), None
        y, counts = self.experts(x, valid=valid)   # ``router``, ``experts``
        with role("experts"):
            return y + self.shared(x), counts


class LatentMoELM(Chain):
    """Causal LM of ``n_dense`` dense blocks, then ``n_layers - n_dense``
    expert blocks.

    ``held = (first, count)``: the routed experts this chip holds of
    each layer's ``n_experts``.  ``rope``: ``dict(theta, factor,
    original_max, beta_fast, beta_slow, mscale, mscale_all_dim)`` (YaRN;
    ``factor`` 1 is plain rotary).  ``param_dtype``: the dtype a server
    holds the parameters in (``serve_param_dtype``); computation follows
    the parameters' dtype, with norm, rotary, router and softmax
    statistics in float32.
    """

    def __init__(self, n_vocab, d_model, n_heads, n_layers, q_rank, kv_rank,
                 nope_dim, rope_dim, v_dim, d_ff, d_expert, n_experts, held,
                 k, routed_scale, rope, n_dense=1, eps=1e-5, max_len=4096,
                 param_dtype=None, seed=0):
        super().__init__()
        self.max_len = int(max_len)
        self.param_dtype = param_dtype
        self.kv_rank, self.rope_dim = kv_rank, rope_dim
        # the cached latent in whole 128-lane tiles: a pool whose last
        # axis is not (576 = 4.5 tiles) is STORED by the TPU runtime
        # with its page axis minor-most, and every program then copies
        # the whole pool into the layout it computes in and back
        self.entry_width = -(-(kv_rank + rope_dim) // 128) * 128
        inv_freq = yarn_inv_freq(rope_dim, rope["theta"], rope["factor"],
                                 rope["original_max"], rope["beta_fast"],
                                 rope["beta_slow"])
        m = yarn_mscale(rope["factor"], rope["mscale_all_dim"])
        # cos and sin carry mscale / mscale_all_dim, which is 1 whenever
        # the two are equal, as in every published config of this family
        if rope["mscale"] != rope["mscale_all_dim"]:
            raise ValueError("rope mscale != mscale_all_dim is not "
                             "implemented (cos/sin would carry a factor)")
        self.softmax_scale = (nope_dim + rope_dim) ** -0.5 * m * m
        attn = dict(n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank,
                    nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
                    inv_freq=inv_freq)
        experts = (d_expert, n_experts, held, k, routed_scale)
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.blocks = ChainList(*[
                LatentMoEBlock(d_model, attn, d_ff=d_ff, eps=eps,
                               experts=None if i < n_dense else experts,
                               seed=seed + 100 * (i + 1))
                for i in range(n_layers)])
            self.ln_f = L.RMSNorm(d_model, eps)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    # -- the whole forward (training-shaped callers, tests) -------------------

    def logits(self, x):
        """``x``: ``[B, T]`` token ids → ``[B, T, V]``, every sequence
        attending causally over itself in the expanded form."""
        def one(tokens):
            T = tokens.shape[0]
            with role("embed"):
                pos = jnp.arange(T, dtype=jnp.int32)
                h = self.embed(tokens)
            for li, block in enumerate(self.blocks):
                with jax.named_scope(f"blocks/{li}"):
                    q_nope, q_rope, lat = self._latents(block, h, pos)
                    h = self._add_attn(block, h, block.attn.expanded(
                        q_nope, q_rope, lat, self.softmax_scale))
                    h = self._add_ffn(block, h, None, [])
            with role("head"):
                return self.head(self.ln_f(h))
        return jnp.stack([one(row) for row in x])

    # -- the serving interface (docs/serving.md) ------------------------------

    @property
    def serve_param_dtype(self):
        return self.param_dtype

    @property
    def serve_max_context(self):
        return self.max_len

    @property
    def serve_cache_layers(self):
        return len(self.blocks)

    @property
    def serve_page_dtype(self):
        return self.param_dtype or jnp.float32

    def serve_cache_entry(self):
        """One array a layer: the latent, ``kv_rank + rope_dim`` values
        a token, zero-filled up to whole lane tiles (``entry_width``)."""
        return ((self.entry_width,),)

    @staticmethod
    def _latents(block, h, pos):
        """``block.attn.latents`` of the normed ``h``."""
        with role("norm"):
            x = block.ln1(h)
        return block.attn.latents(x, pos)

    @staticmethod
    def _add_attn(block, h, o):
        """``h`` plus the output projection of the heads' values ``o``."""
        with role("attn_proj"):
            return h + block.attn.o(o)

    def _entry(self, a):
        """``a [..., kv_rank + rope_dim]`` (a latent, or an absorbed
        query against it) zero-filled to the entry's width: zeros add
        nothing to a score, and the values are read from the first
        ``kv_rank`` entries."""
        pad = self.entry_width - a.shape[-1]
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])

    @staticmethod
    def serve_span_stats(counts):
        """The held experts' copy counts ``[expert layers, held]`` of one
        program, as a span's stats: ``held_copies``, the token-copies
        that landed on held experts, a layer (the mean over expert
        layers), ``held_max``, those on the fullest held expert of a
        layer (likewise), and ``held_hit``, the held experts that
        received a copy, summed over expert layers: the matrices a
        step's grouped products read (``WindowMoELM``'s key)."""
        return {"held_copies": float(counts.sum(axis=1).mean()),
                "held_max": float(counts.max(axis=1).mean()),
                "held_hit": int((counts > 0).sum())}

    @staticmethod
    def _add_ffn(block, h, valid, counts):
        """The block's second half, ``h + FFN(norm(h))``; an expert
        layer's held-copy counts (of ``valid`` tokens) join ``counts``."""
        with role("norm"):
            x = block.ln2(h)
        y, c = block.ffn(x, valid)
        if c is not None:
            counts.append(c)
        with role("experts" if block.routed else "mlp"):
            return h + y

    def _finish(self, h_last, counts):
        with role("head"):
            logits = self.head(self.ln_f(h_last)).astype(jnp.float32)
        with role("router"):
            return logits, jnp.stack(counts)

    def serve_prefill(self, pools, tokens, true_len, bt_row):
        """Full prefill of one (padded) prompt ``tokens [1, Tb]``: the
        expanded attention over the prompt's own latents, each layer's
        latents written to its pages.  Returns ``(pools, logits [V],
        (held_counts [expert layers, held],))``."""
        (pool,) = pools
        T = tokens.shape[1]
        with role("embed"):
            pos = jnp.arange(T, dtype=jnp.int32)
        with role("router"):
            valid = pos < true_len
        with role("embed"):
            h = self.embed(tokens[0])
        counts = []
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                q_nope, q_rope, lat = self._latents(block, h, pos)
                with role("cache_write"):
                    pool = write_prompt_kv(pool, self._entry(lat), bt_row,
                                           true_len, layer=li)
                h = self._add_attn(block, h, block.attn.expanded(
                    q_nope, q_rope, lat, self.softmax_scale))
                h = self._add_ffn(block, h, valid, counts)
        logits, counts = self._finish(_last_row(h, true_len), counts)
        with role("head"):
            return (pool,), logits[0], (counts,)

    def serve_suffix_prefill(self, pools, tokens, true_len, start, bt_row):
        """Suffix prefill at offset ``start`` against cached context:
        the suffix's latents are written first, then the suffix's
        absorbed queries attend over the latents read back through
        ``bt_row`` (shared prefix pages and fresh suffix pages alike)."""
        (pool,) = pools
        T = tokens.shape[1]
        with role("embed"):
            t = jnp.arange(T, dtype=jnp.int32)
            pos = start + t
        with role("router"):
            valid = t < true_len
        with role("embed"):
            h = self.embed(tokens[0])
        counts = []
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                q_nope, q_rope, lat = self._latents(block, h, pos)
                with role("cache_write"):
                    pool = write_prompt_kv_at(pool, self._entry(lat),
                                              bt_row, start, true_len,
                                              layer=li)
                with role("attn_proj"):
                    q_abs = self._entry(block.attn.absorb_query(
                        q_nope, q_rope))[None]
                with role("attn"):
                    o_lat = paged_latent_attention(
                        q_abs, pool, bt_row[None], pos[None], self.kv_rank,
                        scale=self.softmax_scale, layer=li)[0]
                h = self._add_attn(block, h,
                                   block.attn.unabsorb_output(o_lat))
                h = self._add_ffn(block, h, valid, counts)
        logits, counts = self._finish(_last_row(h, true_len), counts)
        with role("head"):
            return (pool,), logits[0], (counts,)

    def serve_decode(self, pools, toks, pos, bts, mode=None, tp_mesh=None):
        """One token a lane (``pos < 0``: an idle lane, nothing written,
        nothing counted).  The latent pool has one lowering, so ``mode``
        chooses nothing here; ``tp_mesh`` is refused by the engine (no
        head axis in the pool).  Returns ``(pools, logits [Bb, V],
        (held_counts,))``."""
        (pool,) = pools
        with role("embed"):
            safe = jnp.maximum(pos, 0)
        with role("router"):
            live = pos >= 0
        with role("embed"):
            h = self.embed(toks)
        counts = []
        for li, block in enumerate(self.blocks):
            with jax.named_scope(f"blocks/{li}"):
                q_nope, q_rope, lat = self._latents(block, h, safe)
                with role("cache_write"):
                    pool = write_token_kv(pool, self._entry(lat), bts, pos,
                                          layer=li)
                with role("attn_proj"):
                    q_abs = self._entry(block.attn.absorb_query(
                        q_nope, q_rope))[:, None]
                with role("attn"):
                    o_lat = paged_latent_attention(
                        q_abs, pool, bts, pos[:, None], self.kv_rank,
                        scale=self.softmax_scale, layer=li)[:, 0]
                h = self._add_attn(block, h,
                                   block.attn.unabsorb_output(o_lat))
                h = self._add_ffn(block, h, live, counts)
        logits, counts = self._finish(h, counts)
        return (pool,), logits, (counts,)
