"""Language model whose stack of layers is applied several times in
sequence with the SAME parameters (a looped, or universal, transformer).

The ``ouro`` model.  ``h = E[x]``; for pass ``r = 0 .. R-1``, for layer
``l = 0 .. L-1`` with the same ``θ_l`` in every pass: ``h = h +
RMSNorm(attention(RMSNorm(h)))``, ``h = h + RMSNorm(SwiGLU(RMSNorm(h)))``
(a norm on each sublayer's input AND on its output); after layer ``L-1``
of every pass the one final RMSNorm, whose output is the next pass's
input, and an exit gate ``g_r = sigmoid(h w_g + b_g)``.  Attention is
grouped-query heads of ``D`` with rotary positions over the whole head
in the pairs ``(i, i + D/2)``.  The gates give a distribution over the
pass a token would leave at: ``p_r = g_r · Π_{j<r}(1 - g_j)``, the last
pass taking what is left.  Every program computes it (it is part of the
model); none acts on it: at the published threshold of 1 every token
runs all ``R`` passes and ``logits = h^(R-1) W_head``.  A threshold
under 1 (lanes that leave the loop at different passes) is refused.

What a token leaves in the cache is its K then its V, ``2 · G · D``
lanes, as :class:`~chainermn_tpu.models.WindowMoELM` keeps them, but in
EVERY PASS of every layer: pass ``r`` of layer ``l`` attends over the
keys and values of pass ``r`` of layer ``l`` alone, so the model declares
one full cache group of ``R · L`` layers for its ``L`` blocks
(``serve_cache_groups``), cache layer ``c = r · L + l``.

The three serving programs are a DEVICE LOOP over passes (``lax.
fori_loop`` under one ``jax.named_scope("loop")``, the blocks inside it
unrolled): ``R · L`` block bodies unrolled would be 192 for the published
model.  The page pool is the loop's carry, written and read at a cache
layer that is a traced value (``serving/kv_cache.py``, ``ops/
paged_attention.py``), in place: one copy of an 8 GB pool does not fit
beside it.

The class serves through :class:`~chainermn_tpu.serving.ServingEngine`;
it has no speculative verify and no head-sharded pool, and the engine
refuses those for it.  It does not train: no loss over the passes is
defined, and the grouped forward defines no backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.link import Chain, ChainList
from ..nn import links as L
from ..observability import role
from ..ops import grouped_attention
from ..ops.paged_attention import (paged_decode_attention,
                                   paged_prefill_attention)
from ..serving.errors import UnsupportedProgramError
from ..serving.kv_cache import (write_prompt_kv, write_prompt_kv_at,
                                write_token_kv)
from .latent_moe import SwiGLU, _last_row
from .window_moe import _entry

__all__ = ["LoopedAttention", "LoopedBlock", "LoopedLM", "exit_distribution"]


def _rotate_half(x, cos, sin):
    """Rotary embedding of ``x [..., heads, D]`` in the pairs ``(i, i +
    D/2)``: ``cos``, ``sin`` ``[..., D/2]`` float32 over ``x``'s leading
    axes; the rotation in float32."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    cos, sin = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# The two reads of the cache, each a jit of its own with everything but
# its operands static: the blocks of a program then share ONE trace and
# ONE lowering of the attention (and of `_paged_decode_kernel`'s body)
# where each block used to make its own, 48 a program
# (`ops.flash_attention._lse_forward_call`'s reason; XLA inlines the
# calls).  On the chip a decode program's first use fell from 27 s to
# 5 s: PERF.md section 6, PR 40.

@functools.partial(jax.jit, static_argnames=("scale", "kv_heads"))
def _decode_attention(q, pool, bt, ctx, layer, *, scale, kv_heads):
    return paged_decode_attention(q, pool, None, bt, ctx, scale=scale,
                                  layer=layer, kv_heads=kv_heads)


@functools.partial(jax.jit, static_argnames=("scale", "kv_heads"))
def _suffix_attention(q, pool, bt, start, true_len, layer, *, scale,
                      kv_heads):
    return paged_prefill_attention(q, pool, None, bt, start, true_len,
                                   scale=scale, layer=layer,
                                   kv_heads=kv_heads)


def exit_distribution(gates):
    """``gates [R, ...]`` (each pass's exit gate, float32) -> ``p [R,
    ...]``: ``p_r = g_r · Π_{j<r}(1 - g_j)`` for ``r < R-1``, and the
    last pass takes what is left, ``Π_{j<R-1}(1 - g_j)``."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]], axis=0)
    return (gates * before).at[-1].set(before[-1])


class LoopedAttention(Chain):
    """One layer's projections: ``n_heads`` query heads over ``n_kv``
    K/V heads of ``head_dim``, no bias, rotary positions over the whole
    head."""

    def __init__(self, d_model, n_heads, n_kv, head_dim, seed=0):
        super().__init__()
        if n_heads % n_kv:
            raise ValueError(f"{n_heads} query heads do not group over "
                             f"{n_kv} K/V heads")
        self.n_heads, self.n_kv, self.head_dim = n_heads, n_kv, head_dim
        with self.init_scope():
            self.q = L.Linear(d_model, n_heads * head_dim, nobias=True,
                              seed=seed)
            self.k = L.Linear(d_model, n_kv * head_dim, nobias=True,
                              seed=seed + 1)
            self.v = L.Linear(d_model, n_kv * head_dim, nobias=True,
                              seed=seed + 2)
            self.o = L.Linear(n_heads * head_dim, d_model, nobias=True,
                              seed=seed + 3)

    @role("attn_proj")
    def project(self, x, cos, sin):
        """``x [..., d]`` normed hidden states, ``cos``, ``sin`` ``[...,
        D/2]`` of their positions: ``(q [..., H, D], k, v [..., G, D])``,
        q and k rotated, k and v as they are cached."""
        lead, D = x.shape[:-1], self.head_dim
        q = self.q(x).reshape(lead + (self.n_heads, D))
        k = self.k(x).reshape(lead + (self.n_kv, D))
        v = self.v(x).reshape(lead + (self.n_kv, D))
        return _rotate_half(q, cos, sin), _rotate_half(k, cos, sin), v

    @role("attn_proj")
    def output(self, att):
        return self.o(att.reshape(att.shape[:-2] + (-1,)))


class LoopedBlock(Chain):
    """One block, a norm on each sublayer's input and on its output:
    ``ln1`` and ``ln2`` around the attention, ``ln3`` and ``ln4`` around
    the SwiGLU."""

    def __init__(self, d_model, d_ff, attn, eps=1e-6, seed=0):
        super().__init__()
        with self.init_scope():
            self.ln1 = L.RMSNorm(d_model, eps)
            self.attn = LoopedAttention(d_model, seed=seed, **attn)
            self.ln2 = L.RMSNorm(d_model, eps)
            self.ln3 = L.RMSNorm(d_model, eps)
            self.mlp = SwiGLU(d_model, d_ff, seed=seed + 10)
            self.ln4 = L.RMSNorm(d_model, eps)

    def project(self, h, cos, sin):
        with role("norm"):
            x = self.ln1(h)
        return self.attn.project(x, cos, sin)

    def residual(self, h, att):
        """``h`` and its heads' outputs ``att [..., H, D]`` through the
        rest of the block."""
        o = self.attn.output(att)
        with role("norm"):
            h = h + self.ln2(o)
            x = self.ln3(h)
        with role("mlp"):
            m = self.mlp(x)
        with role("norm"):
            return h + self.ln4(m)


class LoopedLM(Chain):
    """Causal LM of ``n_layers`` blocks applied ``passes`` times.

    ``exit_threshold``: the cumulative exit probability at which a token
    would stop looping; only 1 (nobody leaves early, as published) is
    served: anything lower raises :class:`~chainermn_tpu.serving.errors.
    UnsupportedProgramError`.  ``param_dtype``: the dtype a server holds
    the parameters in; computation follows it, with norm, rotary, gate
    and softmax statistics in float32.
    """

    def __init__(self, n_vocab, d_model, n_layers, n_heads, n_kv, head_dim,
                 d_ff, passes, rope_theta=10000.0, eps=1e-6,
                 exit_threshold=1.0, max_len=4096, param_dtype=None,
                 seed=0):
        super().__init__()
        if exit_threshold < 1.0:
            # a step whose lanes stop at different passes is not written
            raise UnsupportedProgramError(type(self).__name__, "early_exit")
        self.max_len = int(max_len)
        self.param_dtype = param_dtype
        self.passes = int(passes)
        self.n_kv, self.head_dim = n_kv, head_dim
        self.scale = head_dim ** -0.5
        self.inv_freq = (float(rope_theta) ** (
            -np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)) \
            .astype(np.float32)
        attn = dict(n_heads=n_heads, n_kv=n_kv, head_dim=head_dim)
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.blocks = ChainList(*[
                LoopedBlock(d_model, d_ff, attn, eps=eps,
                            seed=seed + 100 * (i + 1))
                for i in range(n_layers)])
            self.ln_f = L.RMSNorm(d_model, eps)
            self.gate = L.Linear(d_model, 1, seed=seed + 998)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    # -- the loop over passes ------------------------------------------------

    @role("attn_proj")
    def _angles(self, pos):
        """``(cos, sin)`` ``[..., D/2]`` float32 of positions ``pos
        [...]``: the same in every pass and layer, made once a
        program."""
        ang = pos.astype(jnp.float32)[..., None] * self.inv_freq
        return jnp.cos(ang), jnp.sin(ang)

    def _exit_gate(self, h):
        """``sigmoid(h w_g + b_g)`` of the normed rows ``h [rows, d]``,
        the product, the bias and the sigmoid in float32: ``[rows]``."""
        with jax.named_scope("gate"):
            w = self.gate.W.array.astype(jnp.float32)
            return jax.nn.sigmoid(h.astype(jnp.float32) @ w[0]
                                  + self.gate.b.array.astype(jnp.float32)[0])

    def _loop(self, h, carry, attend, pos):
        """Every pass over ``h [rows, d]`` at positions ``pos [rows]``.
        ``attend(c, q, k, v, carry) -> (att, carry)`` is the program's
        attention at cache layer ``c = r · L + l`` (a traced value),
        ``carry`` what it threads through the loop (the page pool, or
        nothing).  Returns ``(h after the last pass's final norm, carry,
        gates [R, rows] float32)``."""
        n = len(self.blocks)
        cos, sin = self._angles(pos)

        def one_pass(r, state):
            h, carry, gates = state
            for l, block in enumerate(self.blocks):
                with jax.named_scope(f"blocks/{block.name}"):
                    with role("cache_write"):
                        c = r * n + l
                    q, k, v = block.project(h, cos, sin)
                    att, carry = attend(c, q, k, v, carry)
                    h = block.residual(h, att)
            with role("head"):
                h = self.ln_f(h)
                return h, carry, jax.lax.dynamic_update_index_in_dim(
                    gates, self._exit_gate(h), r, axis=0)

        with role("head"):
            gates = jnp.zeros((self.passes,) + h.shape[:-1], jnp.float32)
        # the loop's own counter is the cache layer's: ``attn``, as a
        # group's block table cut out for a layer is; every operation of
        # the body has an innermost role of its own
        with jax.named_scope("loop"), role("attn"):
            return jax.lax.fori_loop(0, self.passes, one_pass,
                                     (h, carry, gates))

    @role("attn")
    def _prompt_attention(self, q, k, v):
        """A whole prompt over itself: ``q [T, H, D]``, ``k``, ``v`` ``[T,
        G, D]`` -> ``[T, H, D]``, heads first through the flash
        dispatcher."""
        def heads_first(a):
            return jnp.moveaxis(a, 0, 1)[None]
        out = grouped_attention(heads_first(q), heads_first(k),
                                heads_first(v), scale=self.scale)
        return jnp.moveaxis(out[0], 0, 1)

    # -- the whole forward (tests) ------------------------------------------

    def forward(self, x):
        """``x [B, T]`` token ids -> ``(logits [B, T, V], the exit
        distribution [B, R, T] float32)``: a plain full pass, no
        cache."""
        def one(tokens):
            with role("embed"):
                pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
                h = self.embed(tokens)
            h, _, gates = self._loop(
                h, (), lambda c, q, k, v, carry:
                (self._prompt_attention(q, k, v), carry), pos)
            with role("head"):
                return self.head(h), exit_distribution(gates)
        logits, exits = zip(*[one(row) for row in x])
        return jnp.stack(logits), jnp.stack(exits)

    # -- the serving interface (docs/serving.md) ------------------------------

    @property
    def serve_param_dtype(self):
        return self.param_dtype

    @property
    def serve_max_context(self):
        return self.max_len

    @property
    def serve_page_dtype(self):
        return self.param_dtype or jnp.float32

    def serve_cache_groups(self):
        """One full group of ``passes · blocks`` cache layers (every pass
        of every block keeps its own keys and values), a token's K then
        its V in one row of ``2 · G · D`` lanes."""
        return (("full", self.passes * len(self.blocks),
                 ((2 * self.n_kv * self.head_dim,),), None),)

    def serve_span_stats(self, ctx_tokens, expected_pass):
        """What a program counted, as its span's stats: ``ctx_tokens``
        (the positions its queries attend over, summed over live lanes:
        what a sound step reads of EACH cache layer), ``passes`` and
        ``exit_expected_pass`` (the exit distribution's mean pass,
        1-based, averaged over live lanes)."""
        return {"ctx_tokens": int(ctx_tokens), "passes": self.passes,
                "exit_expected_pass": float(expected_pass)}

    @role("head")
    def _finish(self, h, gates, live, ctx):
        """``(logits [rows, V] float32, extras)`` of the rows ``h`` that
        a program answers for; ``gates [R, rows]``, ``live [rows]`` the
        rows that count, ``ctx [rows]`` their contexts."""
        logits = self.head(h).astype(jnp.float32)
        passes = jax.lax.iota(jnp.float32, self.passes) + 1.0
        expected = jnp.einsum("r,rb->b", passes, exit_distribution(gates))
        n = jnp.maximum(jnp.sum(live), 1)
        return logits, (jnp.sum(jnp.where(live, ctx, 0)),
                        jnp.sum(jnp.where(live, expected, 0.0)) / n)

    def _prefill(self, pools, tokens, true_len, start, bt_rows):
        """The two prefills' body: ``start is None`` is a whole prompt
        from its first token (each pass attends over the prompt itself);
        otherwise the rows follow ``start`` cached positions and each
        pass attends over what is read back through the block table at
        its own cache layers."""
        (pool,) = pools
        T = tokens.shape[1]
        with role("attn"):
            bt = bt_rows[0]
        with role("embed"):
            t = jnp.arange(T, dtype=jnp.int32)
            pos = t if start is None else start + t
            h = self.embed(tokens[0])

        def attend(c, q, k, v, pool):
            if start is None:
                with role("cache_write"):
                    pool = write_prompt_kv(pool, _entry(k, v), bt, true_len,
                                           layer=c)
                return self._prompt_attention(q, k, v), pool
            with role("cache_write"):
                pool = write_prompt_kv_at(pool, _entry(k, v), bt, start,
                                          true_len, layer=c)
            return _suffix_attention(
                q, pool, bt, start, true_len, c, scale=self.scale,
                kv_heads=self.n_kv), pool

        h, pool, gates = self._loop(h, pool, attend, pos)
        with role("head"):
            last = jnp.maximum(true_len - 1, 0)
            seen = true_len if start is None else start + true_len
            logits, extras = self._finish(
                _last_row(h, true_len),
                jax.lax.dynamic_slice_in_dim(gates, last, 1, axis=1),
                (true_len > 0)[None], seen[None])
            return (pool,), logits[0], extras

    def serve_prefill(self, pools, tokens, true_len, bt_rows):
        """Full prefill of one (padded) prompt ``tokens [1, Tb]``;
        ``pools``: the one pool ``[R · L, P, S, 2 · G · D]``; ``bt_rows
        [1, N]``.  Every pass of every layer writes the whole prompt's K
        and V at its own cache layer and attends over the prompt itself.
        Returns ``(pools, logits [V], (ctx_tokens, exit_expected_pass))``."""
        return self._prefill(pools, tokens, true_len, None, bt_rows)

    def serve_suffix_prefill(self, pools, tokens, true_len, start, bt_rows):
        """Suffix prefill at offset ``start`` against cached context: in
        every pass of every layer the suffix's K and V are written first,
        then its queries attend over that pass's own cache layer read
        back through the block table."""
        return self._prefill(pools, tokens, true_len, start, bt_rows)

    def serve_decode(self, pools, toks, pos, bts, mode=None, tp_mesh=None):
        """One token a lane (``pos < 0``: an idle lane, nothing written,
        nothing counted); ``bts [1, Bb, N]``.  The pool has one lowering
        a backend, so ``mode`` chooses nothing; ``tp_mesh`` is refused by
        the engine.  Returns ``(pools, logits [Bb, V], (ctx_tokens,
        exit_expected_pass))``."""
        (pool,) = pools
        with role("attn"):
            bt = bts[0]
            live = pos >= 0
            ctx = jnp.where(live, pos + 1, 0)
        with role("embed"):
            safe = jnp.maximum(pos, 0)
            h = self.embed(toks)

        def attend(c, q, k, v, pool):
            with role("cache_write"):
                pool = write_token_kv(pool, _entry(k, v), bt, pos, layer=c)
            return _decode_attention(
                q, pool, bt, ctx, c, scale=self.scale,
                kv_heads=self.n_kv), pool

        h, pool, gates = self._loop(h, pool, attend, safe)
        logits, extras = self._finish(h, gates, live, ctx)
        return (pool,), logits, extras
