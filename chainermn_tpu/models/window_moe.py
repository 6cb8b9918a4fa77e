"""Language model of window and full attention layers side by side, with
grouped-query heads, per-head output gates and a share of routed experts.

The ``laguna`` block: pre-norm RMSNorm; grouped-query attention (``G``
K/V heads of ``D``, ``H_l`` query heads on layer ``l``: the count differs
by layer) with an RMSNorm over ``D`` on every query and key head; rotary
positions by layer type (a FULL layer rotates part of each head with
YaRN-scaled frequencies and scales cos and sin by an attention factor, a
WINDOW layer rotates all of it with plain frequencies and sees the last
``window`` positions only); a per-head output gate ``softplus(x W_g)``
on the heads' outputs ahead of the output projection; then one dense
SwiGLU, or sigmoid-routed SwiGLU experts beside a shared expert
(:class:`~chainermn_tpu.parallel.moe.HeldExperts`: the router scores every
expert, this chip computes the ones it holds); a final RMSNorm and an
untied head.

What is cached for a token is K (after its norm and rotation) then V,
``2 · G · D`` lanes in every layer, but the two kinds of layer keep it for
different spans, so the model declares its cache by GROUPS of layers
(``serve_cache_groups``): ``full`` layers keep every position, ``window``
layers the last ``window``.  The engine gives each group a page pool and
a block table of its own (docs/serving.md); a window layer's decode reads
the ``window / page + 1`` pages that end at its position whatever the
context.  A whole prompt attends through the flash dispatcher
(``ops.grouped_attention``: no K/V head repeated in memory, under a
window only the band's tiles walked); anything that reads the cache back
goes through ``ops.paged_attention``.

The cache's groups, the prompt's attention and the three serving bodies
live in :class:`WindowCacheLM`, which a model with another block shares
(``models/prerouted_moe.py``); ``WindowMoELM`` adds the ``laguna`` block.

The class serves through :class:`~chainermn_tpu.serving.ServingEngine`;
it has no speculative verify and no head-sharded pool, and the engine
refuses those for it.  It does not train: the grouped and windowed
forward defines no backward.
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
from ..parallel.moe import HeldExperts
from ..serving.kv_cache import (write_prompt_kv, write_prompt_kv_at,
                                write_token_kv)
from .latent_moe import SwiGLU, _last_row, _rotate, yarn_inv_freq

__all__ = ["GatedGroupedAttention", "WindowMoEBlock", "WindowCacheLM",
           "WindowMoELM"]


def _entry(k, v):
    """``k``, ``v`` ``[..., G, D]`` as the cache holds a token: ``[...,
    2 · G · D]``, K then V."""
    return jnp.concatenate([k.reshape(k.shape[:-2] + (-1,)),
                            v.reshape(v.shape[:-2] + (-1,))], axis=-1)


# A decode step's read of the cache, a jit of its own with everything
# but its operands static and the layer's index in its group an operand:
# the layers of one kind (full, window) then share ONE trace and ONE
# lowering of the attention and of ``_paged_decode_kernel``'s body, where
# each layer made its own with its index baked in (``models/looped.py``'s
# reason; XLA inlines the calls, the device runs the same kernels).
# Traced a layer, the kernel was 70 % of a Laguna decode program's trace
# and 64 % of its lowering, nine layers of two kinds: PERF.md section 6,
# PR 48.
@functools.partial(jax.jit, static_argnames=("scale", "window", "kv_heads"))
def _decode_attention(q, pool, bt, ctx, layer, *, scale, window, kv_heads):
    return paged_decode_attention(q, pool, None, bt, ctx, scale=scale,
                                  window=window, layer=layer,
                                  kv_heads=kv_heads)


class GatedGroupedAttention(Chain):
    """The projections of one layer's attention: ``n_heads`` query heads
    over ``n_kv`` K/V heads of ``head_dim``, a norm on each query and key
    head, rotary positions over the first ``rot_dim`` of a head, and the
    per-head output gate.  ``window``: ``None`` for a full layer."""

    def __init__(self, d_model, n_heads, n_kv, head_dim, inv_freq,
                 rot_dim, rot_factor=1.0, window=None, eps=1e-6, seed=0):
        super().__init__()
        if n_heads % n_kv:
            raise ValueError(f"{n_heads} query heads do not group over "
                             f"{n_kv} K/V heads")
        self.n_heads, self.n_kv, self.head_dim = n_heads, n_kv, head_dim
        self.inv_freq = np.asarray(inv_freq, np.float32)
        self.rot_dim, self.rot_factor = int(rot_dim), float(rot_factor)
        self.window = window
        with self.init_scope():
            self.q = L.Linear(d_model, n_heads * head_dim, nobias=True,
                              seed=seed)
            self.k = L.Linear(d_model, n_kv * head_dim, nobias=True,
                              seed=seed + 1)
            self.v = L.Linear(d_model, n_kv * head_dim, nobias=True,
                              seed=seed + 2)
            self.gate = L.Linear(d_model, n_heads, nobias=True,
                                 seed=seed + 3)
            self.o = L.Linear(n_heads * head_dim, d_model, nobias=True,
                              seed=seed + 4)
            self.q_norm = L.RMSNorm(head_dim, eps)
            self.k_norm = L.RMSNorm(head_dim, eps)

    @role("attn_proj")
    def _rotary(self, x, pos):
        """``x [..., heads, D]`` at ``pos [...]``: the first ``rot_dim``
        of each head rotated in float32, cos and sin times
        ``rot_factor``; the rest as it is."""
        rot = _rotate(x[..., :self.rot_dim].astype(jnp.float32), pos,
                      self.inv_freq) * self.rot_factor
        return jnp.concatenate(
            [rot.astype(x.dtype), x[..., self.rot_dim:]], axis=-1)

    @role("attn_proj")
    def project(self, x, pos):
        """``x [..., d]`` normed hidden states at ``pos [...]``: ``(q
        [..., H, D]``, ``k``, ``v`` ``[..., G, D]``, ``gate [..., H]``
        float32)``; q and k normed and rotated, k and v as they are
        cached."""
        lead = x.shape[:-1]
        q = self.q_norm(self.q(x).reshape(
            lead + (self.n_heads, self.head_dim)))
        k = self.k_norm(self.k(x).reshape(
            lead + (self.n_kv, self.head_dim)))
        v = self.v(x).reshape(lead + (self.n_kv, self.head_dim))
        gate = jax.nn.softplus(self.gate(x).astype(jnp.float32))
        return self._rotary(q, pos), self._rotary(k, pos), v, gate

    @role("attn_proj")
    def output(self, att, gate):
        """The heads' outputs ``[..., H, D]``, each times its gate,
        through the output projection."""
        gated = (att.astype(jnp.float32) * gate[..., None]).astype(att.dtype)
        return self.o(gated.reshape(gated.shape[:-2] + (-1,)))


class WindowMoEBlock(Chain):
    """One pre-norm block.  ``experts=None`` makes the feed-forward one
    dense SwiGLU of width ``d_ff``; otherwise ``experts`` is the
    ``HeldExperts`` arguments ``(d_expert, n_experts, held, k, scale)``
    and a shared expert of the same width runs beside them."""

    def __init__(self, d_model, attn, d_ff=None, experts=None, eps=1e-6,
                 seed=0):
        super().__init__()
        with self.init_scope():
            self.ln1 = L.RMSNorm(d_model, eps)
            self.attn = GatedGroupedAttention(d_model, seed=seed, eps=eps,
                                              **attn)
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
        """``x [T, d]`` after ``ln2``: ``(y, counts)``, ``counts`` the
        held experts' copy counts ``[held]`` or ``None`` (dense)."""
        if not self.routed:
            with role("mlp"):
                return self.mlp(x), None
        y, counts = self.experts(x, valid=valid)   # ``router``, ``experts``
        with role("experts"):
            return y + self.shared(x), counts


class WindowCacheLM(Chain):
    """What the language models of window and full attention layers side
    by side share: the cache by two groups of layers, the prompt's
    attention and the three serving bodies.  A subclass builds ``embed``,
    ``blocks`` (each with ``attn.window``), ``ln_f`` and ``head``, and
    gives the block's two halves: ``_project(block, h, pos)`` -> ``(q, k,
    v, extra)`` ahead of the attention and ``_block(block, h, att, extra,
    valid, counts)`` after it, which appends the layer's held experts'
    copy counts to ``counts``.

    ``layer_windows[l]``: a window layer's window (every window layer the
    same one) or ``None`` for a full layer; ``n_kv`` K/V heads of
    ``head_dim`` a token in every layer."""

    def __init__(self, layer_windows, n_kv, head_dim, max_len, param_dtype):
        super().__init__()
        self.max_len = int(max_len)
        self.param_dtype = param_dtype
        self.n_kv, self.head_dim = n_kv, head_dim
        self.scale = head_dim ** -0.5
        windows = {w for w in layer_windows if w is not None}
        if len(windows) > 1:
            raise ValueError(f"window layers of different windows "
                             f"{sorted(windows)} would need a pool each")
        self.window = windows.pop() if windows else None
        # a layer's index inside its group's pools
        self.full_layers = [i for i, w in enumerate(layer_windows)
                            if w is None]
        self.window_layers = [i for i, w in enumerate(layer_windows)
                              if w is not None]

    # -- the whole forward (tests) ------------------------------------------

    def logits(self, x):
        """``x [B, T]`` token ids -> ``[B, T, V]``."""
        def one(tokens):
            with role("embed"):
                pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
                h = self.embed(tokens)
            for block in self.blocks:
                with jax.named_scope(f"blocks/{block.name}"):
                    q, k, v, extra = self._project(block, h, pos)
                    h = self._block(block, h, self._prompt_attention(
                        block.attn, q, k, v), extra, None, [])
            with role("head"):
                return self.head(self.ln_f(h))
        return jnp.stack([one(row) for row in x])

    @role("attn")
    def _prompt_attention(self, attn, q, k, v):
        """A whole prompt over itself: ``q [T, H, D]``, ``k``, ``v``
        ``[T, G, D]`` -> ``[T, H, D]``, heads first through the flash
        dispatcher."""
        def heads_first(a):
            return jnp.moveaxis(a, 0, 1)[None]
        out = grouped_attention(heads_first(q), heads_first(k),
                                heads_first(v), scale=self.scale,
                                window=attn.window)
        return jnp.moveaxis(out[0], 0, 1)

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
        """The cache by groups of layers, the full group first: ``(name,
        layers, entry, window)``.  Both keep ONE array a layer, a token's
        K then its V, each ``G · D`` lanes with the ``G`` heads side by
        side (one pool, so that one gather brings both; and no minor
        ``[8, 128]``, which a program that wants the heads elsewhere
        relays whole); the window group keeps the last ``window``
        positions."""
        entry = ((2 * self.n_kv * self.head_dim,),)
        groups = [("full", len(self.full_layers), entry, None)]
        if self.window_layers:
            groups.append(("window", len(self.window_layers), entry,
                           self.window))
        return tuple(groups)

    @staticmethod
    def serve_span_stats(counts):
        """The held experts' copy counts ``[expert layers, held]`` of one
        program, as a span's stats: ``held_copies`` (token-copies on held
        experts, a layer), ``held_max`` (on the fullest held expert of a
        layer), both means over expert layers, and ``held_hit``, the
        experts that received a copy, summed over expert layers."""
        return {"held_copies": float(counts.sum(axis=1).mean()),
                "held_max": float(counts.max(axis=1).mean()),
                "held_hit": int((counts > 0).sum())}

    @role("attn")       # a group's block table, cut out for each layer
    def _layers(self, pools, bts):
        """Each block with where its cache lies: ``(block, its group's
        pool, its index in the group, its group's block table)``."""
        where = {}
        for g, layers in enumerate((self.full_layers, self.window_layers)):
            for j, i in enumerate(layers):
                where[i] = (g, j, bts[g])
        return [(block,) + where[i] for i, block in enumerate(self.blocks)]

    def _project(self, block, h, pos):
        raise NotImplementedError

    def _block(self, block, h, att, extra, valid, counts):
        raise NotImplementedError

    def _finish(self, h_last, counts):
        with role("head"):
            logits = self.head(self.ln_f(h_last)).astype(jnp.float32)
        with role("router"):
            return logits, jnp.stack(counts)

    def serve_prefill(self, pools, tokens, true_len, bt_rows):
        """Full prefill of one (padded) prompt ``tokens [1, Tb]``;
        ``pools``: the full group's, then the window group's;
        ``bt_rows [groups, N]``.  Every layer writes the whole prompt's
        K and V to its group's pages (the window group's early pages are
        what a later prefix hit reads) and attends over the prompt
        itself.  Returns ``(pools, logits [V], (held_counts,))``."""
        pools = list(pools)
        T = tokens.shape[1]
        with role("embed"):
            pos = jnp.arange(T, dtype=jnp.int32)
        with role("router"):
            valid = pos < true_len
        with role("embed"):
            h = self.embed(tokens[0])
        counts = []
        for block, p, li, bt in self._layers(pools, bt_rows):
            with jax.named_scope(f"blocks/{block.name}"):
                q, k, v, extra = self._project(block, h, pos)
                with role("cache_write"):
                    pools[p] = write_prompt_kv(pools[p], _entry(k, v), bt,
                                               true_len, layer=li)
                h = self._block(block, h, self._prompt_attention(
                    block.attn, q, k, v), extra, valid, counts)
        logits, counts = self._finish(_last_row(h, true_len), counts)
        with role("head"):
            return tuple(pools), logits[0], (counts,)

    def serve_suffix_prefill(self, pools, tokens, true_len, start, bt_rows):
        """Suffix prefill at offset ``start`` against cached context: the
        suffix's K and V are written first, then its queries attend over
        what is read back through each group's block table (a window
        layer: the pages from ``start - window`` on)."""
        pools = list(pools)
        T = tokens.shape[1]
        with role("embed"):
            t = jnp.arange(T, dtype=jnp.int32)
            pos = start + t
        with role("router"):
            valid = t < true_len
        with role("embed"):
            h = self.embed(tokens[0])
        counts = []
        for block, p, li, bt in self._layers(pools, bt_rows):
            with jax.named_scope(f"blocks/{block.name}"):
                q, k, v, extra = self._project(block, h, pos)
                with role("cache_write"):
                    pools[p] = write_prompt_kv_at(
                        pools[p], _entry(k, v), bt, start, true_len,
                        layer=li)
                att = paged_prefill_attention(
                    q, pools[p], None, bt, start, true_len,
                    scale=self.scale, window=block.attn.window, layer=li,
                    kv_heads=self.n_kv)
                h = self._block(block, h, att, extra, valid, counts)
        logits, counts = self._finish(_last_row(h, true_len), counts)
        with role("head"):
            return tuple(pools), logits[0], (counts,)

    def serve_decode(self, pools, toks, pos, bts, mode=None, tp_mesh=None):
        """One token a lane (``pos < 0``: an idle lane, nothing written,
        nothing counted); ``bts [groups, Bb, N]``.  Grouped pools have
        one lowering, so ``mode`` chooses nothing; ``tp_mesh`` is refused
        by the engine.  Returns ``(pools, logits [Bb, V],
        (held_counts,))``."""
        pools = list(pools)
        with role("embed"):
            safe = jnp.maximum(pos, 0)
        with role("router"):
            live = pos >= 0
        with role("attn"):
            ctx = jnp.where(live, pos + 1, 0)
        with role("embed"):
            h = self.embed(toks)
        counts = []
        for block, p, li, bt in self._layers(pools, bts):
            with jax.named_scope(f"blocks/{block.name}"):
                q, k, v, extra = self._project(block, h, safe)
                with role("cache_write"):
                    pools[p] = write_token_kv(pools[p], _entry(k, v), bt,
                                              pos, layer=li)
                with role("attn"):  # the call's own name; its parts' too
                    att = _decode_attention(
                        q, pools[p], bt, ctx, jnp.int32(li),
                        scale=self.scale, window=block.attn.window,
                        kv_heads=self.n_kv)
                h = self._block(block, h, att, extra, live, counts)
        logits, counts = self._finish(h, counts)
        return tuple(pools), logits, (counts,)


class WindowMoELM(WindowCacheLM):
    """Causal LM whose layer ``l`` has ``layer_heads[l]`` query heads and
    is a window layer where ``layer_windows[l]`` is a number (every
    window layer the same one), a full layer where it is ``None``;
    ``layer_dense[l]`` makes its feed-forward the dense SwiGLU.

    ``held = (first, count)``: the routed experts this chip holds of
    each layer's ``n_experts``.  ``rope_full``: ``dict(theta, factor,
    original_max, beta_fast, beta_slow, attention_factor, partial)``
    (YaRN over ``partial`` of a head); ``rope_window``: ``dict(theta,
    partial)`` (plain).  ``param_dtype``: the dtype a server holds the
    parameters in; computation follows it, with norm, rotary, router,
    gate and softmax statistics in float32.
    """

    def __init__(self, n_vocab, d_model, layer_heads, layer_windows,
                 layer_dense, n_kv, head_dim, d_ff, d_expert, n_experts,
                 held, k, routed_scale, rope_full, rope_window, eps=1e-6,
                 max_len=4096, param_dtype=None, seed=0):
        super().__init__(layer_windows, n_kv, head_dim, max_len,
                         param_dtype)
        rot_full = int(head_dim * rope_full["partial"])
        rot_window = int(head_dim * rope_window["partial"])
        kinds = {
            False: dict(
                inv_freq=yarn_inv_freq(
                    rot_full, rope_full["theta"], rope_full["factor"],
                    rope_full["original_max"], rope_full["beta_fast"],
                    rope_full["beta_slow"]),
                rot_dim=rot_full,
                rot_factor=rope_full["attention_factor"], window=None),
            True: dict(
                inv_freq=rope_window["theta"] ** (
                    -np.arange(0, rot_window, 2) / rot_window),
                rot_dim=rot_window, rot_factor=1.0, window=self.window)}
        experts = (d_expert, n_experts, held, k, routed_scale)
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.blocks = ChainList(*[
                WindowMoEBlock(
                    d_model,
                    dict(n_heads=layer_heads[i], n_kv=n_kv,
                         head_dim=head_dim,
                         **kinds[layer_windows[i] is not None]),
                    d_ff=d_ff, eps=eps,
                    experts=None if layer_dense[i] else experts,
                    seed=seed + 100 * (i + 1))
                for i in range(len(layer_heads))])
            self.ln_f = L.RMSNorm(d_model, eps)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    @staticmethod
    def _project(block, h, pos):
        """``block.attn.project`` of the normed ``h``."""
        with role("norm"):
            x = block.ln1(h)
        return block.attn.project(x, pos)

    def _block(self, block, h, att, gate, valid, counts):
        with role("attn_proj"):
            h = h + block.attn.output(att, gate)
        with role("norm"):
            x = block.ln2(h)
        y, c = block.ffn(x, valid)
        if c is not None:
            counts.append(c)
        with role("experts" if block.routed else "mlp"):
            return h + y
