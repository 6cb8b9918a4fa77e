"""Language model of window and full attention layers side by side whose
every feed-forward is a whole layer of small routed experts, routed from
the block's input AHEAD of its attention.

The ``smallthinker`` block, one token's residual state ``h``:

1. ``r = W_r h``: the router's logits over all the experts, from the
   block's input as it arrives (before ``norm1``), so that a server can
   start fetching the chosen experts while the attention runs;
2. ``h' = h + W_o Attn(norm1(h))``: grouped-query attention (``G`` K/V
   heads of ``D``, the same ``H`` query heads on every layer), no bias,
   no norm on query or key heads, no gate; a WINDOW layer sees the last
   ``window`` positions and rotates all of every head (plain rotary
   frequencies), a FULL layer sees every position and carries NO
   positions at all;
3. ``h'' = h' + Σ_{e in top-k(r)} p_e · down_e(act(gate_e x') ⊙ up_e x')``
   with ``x' = norm2(h')`` and ``p`` the softmax over the ``k`` chosen
   logits: :class:`~chainermn_tpu.parallel.moe.SortedExperts`, the
   products grouped by sorting.  No shared expert, no dense layer.

A final RMSNorm and an untied head.  What is cached, by which group of
layers, the prompt's attention and the three serving bodies are
:class:`~chainermn_tpu.models.window_moe.WindowCacheLM`'s, the base it
shares with ``WindowMoELM``: what differs is the block, that is what
``_project`` computes ahead of the attention and ``_block`` after it.
Where that model's projection hands its block the heads' gate, this one
hands it the router's logits.

The class serves through :class:`~chainermn_tpu.serving.ServingEngine`
like its base; it does not train (neither the windowed forward nor the
grouped product here defines a backward).
"""

from __future__ import annotations

import jax
import numpy as np

from ..core.link import Chain, ChainList
from ..nn import links as L
from ..observability import role
from ..parallel.moe import SortedExperts
from .latent_moe import _rotate
from .window_moe import WindowCacheLM

__all__ = ["GroupedAttention", "PreroutedMoEBlock", "PreroutedMoELM"]


class GroupedAttention(Chain):
    """The projections of one layer's attention: ``n_heads`` query heads
    over ``n_kv`` K/V heads of ``head_dim``, no bias.  ``inv_freq``: the
    rotary frequencies over a whole head, or ``None`` for a layer
    without positions.  ``window``: ``None`` for a full layer."""

    def __init__(self, d_model, n_heads, n_kv, head_dim, inv_freq=None,
                 window=None, seed=0):
        super().__init__()
        if n_heads % n_kv:
            raise ValueError(f"{n_heads} query heads do not group over "
                             f"{n_kv} K/V heads")
        self.n_heads, self.n_kv, self.head_dim = n_heads, n_kv, head_dim
        self.inv_freq = None if inv_freq is None \
            else np.asarray(inv_freq, np.float32)
        self.window = window
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
    def project(self, x, pos):
        """``x [..., d]`` normed hidden states at ``pos [...]``: ``(q
        [..., H, D]``, ``k``, ``v`` ``[..., G, D])``; q and k rotated
        (in float32) where the layer has positions, k and v as they are
        cached."""
        lead = x.shape[:-1]
        q = self.q(x).reshape(lead + (self.n_heads, self.head_dim))
        k = self.k(x).reshape(lead + (self.n_kv, self.head_dim))
        v = self.v(x).reshape(lead + (self.n_kv, self.head_dim))
        if self.inv_freq is not None:
            q = _rotate(q, pos, self.inv_freq)
            k = _rotate(k, pos, self.inv_freq)
        return q, k, v

    @role("attn_proj")
    def output(self, att):
        """The heads' outputs ``[..., H, D]`` through the output
        projection."""
        return self.o(att.reshape(att.shape[:-2] + (-1,)))


class PreroutedMoEBlock(Chain):
    """One pre-norm block: ``attn`` the :class:`GroupedAttention`
    arguments, ``experts`` the :class:`SortedExperts` ones."""

    def __init__(self, d_model, attn, experts, eps=1e-6, seed=0):
        super().__init__()
        with self.init_scope():
            self.ln1 = L.RMSNorm(d_model, eps)
            self.attn = GroupedAttention(d_model, seed=seed, **attn)
            self.ln2 = L.RMSNorm(d_model, eps)
            self.experts = SortedExperts(d_model, **experts)


class PreroutedMoELM(WindowCacheLM):
    """Causal LM whose layer ``l`` is a window layer where
    ``layer_windows[l]`` is a number (every window layer the same one)
    and a full layer where it is ``None``, and rotates its queries and
    keys where ``layer_rotary[l]`` is true (``rope_theta``, all of a
    head).  ``held = (first, count)``: the experts this chip holds of
    each layer's ``n_experts`` (all of them: ``(0, n_experts)``);
    ``activation``: the experts' gate function.  ``param_dtype``: the
    dtype a server holds the parameters in; computation follows it,
    with norm, rotary, router and softmax statistics in float32."""

    def __init__(self, n_vocab, d_model, n_heads, n_kv, head_dim,
                 layer_windows, layer_rotary, rope_theta, d_expert,
                 n_experts, held, k, activation=jax.nn.relu, eps=1e-6,
                 max_len=4096, param_dtype=None, seed=0):
        super().__init__(layer_windows, n_kv, head_dim, max_len,
                         param_dtype)
        inv_freq = rope_theta ** (-np.arange(0, head_dim, 2) / head_dim)
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.blocks = ChainList(*[
                PreroutedMoEBlock(
                    d_model,
                    dict(n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
                         inv_freq=inv_freq if layer_rotary[i] else None,
                         window=layer_windows[i]),
                    dict(d_expert=d_expert, n_experts=n_experts, held=held,
                         k=k, activation=activation),
                    eps=eps, seed=seed + 100 * (i + 1))
                for i in range(len(layer_windows))])
            self.ln_f = L.RMSNorm(d_model, eps)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    @staticmethod
    def _project(block, h, pos):
        """The router's logits from ``h`` as it arrives, then
        ``block.attn.project`` of the normed ``h``: ``(q, k, v,
        logits)``."""
        logits = block.experts.logits(h)
        with role("norm"):
            x = block.ln1(h)
        return block.attn.project(x, pos) + (logits,)

    def _block(self, block, h, att, logits, valid, counts):
        with role("attn_proj"):
            h = h + block.attn.output(att)
        with role("norm"):
            x = block.ln2(h)
        y, c = block.experts(x, logits, valid=valid)   # ``router``, ``experts``
        counts.append(c)
        with role("experts"):
            return h + y
