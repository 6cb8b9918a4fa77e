"""Mixture-of-experts transformer LM (expert parallelism end to end).

Beyond-reference model family: Switch-style MoE feed-forward blocks whose
expert bank shards one-expert-per-rank over an ``ep`` mesh axis
(``parallel.moe``), composed with the attention stack of
``models.transformer``.  Inside a compiled step each rank slices its
expert from the replicated bank (``functions.psum_gradient`` keeps the
bank's gradients exact under the replicated-loss convention) and tokens
are exchanged with one ``all_to_all`` round trip per layer — TWO-STAGE
over the ici × dcn hierarchy when ``ep_comm`` is hierarchical (ISSUE 12:
on-host tokens never touch the slow fabric, the DCN crossing compresses
under the communicator's per-hop dtype; ``two_stage=False`` is the
explicit single-axis escape).  ``topk > 1`` switches the router to the
GShard-style top-k mixture.  Outside any mesh axis the layer degrades to
dense routing — same math, no collectives — so the same weights run
single-device and expert-parallel.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.link import Chain, ChainList, Parameter
from ..core import reporter
from ..nn import functions as F
from ..nn import links as L
from .. import functions as mnfn
from ..observability import role
from .transformer import MultiHeadAttention, _axis_bound, _remat_policy

__all__ = ["MoEFeedForward", "MoETransformerBlock", "MoETransformerLM"]


class MoEFeedForward(Chain):
    def __init__(self, d_model, d_ff, ep_comm, capacity_factor=1.25,
                 seed=0, topk=1, two_stage=None):
        super().__init__()
        self.ep_comm = ep_comm
        self.capacity_factor = capacity_factor
        self.topk = int(topk)
        self.two_stage = two_stage
        E = ep_comm.size
        rng = np.random.RandomState(seed)
        with self.init_scope():
            self.router = Parameter(rng.normal(0, 0.02, (d_model, E))
                                    .astype(np.float32))
            self.w_in = Parameter(rng.normal(0, 0.02, (E, d_model, d_ff))
                                  .astype(np.float32))
            self.b_in = Parameter(np.zeros((E, d_ff), np.float32))
            self.w_out = Parameter(rng.normal(0, 0.02, (E, d_ff, d_model))
                                   .astype(np.float32))
            self.b_out = Parameter(np.zeros((E, d_model), np.float32))

    @role("router")     # what is not an expert's own product is routing
    def forward(self, x, aux_sink=None):
        B, T, D = x.shape
        tokens = x.reshape(B * T, D)
        comm = self.ep_comm
        if _axis_bound(comm):
            from ..parallel.moe import (moe_dispatch_combine,
                                        moe_dispatch_combine_topk)
            # slice this rank's expert from the (replicated) bank;
            # psum_gradient reassembles the bank's gradient exactly
            idx = jax.lax.axis_index(comm.axis_name)
            with role("experts"):
                w_in, b_in, w_out, b_out = [
                    jax.lax.dynamic_index_in_dim(
                        mnfn.psum_gradient(comm, p.array), idx, 0, False)
                    for p in (self.w_in, self.b_in, self.w_out, self.b_out)]
            gate_logits = tokens @ self.router.array

            @role("experts")
            def expert_fn(h):
                return F.gelu(h @ w_in + b_in) @ w_out + b_out

            if self.topk > 1:
                out, aux = moe_dispatch_combine_topk(
                    comm, tokens, gate_logits, expert_fn, k=self.topk,
                    capacity_factor=self.capacity_factor,
                    two_stage=self.two_stage)
            else:
                out, aux = moe_dispatch_combine(
                    comm, tokens, gate_logits, expert_fn,
                    capacity_factor=self.capacity_factor,
                    two_stage=self.two_stage)
            if aux_sink is not None:
                aux_sink.append({"aux_loss": aux["aux_loss"],
                                 "dropped_frac": aux["dropped_frac"]})
            return out.reshape(B, T, D)
        # dense fallback (no mesh axis): every expert computed, top-1
        # argmax-selected (or the top-k mixture) per token — identical
        # routing math, no capacity cut (dense drops nothing)
        probs = jax.nn.softmax(tokens @ self.router.array, axis=-1)
        E = comm.size
        with role("experts"):
            h = jnp.einsum("td,edh->teh", tokens, self.w_in.array) \
                + self.b_in.array[None]
            y = jnp.einsum("teh,ehd->ted", F.gelu(h), self.w_out.array) \
                + self.b_out.array[None]
        if self.topk > 1:
            gates, experts = jax.lax.top_k(probs, self.topk)   # [T, k]
            gates = gates / jnp.maximum(
                gates.sum(axis=1, keepdims=True), 1e-9)
            picked = jnp.take_along_axis(
                y, experts[:, :, None].repeat(D, axis=2), 1)   # [T, k, D]
            out = jnp.sum(picked * gates[:, :, None], axis=1)
            frac = jnp.mean(
                jax.nn.one_hot(experts, E).max(axis=1), axis=0)
        else:
            eidx = jnp.argmax(probs, axis=-1)
            gate = jnp.take_along_axis(probs, eidx[:, None], 1)[:, 0]
            out = jnp.take_along_axis(
                y, eidx[:, None, None].repeat(D, axis=2), 1)[:, 0]
            out = out * gate[:, None]
            frac = jnp.mean(jax.nn.one_hot(eidx, E), axis=0)
        if aux_sink is not None:
            aux_sink.append({
                "aux_loss": E * jnp.sum(frac * jnp.mean(probs, axis=0)),
                "dropped_frac": jnp.float32(0.0)})
        return out.reshape(B, T, D)


class MoETransformerBlock(Chain):
    def __init__(self, d_model, n_heads, d_ff, ep_comm, seed=0,
                 sp_comm=None, sp_mode="ring", capacity_factor=1.25,
                 topk=1, two_stage=None):
        super().__init__()
        with self.init_scope():
            self.ln1 = L.LayerNormalization(d_model)
            self.attn = MultiHeadAttention(d_model, n_heads, seed=seed,
                                           sp_comm=sp_comm, sp_mode=sp_mode)
            self.ln2 = L.LayerNormalization(d_model)
            self.moe = MoEFeedForward(d_model, d_ff, ep_comm,
                                      capacity_factor, seed=seed + 50,
                                      topk=topk, two_stage=two_stage)

    def forward(self, x, aux_sink=None, causal=True):
        with role("norm"):
            a = self.ln1(x)
        with role("attn_proj"):     # the kernels inside open ``attn``
            h = x + self.attn(a, causal=causal)
        with role("norm"):
            m = self.ln2(h)
        with role("experts"):       # the layer inside opens ``router``
            return h + self.moe(m, aux_sink=aux_sink)


class MoETransformerLM(Chain):
    """Causal LM with MoE feed-forwards; ``aux_weight`` scales the Switch
    load-balancing loss added to the LM loss.  ``topk``/``two_stage``
    thread through to every block's dispatch (ISSUE 12); the reported
    observations carry ``moe_aux`` (mean load-balancing loss) and
    ``moe_dropped`` (mean capacity-cut fraction — the honesty column
    the bench rows read)."""

    def __init__(self, n_vocab, ep_comm, d_model=128, n_heads=4,
                 n_layers=2, d_ff=None, max_len=2048, seed=0,
                 aux_weight=0.01, capacity_factor=1.25,
                 compute_dtype=None, remat=False, topk=1,
                 two_stage=None):
        super().__init__()
        d_ff = d_ff or 4 * d_model
        self.aux_weight = aux_weight
        # same knobs as TransformerLM: bf16 MXU compute with fp32
        # params/statistics, and per-block remat with jax.checkpoint
        # POLICIES (True/"full"/"dots"/...).  Remat caveat specific to
        # MoE: the block's all_to_all expert exchange is recomputed in
        # the backward under full remat — "dots" keeps the expert GEMM
        # outputs but still re-runs the exchange; policy choice trades
        # a2a traffic against activation memory.
        self.compute_dtype = compute_dtype
        self.remat = remat
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.pos_embed = L.EmbedID(max_len, d_model, seed=seed + 1)
            self.blocks = ChainList(*[
                MoETransformerBlock(d_model, n_heads, d_ff, ep_comm,
                                    seed=seed + 100 * (i + 1),
                                    capacity_factor=capacity_factor,
                                    topk=topk, two_stage=two_stage)
                for i in range(n_layers)])
            self.ln_f = L.LayerNormalization(d_model)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    def forward(self, x, t):
        B, T = x.shape
        with role("embed"):
            pos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            h = self.embed(x) + self.pos_embed(
                jnp.broadcast_to(pos, (B, T)))
            if self.compute_dtype is not None:
                h = h.astype(self.compute_dtype)
        with jax.named_scope("blocks"):
            h, aux_sink = self._run_blocks(h)
        with role("head"):
            h = self.ln_f(h)
            # head GEMM stays in the compute dtype (large-vocab GEMMs are
            # exactly where bf16 MXU rate matters); softmax_cross_entropy
            # upcasts the logits to fp32 internally — same discipline as
            # TransformerLM
            logits = self.head(h.reshape(B * T, -1))
        with role("loss"):
            loss = F.softmax_cross_entropy(logits, t.reshape(-1),
                                           ignore_label=-1)
            n = max(len(aux_sink), 1)
            aux = sum(a["aux_loss"] for a in aux_sink) / n
            dropped = sum(a["dropped_frac"] for a in aux_sink) / n
            reporter.report({"loss": loss, "moe_aux": aux,
                             "moe_dropped": dropped}, self)
            return loss + self.aux_weight * aux

    def _run_blocks(self, h):
        aux_sink = []
        for block in self.blocks:
            if self.remat:
                # aux outputs must cross the checkpoint boundary as
                # explicit results (appending to a closed-over list
                # inside the remat region would leak tracers)
                def run(hh, blk=block):
                    sink = []
                    out = blk(hh, aux_sink=sink)
                    return out, sink[0]
                h, aux = jax.checkpoint(
                    run, policy=_remat_policy(self.remat))(h)
                aux_sink.append(aux)
            else:
                h = block(h, aux_sink=aux_sink)
        return h, aux_sink
