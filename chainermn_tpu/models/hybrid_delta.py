"""Language model of gated delta-rule (linear-attention) layers beside
full-attention layers.

The ``olmo_hybrid`` block.  Both kinds of layer alike: ``h = x +
RMSNorm(mixer(x))``, ``out = h + RMSNorm(SwiGLU(h))`` (the norm on each
sublayer's OUTPUT), a final RMSNorm and an untied head.

* A LINEAR layer's mixer (``DeltaMixer``): ``q~ = x Wq``, ``k~ = x Wk``
  (``H · dk`` each), ``v~ = x Wv`` (``H · dv``); a causal depthwise
  convolution of ``conv`` taps over time on every channel of the three,
  then SiLU; per head ``q = l2norm(q') / sqrt(dk)``, ``k = l2norm(k')``;
  ``beta = 2 · sigmoid(x Wb)``, ``g = -exp(A_log) · softplus(x Wa +
  dt_bias)``, one each a head; the gated delta rule over the head's state
  (:mod:`chainermn_tpu.ops.gated_delta`); the output ``(RMSNorm_dv(o) ·
  silu(x Wg)) Wo``.
* A FULL layer's mixer (``FullMixer``): ``H`` heads of ``D`` over as many
  K/V heads, no bias, an RMSNorm over the whole q and the whole k
  projection, NO rotary position (position reaches a full layer through
  the recurrent layers below it).

What is cached differs in KIND, so the model declares its cache by
groups (``serve_cache_groups``): the full layers keep a token's K then
its V in pages, as :class:`~chainermn_tpu.models.WindowMoELM`'s do; the
linear layers keep ONE entry a sequence, whatever its length: the state
of every head, ``[dk, H · dv]`` float32 (lane-dense: neither ``dk`` nor
``dv`` need be a multiple of 128), and the last ``conv - 1`` inputs of
the convolution.  The engine keeps those in SLOTS beside the page pool
(docs/serving.md) and hands each program, in the state group's row of
the stacked block table, the slots it works on: ``[live, source,
snapshot 0, snapshot 1, ...]``.  A prefill leaves the state as it stood
at every ``stride`` tokens in the snapshot slots, which the prefix trie
keeps; a suffix prefill starts from the state in ``source`` (a hit's
snapshot, or its own live slot between the chunks of a chunked prefill)
and writes the live slot alone.

The class serves through :class:`~chainermn_tpu.serving.ServingEngine`;
it has no speculative verify (a recurrent state cannot be rolled back by
rewinding a counter) and no head-sharded pool, and the engine refuses
those for it.  It does not train: the chunked scan defines no backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.link import Chain, ChainList, Parameter
from ..nn import links as L
from ..observability import role
from ..ops import grouped_attention
from ..ops.gated_delta import CHUNK, gated_delta_chunked, gated_delta_step
from ..ops.paged_attention import (paged_decode_attention,
                                   paged_prefill_attention)
from ..serving.kv_cache import (PerSequence, write_prompt_kv,
                                write_prompt_kv_at, write_token_kv)
from .latent_moe import SwiGLU, _last_row
from .window_moe import _entry

__all__ = ["DeltaMixer", "FullMixer", "HybridDeltaBlock", "HybridDeltaLM"]

#: tokens between the snapshots of state a prefill leaves (128 pages of
#: 16): a prefix hit starts from the deepest one under its match
SNAPSHOT_STRIDE = 2048


@role("state")
def _slots_of(pool, layer, slots):
    """``pool[layer, slots]`` for ``slots [B]``, a ``dynamic_slice`` a
    lane: the TPU compiler lowers the gather of 2 MB rows by first
    slicing the WHOLE pool into lane thirds (13 of a 23 ms decode step at
    any number of lanes: my chip run, PR 33)."""
    rest = pool.shape[2:]
    return jnp.concatenate([
        jax.lax.dynamic_slice(pool, (layer, slots[b]) + (0,) * len(rest),
                              (1, 1) + rest)[0]
        for b in range(slots.shape[0])])


def _l2norm(x, eps=1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


class DeltaMixer(Chain):
    """One linear layer's projections, convolution and gates: ``n_heads``
    heads of key width ``dk`` and value width ``dv``."""

    def __init__(self, d_model, n_heads, dk, dv, conv=4, eps=1e-6, seed=0):
        super().__init__()
        self.n_heads, self.dk, self.dv, self.taps = n_heads, dk, dv, conv
        self.channels = n_heads * (2 * dk + dv)
        with self.init_scope():
            self.q = L.Linear(d_model, n_heads * dk, nobias=True, seed=seed)
            self.k = L.Linear(d_model, n_heads * dk, nobias=True,
                              seed=seed + 1)
            self.v = L.Linear(d_model, n_heads * dv, nobias=True,
                              seed=seed + 2)
            self.a = L.Linear(d_model, n_heads, nobias=True, seed=seed + 3)
            self.b = L.Linear(d_model, n_heads, nobias=True, seed=seed + 4)
            self.gate = L.Linear(d_model, n_heads * dv, nobias=True,
                                 seed=seed + 5)
            self.o = L.Linear(n_heads * dv, d_model, nobias=True,
                              seed=seed + 6)
            self.norm = L.RMSNorm(dv, eps)
            self.conv = Parameter()
            self.A_log = Parameter()
            self.dt_bias = Parameter()
        rng = np.random.RandomState(seed + 7)
        self.conv.draw((self.channels, conv), np.float32,
                       lambda: rng.normal(0, conv ** -0.5,
                                          (self.channels, conv))
                       .astype(np.float32))
        # as Gated DeltaNet's reference code draws them: A in (0, 16),
        # a time step log-uniform in (1e-3, 1e-1) through the inverse
        # of the softplus
        self.A_log.draw((n_heads,), np.float32,
                        lambda: np.log(rng.uniform(1e-3, 16, n_heads))
                        .astype(np.float32))
        self.dt_bias.draw(
            (n_heads,), np.float32,
            lambda: (lambda dt: dt + np.log(-np.expm1(-dt)))(
                np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), n_heads)))
            .astype(np.float32))

    @role("attn_proj")
    def inputs(self, x):
        """``x [..., d]`` -> the convolution's input rows ``[...,
        channels]`` (q~, k~, v~ side by side)."""
        return jnp.concatenate([self.q(x), self.k(x), self.v(x)], axis=-1)

    @role("state")
    def convolve(self, before, rows):
        """The causal convolution and SiLU over ``rows [T, channels]``
        that follow ``before [taps - 1, channels]`` in time: ``[T,
        channels]`` float32."""
        T = rows.shape[0]
        xs = jnp.concatenate([before.astype(jnp.float32),
                              rows.astype(jnp.float32)], axis=0)
        w = self.conv.array.astype(jnp.float32)
        y = sum(xs[j:j + T] * w[:, j] for j in range(self.taps))
        return jax.nn.silu(y)

    @role("state")
    def heads(self, y, dtype):
        """The convolved rows ``[..., channels]`` as ``(q, k [..., H, dk],
        v [..., H, dv])`` in ``dtype``: q and k normed a head, q scaled."""
        H, dk, dv = self.n_heads, self.dk, self.dv
        lead = y.shape[:-1]
        q = _l2norm(y[..., :H * dk].reshape(lead + (H, dk))) * dk ** -0.5
        k = _l2norm(y[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
        v = y[..., 2 * H * dk:].reshape(lead + (H, dv))
        return q.astype(dtype), k.astype(dtype), v.astype(dtype)

    @role("attn_proj")
    def gates(self, x):
        """``(g, beta) [..., H]`` float32: the log decay and the write
        strength (``linear_allow_neg_eigval``: the factor 2)."""
        a = self.a(x).astype(jnp.float32)
        g = -jnp.exp(self.A_log.array.astype(jnp.float32)) \
            * jax.nn.softplus(a + self.dt_bias.array.astype(jnp.float32))
        return g, 2.0 * jax.nn.sigmoid(self.b(x).astype(jnp.float32))

    @role("attn_proj")
    def output(self, o, x):
        """The heads' outputs ``o [..., H, dv]``, normed a head, gated by
        ``silu(x Wg)``, through the output projection."""
        z = self.gate(x).reshape(o.shape)
        y = self.norm(o.astype(x.dtype)) * jax.nn.silu(z)
        return self.o(y.reshape(y.shape[:-2] + (-1,)))


class FullMixer(Chain):
    """One full layer's projections: ``n_heads`` query heads over
    ``n_kv`` K/V heads of ``head_dim``, an RMSNorm over the whole q and
    the whole k projection, no rotary position."""

    def __init__(self, d_model, n_heads, n_kv, head_dim, eps=1e-6, seed=0):
        super().__init__()
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
            self.q_norm = L.RMSNorm(n_heads * head_dim, eps)
            self.k_norm = L.RMSNorm(n_kv * head_dim, eps)

    @role("attn_proj")
    def project(self, x):
        """``x [..., d]`` -> ``(q [..., H, D], k, v [..., G, D])``, k and
        v as they are cached."""
        lead, D = x.shape[:-1], self.head_dim
        return (self.q_norm(self.q(x)).reshape(lead + (self.n_heads, D)),
                self.k_norm(self.k(x)).reshape(lead + (self.n_kv, D)),
                self.v(x).reshape(lead + (self.n_kv, D)))

    @role("attn_proj")
    def output(self, att):
        return self.o(att.reshape(att.shape[:-2] + (-1,)))


class HybridDeltaBlock(Chain):
    """One block: ``mix`` a :class:`DeltaMixer` (``linear=dict(n_heads,
    dk, dv, conv)``) or a :class:`FullMixer` (``full=dict(n_heads, n_kv,
    head_dim)``), each sublayer's OUTPUT normed."""

    def __init__(self, d_model, d_ff, linear=None, full=None, eps=1e-6,
                 seed=0):
        super().__init__()
        self.linear = linear is not None
        with self.init_scope():
            self.mix = DeltaMixer(d_model, eps=eps, seed=seed, **linear) \
                if self.linear else FullMixer(d_model, eps=eps, seed=seed,
                                              **full)
            self.ln1 = L.RMSNorm(d_model, eps)
            self.mlp = SwiGLU(d_model, d_ff, seed=seed + 10)
            self.ln2 = L.RMSNorm(d_model, eps)

    def residual(self, x, mixed):
        with role("norm"):
            h = x + self.ln1(mixed)
        with role("mlp"):
            m = self.mlp(h)
        with role("norm"):
            return h + self.ln2(m)


class HybridDeltaLM(Chain):
    """Causal LM whose layer ``l`` is a gated delta-rule layer where
    ``layer_linear[l]`` and a full-attention layer otherwise.

    ``linear``: ``dict(n_heads, dk, dv, conv)``; ``full``: ``dict(n_heads,
    n_kv, head_dim)``.  ``stride``: the tokens between the snapshots of
    state a prefill leaves (a multiple of the engine's page size and of
    the scan's chunk).  ``param_dtype``: the dtype a server holds the
    parameters in; computation follows it, with the state, the decays,
    the convolution, norm and softmax statistics in float32.
    """

    def __init__(self, n_vocab, d_model, layer_linear, linear, full, d_ff,
                 eps=1e-6, max_len=4096, stride=SNAPSHOT_STRIDE,
                 param_dtype=None, seed=0):
        super().__init__()
        self.max_len = int(max_len)
        self.param_dtype = param_dtype
        self.stride = int(stride)
        self.n_kv, self.head_dim = full["n_kv"], full["head_dim"]
        self.scale = full["head_dim"] ** -0.5
        # a layer's index inside its group's pools
        self.full_layers = [i for i, lin in enumerate(layer_linear)
                            if not lin]
        self.linear_layers = [i for i, lin in enumerate(layer_linear)
                              if lin]
        self._linear = dict(linear)
        with self.init_scope():
            self.embed = L.EmbedID(n_vocab, d_model, seed=seed)
            self.blocks = ChainList(*[
                HybridDeltaBlock(
                    d_model, d_ff, eps=eps, seed=seed + 100 * (i + 1),
                    **({"linear": linear} if lin else {"full": full}))
                for i, lin in enumerate(layer_linear)])
            self.ln_f = L.RMSNorm(d_model, eps)
            self.head = L.Linear(d_model, n_vocab, nobias=True,
                                 seed=seed + 999)

    # -- the whole forward (tests) ------------------------------------------

    def logits(self, x):
        """``x [B, T]`` token ids -> ``[B, T, V]``."""
        def one(tokens):
            with role("embed"):
                h = self.embed(tokens)
            for block in self.blocks:
                with jax.named_scope(f"blocks/{block.name}"):
                    mix = block.mix
                    if block.linear:
                        mixed, _, _ = self._scan(mix, h, None, None, None)
                    else:
                        q, k, v = mix.project(h)
                        mixed = mix.output(self._prompt_attention(q, k, v))
                    h = block.residual(h, mixed)
            with role("head"):
                return self.head(self.ln_f(h))
        return jnp.stack([one(row) for row in x])

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

    @role("state")     # the projections inside open ``attn_proj``
    def _scan(self, mix, x, true_len, state, before):
        """A linear layer over the rows ``x [T, d]`` of one sequence that
        follow the cached ``state [dk, H · dv]`` and convolution inputs
        ``before [taps - 1, channels]`` (``None``: a sequence's start):
        ``(mixed [T, d], states [n, dk, H · dv], convs [n, (taps - 1) ·
        channels])``, entry ``i`` what the cache holds after ``min((i +
        1) · stride, true_len)`` rows, so the last is where the sequence
        stands.  Rows from ``true_len`` on are padding and change
        nothing."""
        T = x.shape[0]
        H, dk, dv, taps = mix.n_heads, mix.dk, mix.dv, mix.taps
        true_len = T if true_len is None else true_len
        valid = (jnp.arange(T) < true_len)[:, None]
        rows = mix.inputs(x)
        if before is None:
            before = jnp.zeros((taps - 1, mix.channels), jnp.float32)
        q, k, v = mix.heads(mix.convolve(before, rows), x.dtype)
        g, beta = mix.gates(x)
        g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
        if state is not None:
            state = jnp.moveaxis(state.reshape(dk, H, dv), 1, 0)
        o, snaps = gated_delta_chunked(q, k, v, g, beta, state,
                                       stride=min(self.stride,
                                                  -(-T // CHUNK) * CHUNK))
        n = snaps.shape[0]
        states = jnp.moveaxis(snaps, 1, 2).reshape(n, dk, H * dv)
        # the convolution's inputs behind each of those positions
        xs = jnp.concatenate([before.astype(jnp.float32),
                              rows.astype(jnp.float32)], axis=0)
        at = jnp.minimum((jnp.arange(n) + 1) * self.stride, true_len)
        convs = jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(
            xs, p, taps - 1, axis=0))(at).reshape(n, -1)
        return mix.output(o, x), states, convs

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
        """The cache by groups of layers: the full layers keep a token's
        K then its V in one row of ``2 · G · D`` lanes, every position;
        the linear layers keep one entry a SEQUENCE
        (:class:`~chainermn_tpu.serving.kv_cache.PerSequence`), float32:
        every head's state side by side, ``[dk, H · dv]``, and the last
        ``taps - 1`` inputs of the convolution in one row, an array
        each (as rows under the state in ONE array, the partial reads
        and writes had the TPU compiler relay the whole pool between
        layers: compiled for the described v5e, PR 33), with a snapshot
        every ``stride`` tokens of a prefill."""
        lin = self._linear
        channels = lin["n_heads"] * (2 * lin["dk"] + lin["dv"])
        return (("full", len(self.full_layers),
                 ((2 * self.n_kv * self.head_dim,),), None),
                ("state", len(self.linear_layers),
                 ((lin["dk"], lin["n_heads"] * lin["dv"]),
                  ((lin["conv"] - 1) * channels,)),
                 PerSequence(self.stride)))

    def _layers(self):
        """Each block with its index inside its group's pools."""
        where = {}
        for layers in (self.full_layers, self.linear_layers):
            for j, i in enumerate(layers):
                where[i] = j
        return [(block, where[i]) for i, block in enumerate(self.blocks)]

    @role("head")
    def _finish(self, h_last):
        return self.head(self.ln_f(h_last)).astype(jnp.float32)

    def _prefill(self, pools, tokens, true_len, start, bt_rows):
        """The two prefills' body: ``start is None`` is a whole prompt
        from its first token (no state is read, the full layers attend
        over the prompt itself); otherwise the rows follow ``start``
        cached positions (the state is read from the SOURCE slot, the
        full layers attend over what is read back through the block
        table)."""
        kv, S, cv = pools
        T = tokens.shape[1]
        with role("attn"):
            bt = bt_rows[0]
        with role("state"):
            slots = bt_rows[1]
            n_slots = S.shape[1]
            n = -(-T // self.stride)
            # [live, source, snapshot 0, ...]: the last of the scan's
            # states goes to the live slot, state i to snapshot i;
            # nothing is written for an empty program (warm-up)
            into = jnp.where(true_len > 0, jnp.concatenate(
                [slots[2:2 + n], slots[:1]]), n_slots)
            fresh = None if start is None else start == 0
        with role("embed"):
            h = self.embed(tokens[0])
        for block, li in self._layers():
            with jax.named_scope(f"blocks/{block.name}"):
                mix = block.mix
                if block.linear:
                    h, S, cv = self._prefill_linear(
                        block, li, h, true_len, start, S, cv, slots, into,
                        fresh)
                    continue
                q, k, v = mix.project(h)
                if start is None:
                    with role("cache_write"):
                        kv = write_prompt_kv(kv, _entry(k, v), bt,
                                             true_len, layer=li)
                    att = self._prompt_attention(q, k, v)
                else:
                    with role("cache_write"):
                        kv = write_prompt_kv_at(kv, _entry(k, v), bt,
                                                start, true_len, layer=li)
                    att = paged_prefill_attention(
                        q, kv, None, bt, start, true_len, scale=self.scale,
                        layer=li, kv_heads=self.n_kv)
                h = block.residual(h, mix.output(att))
        with role("head"):
            return (kv, S, cv), self._finish(_last_row(h, true_len))[0], ()

    def _prefill_linear(self, block, li, h, true_len, start, S, cv, slots,
                        into, fresh):
        """One linear layer of a prefill: the scan on from the SOURCE
        slot's state, every stride's state to its snapshot slot (one
        beyond the pool where there is none to keep), then the last to
        the live slot.  ``(h, S, cv)``."""
        mix = block.mix
        with role("state"):
            state = before = None
            if start is not None:
                state = jnp.where(fresh, 0.0, S[li, slots[1]])
                before = jnp.where(fresh, 0.0, cv[li, slots[1]]) \
                    .reshape(mix.taps - 1, mix.channels)
            mixed, states, convs = self._scan(mix, h, true_len, state,
                                              before)
            S = S.at[li, into].set(
                jnp.concatenate([states, states[-1:]]), mode="drop")
            cv = cv.at[li, into].set(
                jnp.concatenate([convs, convs[-1:]]), mode="drop")
        return block.residual(h, mixed), S, cv

    def serve_prefill(self, pools, tokens, true_len, bt_rows):
        """Full prefill of one (padded) prompt ``tokens [1, Tb]``;
        ``pools``: the full group's pages, then the state group's states
        and convolution inputs; ``bt_rows [2, N]``: the block table, and
        ``[live, source, snapshot 0, ...]`` slots.  Returns ``(pools,
        logits [V], ())``."""
        return self._prefill(pools, tokens, true_len, None, bt_rows)

    def serve_suffix_prefill(self, pools, tokens, true_len, start, bt_rows):
        """Suffix prefill at offset ``start``: the linear layers scan on
        from the state in the SOURCE slot (copied, never written: a
        borrower leaves a snapshot as it found it), the full layers read
        the shared pages."""
        return self._prefill(pools, tokens, true_len, start, bt_rows)

    def serve_decode(self, pools, toks, pos, bts, mode=None, tp_mesh=None):
        """One token a lane (``pos < 0``: an idle lane, nothing
        written); ``bts [2, Bb, N]``, the state group's ``[:, 0]`` each
        lane's live slot, read and written in place.  Grouped pools have
        one lowering, so ``mode`` chooses nothing; ``tp_mesh`` is refused
        by the engine.  Returns ``(pools, logits [Bb, V], ())``."""
        kv, S, cv = pools
        with role("attn"):
            live = pos >= 0
            ctx = jnp.where(live, pos + 1, 0)
        with role("state"):
            slot = bts[1][:, 0]
            into = jnp.where(live, slot, S.shape[1])
        with role("embed"):
            h = self.embed(toks)
        for block, li in self._layers():
            with jax.named_scope(f"blocks/{block.name}"):
                mix = block.mix
                if block.linear:
                    with role("state"):
                        mixed, S, cv = self._decode_linear(
                            mix, li, h, S, cv, slot, into)
                else:
                    q, k, v = mix.project(h)
                    with role("cache_write"):
                        kv = write_token_kv(kv, _entry(k, v), bts[0], pos,
                                            layer=li)
                    with role("attn"):      # the group's block table too
                        att = paged_decode_attention(
                            q, kv, None, bts[0], ctx, scale=self.scale,
                            layer=li, kv_heads=self.n_kv)
                    mixed = mix.output(att)
                h = block.residual(h, mixed)
        return (kv, S, cv), self._finish(h), ()

    @staticmethod
    def _decode_linear(mix, li, h, S, cv, slot, into):
        """One linear layer of a decode step: each lane's slot read, the
        convolution's newest row, the delta update, the slot written in
        place.  ``(mixed, S, cv)``."""
        B = h.shape[0]
        before = _slots_of(cv, li, slot).reshape(
            B, mix.taps - 1, mix.channels)
        row = mix.inputs(h).astype(jnp.float32)
        xs = jnp.concatenate([before, row[:, None]], axis=1)
        y = jax.nn.silu(jnp.einsum(
            "btc,ct->bc", xs, mix.conv.array.astype(jnp.float32)))
        q, k, v = mix.heads(y, h.dtype)
        g, beta = mix.gates(h)
        o, state = gated_delta_step(_slots_of(S, li, slot), q, k, v, g,
                                    beta)
        S = S.at[li, into].set(state, mode="drop")
        cv = cv.at[li, into].set(xs[:, 1:].reshape(B, -1), mode="drop")
        return mix.output(o, h), S, cv
