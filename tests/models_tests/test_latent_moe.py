"""The latent-attention MoE model against its plain reference
(``benchmark/reference/latent_moe.py``, which imports nothing of the
program), at a small size on the CPU with seeded weights in float32:

* through ``ServingEngine``'s latent page pool — a full prefill, a
  prefix-hit suffix prefill and one behind a forked page, each followed
  by decode steps — the logits of every served position equal the
  reference's full forward;
* the absorbed attention (what reads the cache) equals the expanded
  form (what a whole prompt runs);
* the share ties to the model: the held-expert parts of all shares, with
  the shared expert counted once, add up to the uncut reference layer;
* top-k routing drops nothing under a skew onto one expert;
* a program the model lacks is refused with the typed error.

Tolerance: everything here is float32 on both sides, so the only
difference is the order of float32 sums (the program sums experts inside
one product, the reference in a loop; the absorbed form reassociates two
products): logits of magnitude ~4 agree to 2e-4 absolute.  The same
comparison against the reference in float8 (the control) misses by four
orders of magnitude more, which each case asserts.
"""

import copy
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.core.link import bind_state, extract_state
from chainermn_tpu.parallel.moe import (HeldExperts, sorted_experts_ffn,
                                        sigmoid_topk_route)
from chainermn_tpu.serving import (Request, ServingEngine,
                                   UnsupportedProgramError)

ATOL = 2e-4
TINY = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            num_hidden_layers=3, n_routed_experts=4, vocab_size=256,
            num_experts_per_tok=3, param_dtype="float32")
N_EXPERTS = 16


def tiny_config(**over):
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "kimi-k2.6-share.json"))
    cfg.update(TINY)
    cfg.update(over)
    cfg["published"] = dict(cfg["published"], n_routed_experts=N_EXPERTS)
    return cfg


@pytest.fixture(scope="module")
def built():
    """(config, model with seeded weights loaded, params, reference)."""
    cfg = tiny_config()
    builder = harness.load_module("models", "latent_moe_lm")
    model = builder.build(cfg, max_len=128)
    assert all(p.is_abstract for p in model.params())   # nothing drawn
    params = weights.make_params(
        _init.param_spec(model, builder.init_rule), 7)
    _init.load(model, params)
    return cfg, model, params, harness.load_module("reference",
                                                   "latent_moe")


class Recorded:
    """An engine whose three programs' logits are kept as they are
    produced: ``rows[request_id]`` is that request's served logits, the
    prefill's row first, then one row a decode step."""

    def __init__(self, model, **kw):
        self.engine = e = ServingEngine(model, **kw)
        self.rows = {}
        n = len(e.kv.pools)
        self.admitting = None

        def prefill(fn):
            def run(*args):
                out = fn(*args)
                self.rows.setdefault(self.admitting, []).append(
                    np.asarray(out[n]))
                return out
            return run

        def decode(fn):
            def run(*args):
                # the run's lanes: the running sequences less those whose
                # last token is still in flight
                lanes = [r.request_id for r in e._next_lanes()]
                out = fn(*args)
                for j, rid in enumerate(lanes):
                    self.rows[rid].append(np.asarray(out[n][j]))
                return out
            return run
        e._prefill_fn = prefill(e._prefill_fn)
        e._prefix_prefill_fn = prefill(e._prefix_prefill_fn)
        e._decode_fn = decode(e._decode_fn)
        admit = e._admit

        def admitting(req, clock):
            self.admitting = req.request_id
            return admit(req, clock)
        e._admit = admitting


# case: (tokens shared with a live holder's prompt, prefix hits): 16 ends
# on a page boundary (page size 8), 19 inside a page, which is forked
CASES = {"full_prefill": (None, 0), "suffix_prefill": (16, 1),
         "forked_page": (19, 1)}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_latent_pool_matches_the_reference_forward(built, case):
    cfg, model, params, ref = built
    shared, hits = CASES[case]
    rng = np.random.RandomState(3)
    rec = Recorded(model, num_pages=64, page_size=8, max_batch=4,
                   max_context=128)
    e = rec.engine
    holder = Request(rng.randint(0, 256, 21).astype(np.int32), 24,
                     tenant="a", request_id=1)
    e.submit(holder)
    e.step()
    probe = holder
    if shared is not None:
        probe = Request(np.concatenate(
            [holder.prompt[:shared],
             rng.randint(0, 256, 9).astype(np.int32)]), 6, tenant="a",
            request_id=2)
        e.submit(probe)
    e.drain()
    assert e.prefix_hits == hits
    assert e.forks == (1 if case == "forked_page" else 0)
    assert e.prefix_tokens_matched == (shared or 0)
    served = np.stack(rec.rows[probe.request_id][:len(probe.tokens)])
    full = np.zeros(128, np.int32)
    n = probe.prompt.size + len(probe.tokens)
    full[:probe.prompt.size] = probe.prompt
    full[probe.prompt.size:n] = probe.tokens
    rows = slice(probe.prompt.size - 1, n - 1)
    want = np.asarray(ref.sequence_logits(cfg, params, full))[rows]
    np.testing.assert_allclose(served, want, atol=ATOL, rtol=0)
    assert list(served.argmax(-1)) == probe.tokens
    control = np.asarray(ref.sequence_logits(cfg, params, full,
                                             precision="fp8"))[rows]
    assert np.abs(control - want).max() > 1000 * ATOL


def test_absorbed_attention_equals_the_expanded_form(built):
    from chainermn_tpu.ops.paged_attention import paged_latent_attention
    _, model, _, _ = built
    attn = model.blocks[1].attn
    T, S = 24, 8
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(size=(T, 64)).astype(np.float32))
    pos = jnp.arange(T, dtype=jnp.int32)
    with bind_state(model, extract_state(model)):
        q_nope, q_rope, lat = attn.latents(x, pos)
        expanded = attn.expanded(q_nope, q_rope, lat, model.softmax_scale)
        pool = jnp.zeros((T // S + 1, S, lat.shape[-1])) \
            .at[:T // S].set(lat.reshape(T // S, S, -1))
        o_lat = paged_latent_attention(
            attn.absorb_query(q_nope, q_rope)[None], pool,
            jnp.arange(T // S, dtype=jnp.int32)[None], pos[None],
            attn.kv_rank, scale=model.softmax_scale)[0]
        absorbed = attn.unabsorb_output(o_lat)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5, rtol=0)


def test_all_shares_with_the_shared_expert_once_make_the_uncut_layer(built):
    """The reference given EVERY expert (share 0 of 1) is the uncut
    layer; the program's shares each add their held experts' terms."""
    cfg, model, params, ref = built
    uncut = tiny_config(n_routed_experts=N_EXPERTS)
    held = cfg["n_routed_experts"]
    rng = np.random.RandomState(11)

    def draw(*shape, fan_in):
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, shape)
                           .astype(np.float32))
    D, Fw = 64, 32
    layer = {leaf: params[f"/blocks/1/{leaf}"] for leaf in ref._ROUTED}
    layer.update({"experts/w_gate": draw(N_EXPERTS, Fw, D, fan_in=D),
                  "experts/w_up": draw(N_EXPERTS, Fw, D, fan_in=D),
                  "experts/w_down": draw(N_EXPERTS, Fw, D, fan_in=Fw)})
    h = jnp.asarray(rng.normal(size=(40, D)).astype(np.float32))
    whole = ref._routed_layer(h, layer, ref._shape(uncut), "float32")
    # what every share computes alike: attention, the shared expert, the
    # residual — the reference with NO expert held
    none = dict(layer, **{k: layer[k][:0] for k in
                          ("experts/w_gate", "experts/w_up",
                           "experts/w_down")})
    alike = ref._routed_layer(h, none, ref._shape(uncut), "float32")
    after_attention = ref._attention(h, layer, ref._shape(uncut), "float32")
    x = ref._norm(after_attention, layer["ln2/gamma"], cfg["rms_norm_eps"])
    ids, w = sigmoid_topk_route(x, layer["experts/router"],
                                layer["experts/router_bias"],
                                cfg["num_experts_per_tok"],
                                cfg["routed_scaling_factor"])
    parts, copies = 0.0, 0
    for share in range(N_EXPERTS // held):
        sl = slice(share * held, (share + 1) * held)
        y, counts = sorted_experts_ffn(
            x, ids, w, layer["experts/w_gate"][sl],
            layer["experts/w_up"][sl], layer["experts/w_down"][sl],
            share * held, jax.nn.silu)
        parts, copies = parts + y, copies + int(counts.sum())
    assert copies == 40 * cfg["num_experts_per_tok"]   # none dropped
    np.testing.assert_allclose(np.asarray(alike + parts),
                               np.asarray(whole), atol=2e-5, rtol=0)
    assert np.abs(np.asarray(parts)).max() > 0.1


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (0, 16)])
def test_top_k_drops_nothing_under_a_skew_onto_one_expert(held):
    """A selection bias that sends EVERY token to expert 5 first: the
    share that holds it computes all T copies (a capacity buffer would
    have dropped most), the others none of them."""
    T, D, Fw, E, k = 64, 32, 16, 16, 3
    layer = HeldExperts(D, Fw, E, held, k, routed_scale=2.0)
    bias = np.zeros(E, np.float32)
    bias[5] = 10.0
    layer.router_bias.array = jnp.asarray(bias)
    x = jnp.asarray(np.random.RandomState(0).normal(size=(T, D))
                    .astype(np.float32))
    y, counts = layer(x)
    first, count = held
    assert int(counts.sum()) <= T * k
    if first <= 5 < first + count:
        assert int(counts[5 - first]) == T
    # against every expert computed densely, token by token
    ids, w = sigmoid_topk_route(x, layer.router.array, bias, k, 2.0)
    assert (np.asarray(ids) == 5).any(-1).all()
    want = np.zeros((T, D), np.float32)
    for t in range(T):
        for e, we in zip(np.asarray(ids[t]), np.asarray(w[t])):
            if first <= e < first + count:
                g = layer.w_gate.array[e - first] @ x[t]
                u = layer.w_up.array[e - first] @ x[t]
                want[t] += we * np.asarray(
                    (jax.nn.silu(g) * u) @ layer.w_down.array[e - first])
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=0)
    valid = jnp.arange(T) < 10
    assert int(layer(x, valid=valid)[1].sum()) == int(
        ((np.asarray(ids)[:10] >= first)
         & (np.asarray(ids)[:10] < first + count)).sum())


@pytest.mark.parametrize("asked", [dict(spec_k=2), dict(tp=2)])
def test_a_program_the_model_lacks_is_refused_typed(built, asked):
    _, model, _, _ = built
    with pytest.raises(UnsupportedProgramError) as e:
        ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                      max_context=64, **asked)
    assert e.value.program in ("verify", "pool_sharding")
    assert e.value.model == "LatentMoELM"


def test_the_latent_pool_is_one_array_of_the_declared_entry(built):
    _, model, _, _ = built
    e = ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                      max_context=64)
    # 32 + 8 latent values a token, filled up to one 128-lane tile
    assert model.serve_cache_entry() == ((128,),)
    assert [p.shape for p in e.kv.pools] == [(3, 16, 8, 128)]
    assert e.kv.page_bytes == 8 * 128 * 4
    assert e.kv.pool_bytes == 3 * 16 * e.kv.page_bytes


def test_decode_counts_the_held_copies_of_live_lanes_only(built):
    from chainermn_tpu.serving import decode_program
    _, model, _, _ = built
    e = ServingEngine(model, num_pages=16, page_size=8, max_batch=4,
                      max_context=64)
    toks = jnp.zeros(4, jnp.int32)
    bts = jnp.zeros((4, e.n_block_entries), jnp.int32)
    *_, idle = decode_program(model, e.state, *e.kv.pools, toks,
                              jnp.full(4, -1, jnp.int32), bts, mode=None)
    assert idle.shape == (2, 4) and int(idle.sum()) == 0
    *_, live = decode_program(model, e.state, *e.kv.pools, toks,
                              jnp.asarray([0, -1, 0, -1], jnp.int32), bts,
                              mode=None)
    assert 0 < int(live.sum()) <= 2 * 2 * 3   # layers x lanes x k
    stats = model.serve_span_stats(np.asarray(live))
    assert stats["held_copies"] == live.sum() / 2
    assert stats["held_max"] <= stats["held_copies"]
    # the held experts that received a copy, summed over expert layers
    assert stats["held_hit"] == int((np.asarray(live) > 0).sum()) > 0
    assert model.serve_span_stats(np.array([[2, 0, 1, 0], [0, 0, 0, 4]])) \
        == {"held_copies": 3.5, "held_max": 3.0, "held_hit": 3}
