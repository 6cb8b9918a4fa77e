"""``PreroutedMoELM`` (the ``smallthinker`` block) behind ``ServingEngine``
with a page pool for each kind of layer, against the plain reference, at
tiny widths in float32 on seeded weights: the logits of a full prefill,
of a suffix prefill after a prefix hit whose window pages only the trie
still held, and of decoding until every lane is more than a window and
two pages past its prompt; the four ways to get this block wrong, each
far from the reference; what it shares with ``WindowMoELM`` and what the
engine refuses for it.

Tolerances.  ``ATOL`` 2e-4 on logits of size 4: program and reference
are both float32 here and differ in the order of their sums (the
program's experts add six sorted terms, the reference's loop sixteen
masked ones; the paged attention adds page by page); the sound engine
reads 2e-6.  The float8 control reads 0.4, two thousand times ``ATOL``.
A wrong block is held to ``WRONG`` 0.05, 250 times ``ATOL``: the
mildest of the four, SiLU for ReLU, reads 0.12 (it changes the experts'
term alone, which the scaled down-projections keep small beside the
stream), the router fed ``norm2`` 0.30, a full layer rotated 0.91, a
window layer served whole 3.2."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.models import PreroutedMoELM, WindowMoELM
from chainermn_tpu.models.window_moe import WindowCacheLM
from chainermn_tpu.models.prerouted_moe import GroupedAttention
from chainermn_tpu.observability import role
from chainermn_tpu.serving import Request, ServingEngine
from chainermn_tpu.serving.errors import UnsupportedProgramError

from .test_latent_moe import Recorded

ATOL, WRONG = 2e-4, 0.05
WINDOW, PAGE = 16, 8
TINY = dict(hidden_size=64, head_dim=16, num_key_value_heads=2,
            num_attention_heads=6, sliding_window_size=WINDOW,
            moe_ffn_hidden_size=32, moe_num_primary_experts=16,
            moe_num_active_primary_experts=3, vocab_size=256,
            param_dtype="float32")


def tiny_config(**over):
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "smallthinker-21b-a3b-stage.json"))
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def build(cfg, seed=7):
    builder = harness.load_module("models", "prerouted_moe_lm")
    model = builder.build(cfg, max_len=128)
    assert all(p.is_abstract for p in model.params())   # nothing drawn
    params = weights.make_params(
        _init.param_spec(model, builder.init_rule), seed)
    _init.load(model, params)
    return model, params


@pytest.fixture(scope="module")
def built():
    """(config, model with seeded weights loaded, params, reference)."""
    cfg = tiny_config()
    model, params = build(cfg)
    return cfg, model, params, harness.load_module("reference",
                                                   "prerouted_moe")


def engine_kw(**over):
    return dict(dict(num_pages=64, page_size=PAGE, max_batch=4,
                     max_context=128), **over)


def reference_rows(built, req):
    cfg, _, params, ref = built
    full = np.zeros(128, np.int32)
    n = req.prompt.size + len(req.tokens)
    full[:req.prompt.size] = req.prompt
    full[req.prompt.size:n] = req.tokens
    rows = slice(req.prompt.size - 1, n - 1)
    return full, rows, np.asarray(ref.sequence_logits(cfg, params,
                                                      full))[rows]


# case: tokens shared with the live holder's prompt.  The holder's prompt
# is 45 tokens: at 32 its own window (the last 16) has moved on, and the
# pages covering (16, 32) are the trie's alone
CASES = {"full_prefill": None, "suffix_prefill": 32}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_with_two_pools_matches_the_reference_forward(built, case):
    cfg, model, params, ref = built
    shared = CASES[case]
    rng = np.random.RandomState(3)
    rec = Recorded(model, **engine_kw())
    e = rec.engine
    holder = Request(rng.randint(0, 256, 45).astype(np.int32), 44,
                     tenant="a", request_id=1)
    e.submit(holder)
    e.step()
    a = e.allocator
    assert a.window_retained_pages == (45 - WINDOW + 1) // PAGE
    probe = holder
    if shared is not None:
        probe = Request(np.concatenate(
            [holder.prompt[:shared],
             rng.randint(0, 256, 13).astype(np.int32)]), 42, tenant="a",
            request_id=2)
        e.submit(probe)
    while e.running or e.scheduler.pending():
        e.step()
        assert a.check()
    assert e.prefix_hits == (shared is not None) and e.forks == 0
    assert e.prefix_tokens_matched == (shared or 0)
    # every lane went more than a window and two pages past its prompt
    assert len(probe.tokens) > WINDOW + 2 * PAGE
    served = np.stack(rec.rows[probe.request_id][:len(probe.tokens)])
    full, rows, want = reference_rows(built, probe)
    np.testing.assert_allclose(served, want, atol=ATOL, rtol=0)
    assert list(served.argmax(-1)) == probe.tokens
    control = np.asarray(ref.sequence_logits(cfg, params, full,
                                             precision="fp8"))[rows]
    assert np.abs(control - want).max() > 1000 * ATOL
    assert a.window_used_pages == 0 and a.used_pages == 0


# -- the four ways to get this block wrong -------------------------------------

class _RoutedAfterAttention(PreroutedMoELM):
    """The router fed what the experts are fed, ``norm2``'s output."""

    def _block(self, block, h, att, logits, valid, counts):
        with role("attn_proj"):
            after = h + block.attn.output(att)
        with role("norm"):
            logits = block.experts.logits(block.ln2(after))
        return super()._block(block, h, att, logits, valid, counts)


def _wrong_program(name, cfg):
    """A model with the sound one's seeded weights and one thing wrong."""
    if name == "router_fed_norm2":
        model, _ = build(cfg)
        model.__class__ = _RoutedAfterAttention
    elif name == "full_layer_rotated":
        model, _ = build(dict(cfg, rope_layout=[1] * 52))
    elif name == "window_layer_served_whole":
        model, _ = build(dict(cfg, sliding_window_layout=[0] * 52))
    else:
        assert name == "silu_for_relu"
        model, _ = build(cfg)
        for block in model.blocks:
            block.experts.activation = jax.nn.silu
    return model


@pytest.mark.parametrize("wrong", [
    "router_fed_norm2", "full_layer_rotated", "window_layer_served_whole",
    "silu_for_relu"])
def test_a_wrong_block_is_far_from_the_reference(built, wrong):
    """The whole forward of a 64-token sequence (four windows long): the
    sound program is the reference's to ``ATOL``, each wrong one is
    further than ``WRONG``."""
    cfg, model, params, ref = built
    tokens = np.random.RandomState(5).randint(0, 256, 64).astype(np.int32)
    want = np.asarray(ref.sequence_logits(cfg, params, tokens))
    sound = np.asarray(model.logits(jnp.asarray(tokens[None])))[0]
    np.testing.assert_allclose(sound, want, atol=ATOL, rtol=0)
    got = np.asarray(_wrong_program(wrong, cfg).logits(
        jnp.asarray(tokens[None])))[0]
    assert np.abs(got - want).max() > WRONG
    if wrong == "window_layer_served_whole":
        # inside the first window the two are the same model
        np.testing.assert_allclose(got[:WINDOW], want[:WINDOW], atol=ATOL,
                                   rtol=0)


def test_the_references_own_keys_move_it_as_the_program_moves(built):
    """The same four departures made in the REFERENCE's configuration
    (where it has the key) move the reference: the keys are read."""
    cfg, _, params, ref = built
    tokens = np.random.RandomState(5).randint(0, 256, 64).astype(np.int32)
    want = np.asarray(ref.sequence_logits(cfg, params, tokens))
    for over in (dict(rope_layout=[1] * 52),
                 dict(sliding_window_layout=[0] * 52),
                 dict(moe_num_active_primary_experts=2)):
        got = np.asarray(ref.sequence_logits(dict(cfg, **over), params,
                                             tokens))
        assert np.abs(got - want).max() > WRONG


# -- what it shares with the window model, and what it is ----------------------

def test_the_serving_bodies_are_the_window_models(built):
    _, model, _, _ = built
    assert isinstance(model, WindowCacheLM)
    assert not isinstance(model, WindowMoELM)   # its blocks are not built
    for name in ("serve_prefill", "serve_suffix_prefill", "serve_decode",
                 "serve_cache_groups", "serve_span_stats",
                 "_prompt_attention", "_layers", "_finish", "logits"):
        assert getattr(PreroutedMoELM, name) is getattr(WindowMoELM, name) \
            is getattr(WindowCacheLM, name)
    for name in ("_project", "_block"):
        assert getattr(PreroutedMoELM, name) is not getattr(WindowMoELM,
                                                            name)


def test_each_group_has_pools_of_its_own_page_count(built):
    _, model, _, _ = built
    assert model.serve_cache_groups() == (
        ("full", 1, ((64,),), None),
        ("window", 3, ((64,),), WINDOW))
    e = ServingEngine(model, **engine_kw())
    # 4 lanes x 3 pages + 4 x 16 = 76 -> 128
    assert [p.shape for p in e.kv.pools] == \
        [(1, 64, PAGE, 64), (3, 128, PAGE, 64)]
    assert e.cache_groups == 2


def test_a_block_has_no_gate_no_head_norm_no_bias_no_shared_expert(built):
    cfg, model, _, _ = built
    leaves = sorted(path for path, _ in model.blocks[1].namedparams())
    assert leaves == ["/attn/k/W", "/attn/o/W", "/attn/q/W", "/attn/v/W",
                      "/experts/router", "/experts/w_down",
                      "/experts/w_gate", "/experts/w_up", "/ln1/gamma",
                      "/ln2/gamma"]
    # positions where rope_layout says, a window where the other list does
    assert [b.attn.inv_freq is not None for b in model.blocks] == \
        [False, True, True, True]
    assert [b.attn.window for b in model.blocks] == \
        [None, WINDOW, WINDOW, WINDOW]
    assert model.blocks[1].attn.inv_freq[1] == pytest.approx(
        cfg["rope_theta"] ** (-2 / 16))
    with pytest.raises(ValueError, match="do not group"):
        GroupedAttention(64, 5, 2, 16)


def test_the_router_reads_the_blocks_input_under_its_own_role(built):
    """In the traced block the router's product is the FIRST operation
    that reads the block's input, ahead of ``ln1``'s statistics, and it
    lies under role ``router``."""
    _, model, _, _ = built
    block = model.blocks[1]
    h = jnp.ones((8, 64), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(lambda h: model._project(block, h, pos))(h)
    first = jaxpr.jaxpr.eqns[0]
    names = [str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns]
    dots = [n for e, n in zip(jaxpr.jaxpr.eqns, names)
            if e.primitive.name == "dot_general"]
    assert "~router" in dots[0] and all("~attn_proj" in n for n in dots[1:])
    assert first.primitive.name in ("transpose", "dot_general",
                                    "convert_element_type")
    assert "~router" in names[0]


@pytest.mark.parametrize("asked,program", [
    (dict(spec_k=2), "verify"), (dict(tp=2), "pool_sharding"),
    (dict(disagg=True), "page_ship")])
def test_a_program_two_groups_lack_is_refused_typed(built, asked, program):
    _, model, _, _ = built
    with pytest.raises(UnsupportedProgramError) as e:
        ServingEngine(model, **engine_kw(**asked))
    assert e.value.model == "PreroutedMoELM" and e.value.program == program


def test_span_stats_count_the_experts_touched_of_all(built):
    _, model, _, _ = built
    counts = np.zeros((4, 16), np.int32)
    counts[0, :3] = [2, 1, 3]
    counts[2, 15] = 6
    assert model.serve_span_stats(counts) == {
        "held_copies": 3.0, "held_max": 2.25, "held_hit": 4}


def test_decode_counts_the_live_lanes_copies_alone(built):
    """Two live lanes of four: 2 x 3 copies a layer, and never an expert
    for an idle lane."""
    _, model, _, _ = built
    e = ServingEngine(model, **engine_kw())
    rng = np.random.RandomState(9)
    for i in range(2):
        e.submit(Request(rng.randint(0, 256, 12).astype(np.int32), 6,
                         tenant="a", request_id=i + 1))
    e.step()
    out = e._decode_fn(e.state, *e.kv.pools, jnp.zeros(4, jnp.int32),
                       jnp.asarray([12, 12, -1, -1], jnp.int32),
                       jnp.asarray(e._zero_bt(4)))
    counts = np.asarray(out[-1])
    assert counts.shape == (4, 16)
    assert list(counts.sum(axis=1)) == [6, 6, 6, 6]
