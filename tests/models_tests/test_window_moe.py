"""``WindowMoELM`` (the ``laguna`` block) behind ``ServingEngine`` with a
page pool for each kind of layer, against the plain reference, at tiny
widths in float32 on seeded weights: the logits of a full prefill, of a
suffix prefill after a prefix hit whose window pages only the trie still
held, and of decoding until every lane is more than a window and two
pages past its prompt; the shares' routed parts with the shared expert
once make the uncut layer; what the engine refuses for it; and what its
programs gather."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.parallel.moe import sigmoid_topk_route, sorted_experts_ffn
from chainermn_tpu.serving import Request, ServingEngine
from chainermn_tpu.serving.errors import UnsupportedProgramError

from .test_latent_moe import Recorded

ATOL = 2e-4
WINDOW, PAGE = 16, 8
TINY = dict(hidden_size=64, head_dim=16, num_key_value_heads=2,
            num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
            sliding_window=WINDOW, intermediate_size=96,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_hidden_layers=5, num_experts=4, vocab_size=256,
            num_experts_per_tok=3, param_dtype="float32")
N_EXPERTS = 16


def tiny_config(**over):
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "laguna-s-2.1-share.json"))
    cfg.update(TINY)
    cfg.update(over)
    cfg["published"] = dict(cfg["published"], num_experts=N_EXPERTS)
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 32
    return cfg


@pytest.fixture(scope="module")
def built():
    """(config, model with seeded weights loaded, params, reference)."""
    cfg = tiny_config()
    builder = harness.load_module("models", "window_moe_lm")
    model = builder.build(cfg, max_len=128)
    assert all(p.is_abstract for p in model.params())   # nothing drawn
    params = weights.make_params(
        _init.param_spec(model, builder.init_rule), 7)
    _init.load(model, params)
    return cfg, model, params, harness.load_module("reference",
                                                   "window_moe")


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
    most = 0
    while e.running or e.scheduler.pending():
        e.step()
        assert a.check()
        # outside a prefill a sequence holds its window and the page it
        # grows into, whatever its context
        for r in e.running:
            table, low = a.window_table(r.request_id)
            most = max(most, len(table) - low)
    assert WINDOW // PAGE + 1 <= most <= WINDOW // PAGE + 2
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


def test_a_window_layer_read_whole_is_another_model(built):
    """The reference with its sliding layers' window opened wide
    differs past the window: it is in the numbers, not only in the
    pages."""
    cfg, _, params, ref = built
    tokens = np.random.RandomState(5).randint(0, 256, 64).astype(np.int32)
    full = dict(cfg, sliding_window=1 << 20)
    a = np.asarray(ref.sequence_logits(cfg, params, tokens))
    b = np.asarray(ref.sequence_logits(full, params, tokens))
    np.testing.assert_allclose(a[:WINDOW], b[:WINDOW], atol=1e-5, rtol=0)
    assert np.abs(a[WINDOW + 8:] - b[WINDOW + 8:]).max() > 1000 * ATOL


def test_all_shares_with_the_shared_expert_once_make_the_uncut_layer(built):
    """The reference given EVERY expert (share 0 of 1) is the uncut
    layer; the program's shares each add their held experts' terms."""
    cfg, model, params, ref = built
    uncut = tiny_config(num_experts=N_EXPERTS)
    held = cfg["num_experts"]
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
    shape = ref._shape(uncut, 1)
    whole = ref._routed_layer(h, layer, shape, "float32")
    # what every share computes alike: attention, the shared expert, the
    # residual: the reference with NO expert held
    none = dict(layer, **{k: layer[k][:0] for k in
                          ("experts/w_gate", "experts/w_up",
                           "experts/w_down")})
    alike = ref._routed_layer(h, none, shape, "float32")
    after_attention = ref._attention(h, layer, shape, "float32")
    x = ref._norm(after_attention, layer["ln2/gamma"], cfg["rms_norm_eps"])
    ids, w = sigmoid_topk_route(x, layer["experts/router"],
                                layer["experts/router_bias"],
                                cfg["num_experts_per_tok"],
                                cfg["moe_routed_scaling_factor"])
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


@pytest.mark.parametrize("asked,program", [
    (dict(spec_k=2), "verify"), (dict(tp=2), "pool_sharding"),
    (dict(disagg=True), "page_ship")])
def test_a_program_two_groups_lack_is_refused_typed(built, asked, program):
    _, model, _, _ = built
    with pytest.raises(UnsupportedProgramError) as e:
        ServingEngine(model, **engine_kw(**asked))
    assert e.value.model == "WindowMoELM" and e.value.program == program


def test_each_group_has_pools_of_its_own_page_count(built):
    _, model, _, _ = built
    assert model.serve_cache_groups() == (
        ("full", 2, ((64,),), None),
        ("window", 3, ((64,),), WINDOW))
    e = ServingEngine(model, **engine_kw())
    # 4 lanes x 3 pages + 4 x 16 = 76 -> 128
    assert [p.shape for p in e.kv.pools] == \
        [(2, 64, PAGE, 64), (3, 128, PAGE, 64)]
    assert e.kv.pool_bytes == sum(p.size * 4 for p in e.kv.pools)
    assert e.kv.page_bytes == PAGE * 64 * 4
    assert e.allocator.num_pages == 64
    assert e.allocator.windows[0].num_pages == 128
    assert e.cache_groups == 2


@pytest.mark.parametrize("max_context", [128, 256])
def test_a_window_layers_decode_gather_does_not_grow_with_the_context(
        built, max_context):
    """From the lowered decode program's shapes: each window layer
    gathers ``window / page + 1`` pages a lane from its K and from its V
    pool, each full layer the whole block table.  The layers of a kind
    share ONE lowering of their read (``window_moe._decode_attention``,
    PR 48): the program holds one gather a kind and calls it a layer."""
    import re
    cfg = tiny_config()
    model = harness.load_module("models", "window_moe_lm").build(
        cfg, max_len=256)
    _init.load(model, weights.make_params(_init.param_spec(
        model, harness.load_module("models", "window_moe_lm").init_rule),
        7))
    e = ServingEngine(model, **engine_kw(max_context=max_context))
    N = max_context // PAGE
    text = e._decode_fn.lower(
        e.state, *e.kv.pools, jnp.zeros(4, jnp.int32),
        jnp.full(4, -1, jnp.int32),
        jnp.zeros((2, 4, N), jnp.int32)).as_text()
    gathered = re.findall(r"-> tensor<4x(\d+)x8x64xf32>",
                          "\n".join(line for line in text.splitlines()
                                    if "stablehlo.gather" in line))
    assert sorted(gathered) == sorted([str(WINDOW // PAGE + 1), str(N)])
    calls = re.findall(r"call @(_decode_attention\w*)\(", text)
    assert len(calls) == 3 + 2 and len(set(calls)) == 2


def test_span_stats_count_the_experts_hit(built):
    _, model, _, _ = built
    counts = np.array([[2, 0, 1, 0], [0, 0, 0, 4]])
    assert model.serve_span_stats(counts) == {
        "held_copies": 3.5, "held_max": 3.0, "held_hit": 3}


def test_a_chunked_prefill_slides_after_each_chunk(built):
    """``chunk_tokens``: the prompt goes in chunks through the suffix
    program, the window group released behind each, and the served
    logits are the reference's."""
    _, model, _, _ = built
    rec = Recorded(model, **engine_kw(chunk_tokens=16))
    e = rec.engine
    chunk, n = e._chunk_fn, len(e.kv.pools)

    def recorded(*args):        # the last chunk's row is the first token's
        out = chunk(*args)
        rec.rows.setdefault(rec.admitting, []).append(np.asarray(out[n]))
        return out
    e._chunk_fn = recorded
    req = Request(np.random.RandomState(3).randint(0, 256, 45)
                  .astype(np.int32), 30, tenant="a", request_id=1)
    e.submit(req)
    while e.running or e.prefilling or e.scheduler.pending():
        e.step()
        assert e.allocator.check()
    assert e.chunk_prefills == 3
    served = np.stack(rec.rows[1][-len(req.tokens):])
    _, _, want = reference_rows(built, req)
    np.testing.assert_allclose(served, want, atol=ATOL, rtol=0)
