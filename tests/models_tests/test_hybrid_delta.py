"""``HybridDeltaLM`` (the ``olmo_hybrid`` block) behind ``ServingEngine``
with state slots beside the page pool, against the plain reference's one
full forward pass, at tiny widths in float32 on seeded weights, LOGITS
not tokens: a full prefill and the decode steps through the slots; a
prefix hit that restores a snapshot of state after its holder has
decoded on; a chunked prefill that carries the live slot from chunk to
chunk and leaves the same snapshots; eviction and replay; what the
engine refuses for it; and what its cache is declared as."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.serving import PerSequence, Request, ServingEngine
from chainermn_tpu.serving.errors import UnsupportedProgramError

from .test_latent_moe import Recorded

ATOL = 3e-4
PAGE, STRIDE = 8, 64
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, vocab_size=256, num_hidden_layers=8,
            param_dtype="float32", snapshot_stride=STRIDE)


def tiny_config(**over):
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "olmo-hybrid-7b-stage.json"))
    cfg.update(TINY)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def built():
    """(config, model with seeded weights loaded, params, reference)."""
    cfg = tiny_config()
    builder = harness.load_module("models", "hybrid_delta_lm")
    model = builder.build(cfg, max_len=256)
    assert all(p.is_abstract for p in model.params())   # nothing drawn
    params = weights.make_params(
        _init.param_spec(model, builder.init_rule), 7)
    _init.load(model, params)
    return cfg, model, params, harness.load_module("reference",
                                                   "hybrid_delta")


def engine_kw(**over):
    return dict(dict(num_pages=96, page_size=PAGE, max_batch=4,
                     max_context=256), **over)


def prompt_of(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def run_out(e):
    while e.running or e.prefilling or e.scheduler.pending():
        e.step()
        assert e.allocator.check()


def assert_served_is_the_reference(built, rec, req, first):
    """Every logits row the engine produced for ``req`` (the prefill's,
    then one a decode step) against the reference's forward over the
    whole sequence; ``first``: the prompt's length as submitted (an
    evicted request folds its tokens into its prompt)."""
    cfg, _, params, ref = built
    full = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
    served = np.stack(rec.rows[req.request_id])
    want = ref.sequence_logits(cfg, params, full)[first - 1:full.size - 1]
    assert served.shape == want.shape
    np.testing.assert_allclose(served, want, atol=ATOL, rtol=0)
    assert list(served.argmax(-1)) == list(full[first:])
    return full, want


def test_whole_forward_is_the_reference(built):
    cfg, model, params, ref = built
    tokens = prompt_of(100, 0)
    got = np.asarray(model.logits(jnp.asarray(tokens)[None]))[0]
    want = ref.sequence_logits(cfg, params, tokens)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_full_prefill_and_decode_through_the_slots(built):
    cfg, model, params, ref = built
    rec = Recorded(model, **engine_kw())
    e = rec.engine
    req = Request(prompt_of(150, 3), 40, tenant="a", request_id=1)
    e.submit(req)
    e.step()
    a = e.allocator
    # the prompt left a snapshot at 64 and at 128: the trie's alone
    assert a.group_stats()["state_retained_slots"] == 2
    assert a.group_stats()["state_used_slots"] == 3
    run_out(e)
    assert len(req.tokens) == 40 and e.prefix_hits == 0
    full, want = assert_served_is_the_reference(built, rec, req, 150)
    control = ref.sequence_logits(cfg, params, full,
                                  precision="fp8")[149:full.size - 1]
    assert np.abs(control - want).max() > 100 * ATOL
    assert a.used_pages == 0 and a.group_stats()["state_used_slots"] == 0


def test_a_hit_restores_a_snapshot_after_its_holder_has_decoded_on(built):
    """The holder's live state is 20 tokens past its prompt when the
    probe arrives; the probe shares 140 tokens, 17 whole pages, and is
    cut back to the snapshot at 128."""
    _, model, _, _ = built
    rec = Recorded(model, **engine_kw())
    e = rec.engine
    holder = Request(prompt_of(150, 3), 60, tenant="a", request_id=1)
    e.submit(holder)
    for _ in range(20):
        e.step()
    probe = Request(np.concatenate([holder.prompt[:140], prompt_of(25, 4)]),
                    30, tenant="a", request_id=2)
    e.submit(probe)
    run_out(e)
    assert e.prefix_hits == 1 and e.prefix_tokens_matched == 128
    assert e.forks == 0
    assert_served_is_the_reference(built, rec, probe, 165)
    assert_served_is_the_reference(built, rec, holder, 150)


def test_a_hit_served_from_a_zero_state_is_another_model(built,
                                                         monkeypatch):
    """What the snapshot is for: the same hit with the restored state
    zeroed differs in the numbers, not only in the slots."""
    from chainermn_tpu.models import hybrid_delta
    _, model, _, _ = built
    scan = hybrid_delta.HybridDeltaLM._scan

    def from_zero(self, mix, x, true_len, state, before):
        if state is not None:
            state, before = jnp.zeros_like(state), jnp.zeros_like(before)
        return scan(self, mix, x, true_len, state, before)
    monkeypatch.setattr(hybrid_delta.HybridDeltaLM, "_scan", from_zero)
    rec = Recorded(model, **engine_kw())
    e = rec.engine
    holder = Request(prompt_of(150, 3), 30, tenant="a", request_id=1)
    probe = Request(np.concatenate([holder.prompt[:140], prompt_of(25, 4)]),
                    10, tenant="a", request_id=2)
    e.submit(holder)
    e.step()
    e.submit(probe)
    run_out(e)
    assert e.prefix_tokens_matched == 128
    with pytest.raises(AssertionError):
        assert_served_is_the_reference(built, rec, probe, 165)


def test_a_chunked_prefill_carries_the_live_slot_and_leaves_snapshots(built):
    """``chunk_tokens=64``: the prompt goes in three chunks through the
    suffix program, each starting from the live slot the last one left;
    the chunks that end on a stride leave the snapshots a later hit
    restores."""
    _, model, _, _ = built
    rec = Recorded(model, **engine_kw(chunk_tokens=64))
    e = rec.engine
    chunk, n = e._chunk_fn, len(e.kv.pools)

    def recorded(*args):        # the last chunk's row is the first token's
        out = chunk(*args)
        rec.rows[rec.admitting] = [np.asarray(out[n])]
        return out
    e._chunk_fn = recorded
    req = Request(prompt_of(150, 3), 30, tenant="a", request_id=1)
    e.submit(req)
    for _ in range(8):
        e.step()
        assert e.allocator.check()
    assert e.chunk_prefills == 3 and len(req.tokens) > 1
    assert e.allocator.group_stats()["state_retained_slots"] == 2
    probe = Request(np.concatenate([req.prompt[:140], prompt_of(25, 4)]),
                    12, tenant="a", request_id=2)
    e.submit(probe)
    run_out(e)
    assert e.prefix_tokens_matched == 128
    assert_served_is_the_reference(built, rec, req, 150)
    assert_served_is_the_reference(built, rec, probe, 165)


def test_an_evicted_sequence_gives_its_slot_back_and_replays(built):
    """A page pool too small for both: the younger sequence is evicted
    (pages and slot given back), re-queued with its tokens folded into
    its prompt, and replayed; every row it was served is still the
    reference's."""
    _, model, _, _ = built
    rec = Recorded(model, **engine_kw(num_pages=40, max_context=192))
    e = rec.engine
    old = Request(prompt_of(120, 5), 60, tenant="a", request_id=1)
    young = Request(prompt_of(100, 6), 80, tenant="b", request_id=2)
    e.submit(old)
    e.submit(young)
    run_out(e)
    assert e.evictions >= 1 and young.preemptions >= 1
    assert_served_is_the_reference(built, rec, old, 120)
    assert_served_is_the_reference(built, rec, young, 100)
    assert e.allocator.group_stats()["state_used_slots"] == 0


@pytest.mark.parametrize("asked,program", [
    (dict(spec_k=2), "verify"), (dict(tp=2), "pool_sharding"),
    (dict(disagg=True), "page_ship")])
def test_a_program_a_recurrent_state_lacks_is_refused_typed(built, asked,
                                                            program):
    _, model, _, _ = built
    with pytest.raises(UnsupportedProgramError) as e:
        ServingEngine(model, **engine_kw(**asked))
    assert e.value.model == "HybridDeltaLM" and e.value.program == program


def test_the_cache_is_pages_for_full_layers_and_slots_for_linear(built):
    _, model, _, _ = built
    assert model.serve_cache_groups() == (
        ("full", 2, ((2 * 4 * 16,),), None),
        ("state", 6, ((8, 4 * 16), (3 * 4 * (8 + 8 + 16),)),
         PerSequence(STRIDE)))
    e = ServingEngine(model, **engine_kw())
    # 4 lanes + 4 prompts x 4 snapshots = 20 -> 24 slots
    assert [p.shape for p in e.kv.pools] == [
        (2, 96, PAGE, 128), (6, 24, 8, 64), (6, 24, 384)]
    assert [p.dtype for p in e.kv.pools] == [jnp.float32] * 3
    assert e.kv.pool_bytes == sum(p.size * 4 for p in e.kv.pools)
    assert e.allocator.states[0].num_slots == 24
    assert e.cache_groups == 2 and not e.allocator.windows


def test_warm_up_writes_no_slot_and_no_page(built):
    _, model, _, _ = built
    e = ServingEngine(model, **engine_kw(max_context=128))
    e.kv.pools = [p + 1.0 for p in e.kv.pools]
    e.warmup()
    assert all(bool((p == 1.0).all()) for p in e.kv.pools)
