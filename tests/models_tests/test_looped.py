"""``LoopedLM`` (the ``ouro`` model: the same blocks applied several
times) against the plain reference, at tiny widths on seeded weights
(width 64, 2 heads of 32, 3 layers, 3 passes, vocabulary 128): the
logits and the exit distribution of the plain full pass; one pass equal
to the same blocks run once with no loop; every pass with keys and
values of its own in the pool; what is refused."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.models import LoopedLM
from chainermn_tpu.models.looped import exit_distribution
from chainermn_tpu.serving import Request, ServingEngine
from chainermn_tpu.serving.errors import UnsupportedProgramError

# float32 against float32 at "highest": what is left is the order of the
# sums (the model's fused q/k/v and softmax against the reference's head
# by head), 3e-6 on logits of size 1 as read here; bfloat16 parameters
# and activations read 0.02-0.05 (the last test), a hundred times over
ATOL = 2e-4
L, R = 3, 3
TINY = dict(hidden_size=64, head_dim=32, num_attention_heads=2,
            num_key_value_heads=2, intermediate_size=96, vocab_size=128,
            num_hidden_layers=L, total_ut_steps=R, param_dtype="float32")


def tiny_config(**over):
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "ouro-2.6b.json"))
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def build(**over):
    """(config, model with seeded weights loaded, params)."""
    cfg = tiny_config(**over)
    builder = harness.load_module("models", "looped_lm")
    model = builder.build(cfg, max_len=64)
    assert all(p.is_abstract for p in model.params())   # nothing drawn
    def rule(path, shape):
        # the builder scales the output norms' gains for the published
        # depth; the tiny model's for its own
        if path.endswith(("ln2/gamma", "ln4/gamma")):
            return ("full", (2 * L) ** -0.5)
        return builder.init_rule(path, shape)
    params = dict(weights.make_params(_init.param_spec(model, rule), 7))
    # the seeded gate bias is 0; the test moves it off zero, so that a
    # dropped bias shows
    params["/gate/b"] = jnp.full((1,), 0.3, jnp.float32)
    _init.load(model, params)
    return cfg, model, params


@pytest.fixture(scope="module")
def built():
    return build() + (harness.load_module("reference", "looped"),)


def test_forward_matches_the_reference(built):
    cfg, model, params, ref = built
    tokens = np.random.RandomState(0).randint(0, 128, (2, 40)) \
        .astype(np.int32)
    logits, exits = model.forward(jnp.asarray(tokens))
    assert logits.shape == (2, 40, 128) and exits.shape == (2, R, 40)
    for b in range(2):
        want, want_exits = ref.sequence_outputs(cfg, params, tokens[b])
        np.testing.assert_allclose(logits[b], want, atol=ATOL, rtol=0)
        # probabilities: the same tolerance on numbers under 1
        np.testing.assert_allclose(exits[b], want_exits, atol=ATOL, rtol=0)
    np.testing.assert_allclose(np.asarray(exits).sum(1), 1.0, atol=1e-6)
    # the gate is live on seeded weights: no pass takes all of it
    assert 0.001 < float(np.asarray(exits).min()) \
        and float(np.asarray(exits).max()) < 0.999


def test_the_reference_is_float32_at_highest_and_shares_no_code():
    path = os.path.join(harness.HERE, "reference", "looped.py")
    with open(path) as f:
        text = f.read()
    assert "chainermn_tpu" not in text.split('"""', 2)[2]
    assert "lax.scan" not in text and "fori_loop" not in text
    from benchmark.reference import _precision
    assert "Precision.HIGHEST" in open(_precision.__file__).read()


@pytest.mark.parametrize("gates, want", [
    ([[0.5], [0.5], [0.5]], [[0.5], [0.25], [0.25]]),
    ([[1.0], [0.3], [0.3]], [[1.0], [0.0], [0.0]]),
    ([[0.0], [0.0], [0.9]], [[0.0], [0.0], [1.0]]),
    ([[0.2]], [[1.0]]),
])
def test_exit_distribution_gives_the_last_pass_what_is_left(gates, want):
    got = exit_distribution(jnp.asarray(gates, jnp.float32))
    np.testing.assert_allclose(got, want, atol=1e-7)


def test_one_pass_is_the_blocks_run_once_with_no_loop():
    """``R = 1``: the loop's body once.  The same blocks applied by hand
    (embedding, each block over the prompt itself, the final norm, the
    head) give the same logits to rounding."""
    cfg, model, params = build(total_ut_steps=1)
    assert model.passes == 1
    tokens = np.random.RandomState(1).randint(0, 128, 24).astype(np.int32)
    logits, exits = model.forward(jnp.asarray(tokens)[None])
    h = model.embed(jnp.asarray(tokens))
    cos, sin = model._angles(jnp.arange(24))
    for block in model.blocks:
        q, k, v = block.project(h, cos, sin)
        h = block.residual(h, model._prompt_attention(q, k, v))
    by_hand = model.head(model.ln_f(h))
    np.testing.assert_allclose(logits[0], by_hand, atol=1e-5, rtol=0)
    np.testing.assert_allclose(exits, 1.0)
    # and three passes are not one: the loop does something
    _, looped, _ = build()
    assert float(jnp.abs(looped.forward(jnp.asarray(tokens)[None])[0][0]
                         - by_hand).max()) > 0.1


def test_passes_do_not_share_cache(built):
    """After a prefill the pool holds ``R x L`` layers and layer ``r·L +
    l`` differs between passes: each pass kept its own keys and
    values."""
    _, model, _, _ = built
    engine = ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                           max_context=64)
    assert len(model.blocks) == L
    (pool,) = engine.kv.pools
    assert pool.shape == (R * L, 16, 8, 2 * 2 * 32)
    prompt = np.random.RandomState(2).randint(0, 128, 19).astype(np.int32)
    engine.submit(Request(prompt, 10, request_id=1))
    engine.step()
    (pool,) = engine.kv.pools
    table = engine.allocator.block_table(1)
    written = np.asarray(pool[:, np.asarray(table)]).reshape(R * L, -1, 128)
    written = written[:, :19]
    for c in range(R * L):
        assert np.abs(written[c]).max() > 0.1      # every layer written
    for l in range(L):
        for r in range(1, R):
            gap = np.abs(written[r * L + l] - written[l]).max()
            assert gap > 0.05, (r, l, gap)


def test_a_threshold_under_one_is_refused():
    with pytest.raises(UnsupportedProgramError, match="early_exit"):
        LoopedLM(128, 64, 2, 2, 2, 32, 96, 3, exit_threshold=0.9)


@pytest.mark.parametrize("option, program", [
    (dict(spec_k=2), "verify"), (dict(tp=2), "pool_sharding")])
def test_the_engine_refuses_what_the_looped_cache_does_not_have(
        built, option, program):
    _, model, _, _ = built
    with pytest.raises(UnsupportedProgramError, match=program):
        ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                      max_context=64, **option)


def test_bfloat16_where_float32_is_stated_would_fail_the_tolerance(built):
    """The tolerance can tell: the same weights held and computed in
    bfloat16 miss the float32 reference by a hundred times ATOL."""
    cfg, _, params, ref = built
    _, model, _ = build(param_dtype="bfloat16")
    from chainermn_tpu.core.link import cast_params
    cast_params(model, jnp.bfloat16)
    tokens = np.random.RandomState(0).randint(0, 128, 40).astype(np.int32)
    logits = model.forward(jnp.asarray(tokens)[None])[0][0]
    want, _ = ref.sequence_outputs(cfg, params, tokens)
    gap = float(jnp.abs(logits.astype(jnp.float32) - want).max())
    assert gap > 20 * ATOL, gap
