"""The lowered serving programs of every model the benchmark serves,
held to what they were on the tree before ``sorted_experts_ffn`` and
``PreroutedMoELM`` existed (PR 44's parent, commit 67d8758): a prefill,
a suffix prefill and a decode step of ``LatentMoELM``, ``WindowMoELM``,
``HybridDeltaLM``, ``LoopedLM`` and ``TransformerLM`` at the tiny sizes
of their own tests, by the hash of their StableHLO text, the way
``test_model_owned_block.py`` pinned GPT-2's (PR 27).  A grouped product
by sorting is for the model that asks for it: a change that moves one of
these programs either meant to, and re-pins it here saying so, or has
leaked into a cell that did not ask.  The text is JAX's, so the
constants hold for the JAX they were made with.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import ServingEngine

from ..models_tests import (test_hybrid_delta, test_latent_moe, test_looped,
                            test_window_moe)

PROGRAMS = ("_prefill", "_prefix_prefill", "_decode")
PINNED = {
    ("LatentMoELM", "_prefill"): "4a12b55267edb1a1",
    ("LatentMoELM", "_prefix_prefill"): "fa1a383a8d6f3b7a",
    ("LatentMoELM", "_decode"): "e78fca3c09f60251",
    ("WindowMoELM", "_prefill"): "f01770807ccd08c0",
    ("WindowMoELM", "_prefix_prefill"): "3186a5f119d4550b",
    ("WindowMoELM", "_decode"): "b856befc32288bf3",
    ("HybridDeltaLM", "_prefill"): "7fe9aa73e803d3d3",
    ("HybridDeltaLM", "_prefix_prefill"): "a022267e89a4bb08",
    ("HybridDeltaLM", "_decode"): "bfbc412f463c4859",
    ("LoopedLM", "_prefill"): "4207bb67fde430bd",
    ("LoopedLM", "_prefix_prefill"): "bf3ec9503e9e1bd1",
    ("LoopedLM", "_decode"): "8df20fb5de0bbe90",
    ("TransformerLM", "_prefill"): "3a3b88a2bc29815a",
    ("TransformerLM", "_prefix_prefill"): "8edbea865d65105f",
    ("TransformerLM", "_decode"): "e818684fdfdef85a",
}


def _seeded(builder_name, cfg, max_len):
    builder = harness.load_module("models", builder_name)
    model = builder.build(cfg, max_len=max_len)
    _init.load(model, weights.make_params(
        _init.param_spec(model, builder.init_rule), 7))
    return model


def _engine(name):
    if name == "TransformerLM":
        return ServingEngine(
            TransformerLM(n_vocab=97, d_model=32, n_heads=4, n_layers=2,
                          max_len=64, seed=0),
            num_pages=32, page_size=8, max_batch=4, max_context=64)
    if name == "LatentMoELM":
        model = _seeded("latent_moe_lm", test_latent_moe.tiny_config(), 128)
    elif name == "WindowMoELM":
        model = _seeded("window_moe_lm", test_window_moe.tiny_config(), 128)
    elif name == "HybridDeltaLM":
        model = _seeded("hybrid_delta_lm", test_hybrid_delta.tiny_config(),
                        128)
    else:
        model = _seeded("looped_lm", test_looped.tiny_config(), 64)
    return ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                         max_context=model.serve_max_context)


@pytest.fixture(scope="module", params=["LatentMoELM", "WindowMoELM",
                                        "HybridDeltaLM", "LoopedLM",
                                        "TransformerLM"])
def engine(request):
    return request.param, _engine(request.param)


def lowered_hash(e, program):
    """The first 16 hex digits of the program's StableHLO text at one
    prompt bucket (32; a suffix of 16) and the four-lane decode."""
    row = jnp.asarray(e._zero_bt())
    operands = {
        "_prefill": (jnp.zeros((1, 32), jnp.int32), np.int32(0), row),
        "_prefix_prefill": (jnp.zeros((1, 16), jnp.int32), np.int32(0),
                            np.int32(0), row),
        "_decode": (jnp.zeros(4, jnp.int32), jnp.full(4, -1, jnp.int32),
                    jnp.asarray(e._zero_bt(4))),
    }[program]
    text = getattr(e, program + "_fn").lower(
        e.state, *e.kv.pools, *operands).as_text()
    assert f"@jit_{program}" in text     # the name the trace is read by
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the hashes were made with JAX 0.9.0")
@pytest.mark.parametrize("program", PROGRAMS)
def test_lowered_serving_program_is_the_parents(engine, program):
    name, e = engine
    assert lowered_hash(e, program) == PINNED[name, program]
