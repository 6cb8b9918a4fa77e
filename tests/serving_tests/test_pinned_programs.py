"""The lowered serving programs of every model the benchmark serves,
held by the hash of their StableHLO text, the way
``test_model_owned_block.py`` pinned GPT-2's (PR 27): a prefill, a
suffix prefill and a decode step of ``LatentMoELM``, ``WindowMoELM``,
``PreroutedMoELM``, ``HybridDeltaLM``, ``LoopedLM`` and
``TransformerLM`` at the tiny sizes of their own tests.  A change that
moves one of these programs either meant to, and re-pins it here saying
so, or has leaked into a cell that did not ask.  The text is JAX's, so
the constants hold for the JAX they were made with.

``HybridDeltaLM``, ``LoopedLM`` and ``TransformerLM`` are as they were
before ``sorted_experts_ffn`` existed (PR 44's parent, commit 67d8758).
``LatentMoELM`` and ``WindowMoELM`` were re-pinned by PR 47, which meant
to move them: ``HeldExperts`` groups its products by sorting, one body a
program, where these pins held the masked form.  ``PreroutedMoELM`` was
pinned by PR 47 at ITS parent (commit 39b8980), as the control of that
change: the function it shares with the two is the same program for it.
PR 48 re-pinned the ``_decode`` of ``WindowMoELM`` and ``PreroutedMoELM``
and meant to: the layers of a kind share one lowering of their read of
the cache (``models/window_moe._decode_attention``, the layer's index an
operand), so the program calls one function a layer where it held the
read once a layer; their prefills and the four other models' programs
are as they were (off the TPU the grouped products' schedule, PR 48's
other change, does not exist).
"""

import hashlib
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.models import _init
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import ServingEngine

from ..models_tests import (test_hybrid_delta, test_latent_moe, test_looped,
                            test_prerouted_moe, test_window_moe)

PROGRAMS = ("_prefill", "_prefix_prefill", "_decode")
PINNED = {
    ("LatentMoELM", "_prefill"): "fe12bc532c0d73b1",
    ("LatentMoELM", "_prefix_prefill"): "262d6da88c956ba5",
    ("LatentMoELM", "_decode"): "108a18871580a5cc",
    ("WindowMoELM", "_prefill"): "090ea428633dbd83",
    ("WindowMoELM", "_prefix_prefill"): "84d5b7c7bf6ef11a",
    ("WindowMoELM", "_decode"): "8bf76d9a61b98bab",
    ("PreroutedMoELM", "_prefill"): "64ab7460b1960def",
    ("PreroutedMoELM", "_prefix_prefill"): "970ac01bb1574058",
    ("PreroutedMoELM", "_decode"): "8c59d2538a458fcc",
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
    elif name == "PreroutedMoELM":
        model = _seeded("prerouted_moe_lm", test_prerouted_moe.tiny_config(),
                        128)
    else:
        model = _seeded("looped_lm", test_looped.tiny_config(), 64)
    return ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                         max_context=model.serve_max_context)


@pytest.fixture(scope="module", params=["LatentMoELM", "WindowMoELM",
                                        "PreroutedMoELM", "HybridDeltaLM",
                                        "LoopedLM", "TransformerLM"])
def engine(request):
    return request.param, _engine(request.param)


def lowered_text(e, program):
    """The program's StableHLO text at one prompt bucket (32; a suffix
    of 16) and the four-lane decode."""
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
    return text


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the hashes were made with JAX 0.9.0")
@pytest.mark.parametrize("program", PROGRAMS)
def test_lowered_serving_program_is_the_pinned_one(engine, program):
    name, e = engine
    text = lowered_text(e, program)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PINNED[name, program]


@pytest.mark.parametrize("program", PROGRAMS)
def test_no_product_runs_over_the_stacked_leaves(engine, program):
    """Every routed layer's held experts are three grouped products that
    take ``[H, F, D]`` as it lies (off the chip JAX lowers
    ``ragged_dot_general`` to a product that contracts the group too): no
    leaf is reshaped into one matrix of ``H x F`` rows, the masked
    form's, which computes every held expert for every token."""
    name, e = engine
    routed = [b.experts for b in e.model.blocks if hasattr(b, "experts")]
    if not routed:
        pytest.skip(f"{name} has no routed expert layer")
    leaf = "tensor<{}x{}x{}x".format(*routed[0].w_gate.shape)
    lines = lowered_text(e, program).splitlines()
    # ``HeldExperts`` lowers ONE body a program and calls it a layer
    shared = sum("call @_held_share" in line for line in lines)
    assert shared == (0 if name == "PreroutedMoELM" else len(routed))
    assert sum("stablehlo.dot_general" in line and leaf in line
               for line in lines) == 3 * (1 if shared else len(routed))
    assert not any("stablehlo.reshape" in line and leaf in line
                   for line in lines)


def _functions(text):
    """``{name: its lines}`` of a StableHLO module's functions."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@(\w+)\(", line)
        if m:
            name = m.group(1)
        if name is not None:
            out.setdefault(name, []).append(line)
    return out


def _calls(lines, callee):
    """The calls, in ``lines``, of ``callee`` or of a numbered copy of it
    (JAX names a function's second signature ``<name>_<n>``)."""
    return sum(re.search(rf"call @{callee}(_\d+)?\(", line) is not None
               for line in lines)


@pytest.fixture
def traced_as_on_the_chip(monkeypatch):
    """``_on_tpu()`` answered with True, between two ``clear_caches``: a
    trace is kept by its shapes, not by that answer, so neither may a
    trace made before be found here nor one made here later."""
    jax.clear_caches()
    monkeypatch.setattr(
        importlib.import_module("chainermn_tpu.ops.flash_attention"),
        "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def test_the_chips_decode_program_orders_its_grouped_products_once_a_body(
        engine, traced_as_on_the_chip):
    """The decode program as the chip takes it (the Pallas grouped
    product in, lowered for the TPU from here): an expert layer's three
    products are calls of ``ops.grouped_matmul.gmm``, whose functions
    hold the kernel and nothing else, and their order of visits
    (``group_metadata``, which holds the program's only running sums) is
    made ONCE beside the two sorts, in the layer's body.  ``HeldExperts``
    lowers one body a program and calls it a layer (PR 47);
    ``megablox.gmm`` made the order inside every product's function, so
    twice a body (PR 48)."""
    name, e = engine
    routed = [b.experts for b in e.model.blocks if hasattr(b, "experts")]
    if not routed:
        pytest.skip(f"{name} has no routed expert layer")
    operands = (jnp.zeros(4, jnp.int32), jnp.full(4, -1, jnp.int32),
                jnp.asarray(e._zero_bt(4)))
    fns = _functions(e._decode_fn.trace(e.state, *e.kv.pools, *operands)
                     .lower(lowering_platforms=("tpu",)).as_text())
    if name == "PreroutedMoELM":        # traced a layer, no body of its own
        bodies, body = len(routed), fns["main"]
    else:
        assert [f for f in fns if f.startswith("_held_share")] \
            == ["_held_share"]
        assert _calls(fns["main"], "_held_share") == len(routed)
        bodies, body = 1, fns["_held_share"]
    assert _calls(body, "gmm") == 3 * bodies
    assert _calls(body, "argsort") == 2 * bodies
    assert _calls(body, "group_metadata") == bodies
    assert [f for f, lines in fns.items() if _calls(lines, "cumsum")
            and not f.startswith("cumsum")] == ["group_metadata"]
    kernels = [lines for f, lines in fns.items()
               if re.match(r"gmm(_\d+)?$", f)]
    assert len(kernels) == 2            # gate and up share one, down's
    for lines in kernels:
        assert sum("tpu_custom_call" in line for line in lines) == 1
        assert not any(" call @" in line for line in lines)
    assert sum("tpu_custom_call" in line for lines in fns.values()
               for line in lines) == 2
