"""Role coverage (ISSUE 38) of the five served models whose cache has
groups or a latent entry, at the tiny configurations of their cells'
rehearsals: in the text of each serving program compiled on the CPU,
every instruction has a role of the vocabulary."""

import importlib
import re

import pytest

from benchmark import device_scopes
from benchmark.drivers import serve

from .. import _programs

MODELS = {
    # rehearsal module, roles every program of the model must show
    "latent": {"embed", "norm", "attn_proj", "cache_write", "attn", "mlp",
               "router", "experts", "head"},
    "window": {"embed", "norm", "attn_proj", "cache_write", "attn", "mlp",
               "router", "experts", "head"},
    "hybrid": {"embed", "norm", "attn_proj", "cache_write", "attn", "state",
               "mlp", "head"},
    # its programs are a device loop over passes: the body's operations
    # keep their roles inside ``loop/while/body``
    "looped": {"embed", "norm", "attn_proj", "cache_write", "attn", "mlp",
               "head"},
    # no dense feed-forward anywhere: every layer is routed experts, and
    # the router's product is a block's first operation
    "prerouted": {"embed", "norm", "attn_proj", "cache_write", "attn",
                  "router", "experts", "head"},
}
# The engine's token pick (``argmax`` under ``~head``) starts its reduction
# from a scalar ``-inf`` that XLA names by the scope alone.  In the other
# models' entry computations it merges with a masked score's ``-inf`` and
# takes that one's name; the looped decode program keeps every other
# ``-inf`` inside the loop's body, so there the scalar keeps its own.
LONE_CONSTANTS = {("looped", "_decode"): ["jit(_decode)/~head"]}
_texts = {}


def _serving_texts(model):
    if model not in _texts:
        rehearsal = importlib.import_module(
            f"tests.benchmark_tests.test_rehearsal_serve_{model}")
        _texts[model] = _programs.serving_texts(
            serve.Program(rehearsal.tiny_run()))
    return _texts[model]


@pytest.mark.parametrize("program", ["_prefill", "_prefix_prefill",
                                     "_decode"])
@pytest.mark.parametrize("model", list(MODELS))
def test_every_instruction_of_a_serving_program_has_a_role(model, program):
    names = _programs.op_names(_serving_texts(model)[program])
    assert len(names) > 500
    parsed = [device_scopes.parse(n) for n in names]
    no_role = sorted({n for n, p in zip(names, parsed) if p.role is None})
    assert no_role == LONE_CONSTANTS.get((model, program), [])
    assert {p.role for p in parsed if p.role} == MODELS[model]
    assert not any(p.backward for p in parsed)
    if model == "looped":
        # the blocks are the body of the loop over passes
        assert any(p.where.startswith("loop/") and "/while/body/" in p.where
                   and "/blocks/1/" in p.where for p in parsed)
    else:
        assert any(p.where.startswith("blocks/1/") for p in parsed)


@pytest.mark.parametrize("model, program", list(LONE_CONSTANTS))
def test_a_name_without_a_role_is_a_scalar_constant_alone(model, program):
    """No device operation: the one instruction that carries the name is
    the scalar a reduction starts from."""
    text = _serving_texts(model)[program]
    for name in LONE_CONSTANTS[model, program]:
        lines = [ln for ln in text.splitlines()
                 if f'op_name="{name}"' in ln]
        assert len(lines) == 1
        assert re.match(r"\s*%?[\w.]+ = f32\[\] constant\(-inf\)", lines[0])
