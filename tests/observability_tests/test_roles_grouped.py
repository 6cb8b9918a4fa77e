"""Role coverage (ISSUE 38) of the three served models whose cache has
groups or a latent entry, at the tiny configurations of their cells'
rehearsals: in the text of each serving program compiled on the CPU,
every instruction has a role of the vocabulary."""

import importlib

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
}
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
    assert sorted({n for n, p in zip(names, parsed) if p.role is None}) == []
    assert {p.role for p in parsed} == MODELS[model]
    assert not any(p.backward for p in parsed)
    assert any(p.where.startswith("blocks/1/") for p in parsed)
