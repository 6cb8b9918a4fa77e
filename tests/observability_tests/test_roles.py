"""Names on the device (ISSUE 38): the role vocabulary, ``Link.__call__``
putting a link's name on the path, and ROLE COVERAGE: in the text of
the program compiled on the CPU, every instruction of a serving program,
and every instruction under ``mn_forward_backward`` of a training step,
has a role of the vocabulary.  The three grouped models' serving programs
are in ``test_roles_grouped.py``."""

import re

import pytest

import jax
import jax.numpy as jnp

import chainermn_tpu as ct
from chainermn_tpu import observability
from chainermn_tpu.core.link import Chain, Link
from chainermn_tpu.models import MoETransformerLM, TransformerLM
from chainermn_tpu.nn import links as L
from chainermn_tpu.utils import profiling

from benchmark import device_scopes
from benchmark.drivers import serve

from .. import _programs
from ..benchmark_tests import _tiny


def without_role(names):
    return sorted({n for n in names if device_scopes.parse(n).role is None})


def test_the_vocabulary_is_one_tuple_and_the_benchmark_keeps_a_copy():
    assert observability.ROLES == (
        "embed", "norm", "attn_proj", "cache_write", "attn", "state", "mlp",
        "router", "experts", "head", "loss")
    # the benchmark's files also run over the parent's checkout, which
    # has no vocabulary to import: theirs is a copy, held equal here
    assert device_scopes.ROLES == observability.ROLES
    assert device_scopes.MARK == observability.ROLE_MARK
    with pytest.raises(ValueError, match="unknown role"):
        observability.role("attention")
    assert profiling.annotate("x").__class__ \
        is jax.named_scope("x").__class__


def _op_names(fn, *args):
    return re.findall(r'op_name="([^"]*)"',
                      jax.jit(fn).lower(*args).compile().as_text())


def test_a_role_is_on_the_path_of_what_is_traced_under_it():
    def f(x):
        with observability.role("mlp"):
            return jnp.sin(x) * 2

    @observability.role("attn")
    def g(x):
        return jnp.cos(x) + 1
    assert "jit(f)/~mlp/sin" in _op_names(f, jnp.ones(4))
    # as a decorator, at every call
    for _ in range(2):
        assert "jit(g)/~attn/cos" in _op_names(g, jnp.ones(4))


def test_the_mark_is_not_an_at_sign_because_xla_cuts_the_name_there():
    def f(x):
        with jax.named_scope("@mlp"):
            return jnp.sin(x) * 2
    assert "jit(f)/" in _op_names(f, jnp.ones(4))
    assert not any("mlp" in n for n in _op_names(f, jnp.ones(4)))


def test_link_call_puts_the_links_name_on_the_path():
    class Pair(Chain):
        def __init__(self):
            super().__init__()
            with self.init_scope():
                self.inner = L.Linear(4, 4)

        def forward(self, x):
            return self.inner(x)

    class Bare(Link):
        def forward(self, x):
            return jnp.tanh(x)

    pair, bare = Pair(), Bare()
    assert pair.name is None and pair.inner.name == "inner"
    names = _op_names(lambda x: bare(pair(x)), jnp.ones((2, 4)))
    assert any(n.startswith("jit(<lambda>)/inner/") for n in names)
    # an unnamed link adds nothing
    assert "jit(<lambda>)/tanh" in names
    # and a link's name is never read as a role
    for n in names:
        assert device_scopes.parse(n).role is None


def test_a_whole_chain_call_reads_blocks_index_link():
    lm = TransformerLM(64, d_model=32, n_heads=2, n_layers=2, max_len=32)
    names = _op_names(lambda x: lm.logits(x), jnp.zeros((2, 16), jnp.int32))
    assert "jit(<lambda>)/blocks/1/~attn_proj/attn/qkv/dot_general" in names
    assert "jit(<lambda>)/blocks/0/~mlp/fc1/dot_general" in names
    assert "jit(<lambda>)/~head/head/dot_general" in names
    assert without_role(n for n in names if n.startswith("jit(")) == []


@pytest.mark.parametrize("program", ["_prefill", "_prefix_prefill",
                                     "_decode"])
def test_every_instruction_of_gpt2s_serving_programs_has_a_role(
        program, gpt2_serving_texts):
    names = _programs.op_names(gpt2_serving_texts[program])
    assert len(names) > 100
    assert without_role(names) == []
    paths = {device_scopes.parse(n).where for n in names}
    assert any(p.startswith("blocks/1/") for p in paths)
    roles = {device_scopes.parse(n).role for n in names}
    assert {"embed", "norm", "attn_proj", "cache_write", "attn", "mlp",
            "head"} <= roles


@pytest.fixture(scope="module")
def gpt2_serving_texts():
    return _programs.serving_texts(
        serve.Program(_tiny.tiny_run("gpt2m-serve-chat")))


def _lm(comm):
    return TransformerLM(64, d_model=32, n_heads=2, n_layers=2, max_len=32,
                         compute_dtype=jnp.bfloat16)


def _lm_remat(comm):
    return TransformerLM(64, d_model=32, n_heads=2, n_layers=2, max_len=32,
                         remat="dots")


def _moe(comm):
    return MoETransformerLM(64, comm, d_model=32, n_heads=2, n_layers=2,
                            max_len=32)


@pytest.mark.parametrize("build,roles", [
    (_lm, {"embed", "norm", "attn_proj", "attn", "mlp", "head", "loss"}),
    (_lm_remat, {"embed", "norm", "attn_proj", "attn", "mlp", "head",
                 "loss"}),
    (_moe, {"embed", "norm", "attn_proj", "attn", "router", "experts",
            "head", "loss"}),
])
def test_every_instruction_of_the_training_steps_passes_has_a_role(
        build, roles):
    comm = ct.create_communicator("jax_ici", devices=jax.devices()[:1])
    text = _programs.step_text(build(comm), comm,
                               jnp.zeros((2, 16), jnp.int32))
    names = _programs.op_names(text)
    passes = [n for n in names if "/mn_forward_backward/" in n]
    assert len(passes) > 500
    assert without_role(passes) == []
    parsed = [device_scopes.parse(n) for n in passes]
    assert {p.role for p in parsed} == roles
    # both directions of every role that has parameters or activations
    for role in roles - {"loss"}:
        assert {p.backward for p in parsed if p.role == role} \
            == {False, True}, role
    # the update is under its own phase and carries no role
    update = [device_scopes.parse(n) for n in names
              if "/mn_optimizer_update/" in n]
    assert update and all(p.role is None and p.scoped
                          and p.phase == "mn_optimizer_update"
                          for p in update)
