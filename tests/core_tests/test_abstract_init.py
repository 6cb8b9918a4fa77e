"""Abstract construction (``core.link.abstract_init``): a link built
inside keeps shapes only; loading fills it; ``cast_params`` holds it in
another dtype leaf by leaf.  Outside it, links draw what they always
drew."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.core.link import (abstract_init, cast_params,
                                     extract_state, load_param_tree)
from chainermn_tpu.nn import links as L


def _links():
    return [L.Linear(6, 4, seed=3), L.EmbedID(5, 3, seed=4), L.RMSNorm(7)]


def test_links_built_abstractly_hold_shapes_and_allocate_nothing():
    with abstract_init():
        lin, emb, norm = _links()
    for link, shapes in ((lin, {"/W": (4, 6), "/b": (4,)}),
                         (emb, {"/W": (5, 3)}), (norm, {"/gamma": (7,)})):
        got = dict(link.namedparams())
        assert {k: p.shape for k, p in got.items()} == shapes
        assert all(p.is_abstract and p.dtype == jnp.float32
                   for p in got.values())
        with pytest.raises(ValueError, match="shapes only"):
            extract_state(link)
    # the flag does not outlive the block
    assert not L.Linear(2, 2).W.is_abstract


def test_loading_fills_an_abstract_link():
    with abstract_init():
        lin = L.Linear(6, 4, seed=3)
    load_param_tree(lin, {"/W": jnp.ones((4, 6)), "/b": jnp.zeros(4)})
    assert not lin.W.is_abstract
    np.testing.assert_array_equal(lin(jnp.ones((2, 6))), 6.0)


def test_concrete_construction_draws_what_it_always_drew():
    lin, emb, norm = _links()
    rng = np.random.RandomState(3)
    np.testing.assert_array_equal(
        lin.W.array, (rng.normal(0, 1, (4, 6)) / np.sqrt(6))
        .astype(np.float32))
    np.testing.assert_array_equal(lin.b.array, 0)
    np.testing.assert_array_equal(
        emb.W.array, np.random.RandomState(4).normal(0, 1, (5, 3))
        .astype(np.float32))
    np.testing.assert_array_equal(norm.gamma.array, 1)


def test_cast_params_casts_floating_leaves_one_at_a_time():
    lin = L.Linear(6, 4, seed=3)
    before = lin.W.array
    cast_params(lin, jnp.bfloat16)
    assert lin.W.dtype == lin.b.dtype == jnp.bfloat16
    np.testing.assert_array_equal(lin.W.array, before.astype(jnp.bfloat16))
    same = lin.W.array
    cast_params(lin, jnp.bfloat16)
    assert lin.W.array is same      # already held so: not copied again


def test_rms_norm_statistics_are_float32_and_the_dtype_is_kept():
    norm = L.RMSNorm(8, eps=1e-5)
    norm.gamma.array = jnp.arange(1.0, 9.0)
    x = np.random.RandomState(0).normal(size=(3, 8)).astype(np.float32)
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.arange(1.0, 9.0)
    np.testing.assert_allclose(norm(jnp.asarray(x)), want, rtol=1e-6)
    low = norm(jnp.asarray(x, jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), want,
                               rtol=2e-2)
