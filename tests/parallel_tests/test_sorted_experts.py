"""``sorted_experts_ffn`` (the routed products grouped by sorting) held to
``held_experts_ffn`` (the same contract, grouped by masking) on the same
inputs, over the routings that break a sort: even, every copy on one
expert, an expert with none, a share that starts past expert 0 and a
``valid`` mask; the shares' sum is the whole layer; the router is the
softmax over all kept at the chosen; and the Pallas grouped product the
TPU takes, interpreted, is the plain ragged product the CPU takes.
float32 throughout: the two forms add the same terms in another order,
so they agree to rounding (1e-5 on sums of 16-48 terms of size 1)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.parallel import moe
from chainermn_tpu.parallel.moe import (SortedExperts, held_experts_ffn,
                                        softmax_topk_route,
                                        sorted_experts_ffn)

T, D, F, E, K = 40, 32, 16, 16, 3
ATOL = 1e-5


def _leaves(rng, held):
    def draw(*shape, fan_in):
        return jnp.asarray(rng.normal(0, fan_in ** -0.5, shape)
                           .astype(np.float32))
    return (draw(held, F, D, fan_in=D), draw(held, F, D, fan_in=D),
            draw(held, F, D, fan_in=F))


def _routing(name, rng):
    """``(logits [T, E], first, held, valid)`` of one case."""
    logits = rng.normal(size=(T, E)).astype(np.float32)
    first, held, valid = 0, E, None
    if name == "even":                  # token t: experts t, t+1, t+2
        logits = np.full((T, E), -9.0, np.float32)
        for t in range(T):
            logits[t, (t + np.arange(K)) % E] = [3.0, 2.0, 1.0]
    elif name == "all_on_one":          # expert 5 first for every token
        logits[:, 5] = 50.0
    elif name == "one_expert_idle":
        logits[:, 7] = -50.0
    elif name == "a_share_past_zero":
        first, held = 4, 8
    elif name == "a_valid_mask":
        valid = jnp.asarray(rng.rand(T) < 0.6)
    return jnp.asarray(logits), first, held, valid


CASES = ["random", "even", "all_on_one", "one_expert_idle",
         "a_share_past_zero", "a_valid_mask"]


@pytest.mark.parametrize("case", CASES)
def test_sorted_with_silu_is_the_masked_form(case):
    rng = np.random.RandomState(CASES.index(case))
    logits, first, held, valid = _routing(case, rng)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    w_gate, w_up, w_down = _leaves(rng, held)
    ids, w = softmax_topk_route(logits, K)
    want, want_counts = held_experts_ffn(x, ids, w, w_gate, w_up, w_down,
                                         first, valid=valid)
    got, counts = jax.jit(functools.partial(
        sorted_experts_ffn, first=first, activation=jax.nn.silu))(
            x, ids, w, w_gate, w_up, w_down, valid=valid)
    assert counts.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    keep = np.ones(T, bool) if valid is None else np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[keep],
                               np.asarray(want)[keep], atol=ATOL, rtol=0)
    # a token outside ``valid`` is not computed at all
    assert not np.asarray(got)[~keep].any()
    assert np.abs(np.asarray(want)[keep]).max() > 0.1
    if case == "all_on_one":
        assert int(counts[5]) == T
    if case == "one_expert_idle":
        assert int(counts[7]) == 0
    if case in ("random", "even", "all_on_one", "one_expert_idle"):
        assert int(counts.sum()) == T * K       # nothing dropped


def test_relu_is_another_layer_than_silu():
    rng = np.random.RandomState(1)
    logits, first, held, _ = _routing("random", rng)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    leaves = _leaves(rng, held)
    ids, w = softmax_topk_route(logits, K)
    a, _ = sorted_experts_ffn(x, ids, w, *leaves, 0, jax.nn.relu)
    b, _ = sorted_experts_ffn(x, ids, w, *leaves, 0, jax.nn.silu)
    assert np.abs(np.asarray(a - b)).max() > 1000 * ATOL
    # and it is the plain sum over the chosen
    t = 3
    want = sum(float(w[t, j]) * (
        (jax.nn.relu(leaves[0][e] @ x[t]) * (leaves[1][e] @ x[t]))
        @ leaves[2][e]) for j, e in enumerate(np.asarray(ids[t])))
    np.testing.assert_allclose(np.asarray(a[t]), np.asarray(want),
                               atol=ATOL, rtol=0)


def test_four_shares_of_sixteen_make_the_whole_layer():
    rng = np.random.RandomState(2)
    N = 64
    logits = jnp.asarray(rng.normal(size=(T, N)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    w_gate, w_up, w_down = _leaves(rng, N)
    ids, w = softmax_topk_route(logits, 6)
    whole, counts = sorted_experts_ffn(x, ids, w, w_gate, w_up, w_down, 0,
                                       jax.nn.relu)
    parts, copies = 0.0, []
    for first in range(0, N, 16):
        sl = slice(first, first + 16)
        y, c = sorted_experts_ffn(x, ids, w, w_gate[sl], w_up[sl],
                                  w_down[sl], first, jax.nn.relu)
        parts = parts + y
        copies.append(np.asarray(c))
    np.testing.assert_array_equal(np.concatenate(copies),
                                  np.asarray(counts))
    assert int(counts.sum()) == T * 6
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=ATOL, rtol=0)
    assert np.abs(np.asarray(whole)).max() > 0.1


def test_the_router_is_the_softmax_over_all_kept_at_the_chosen():
    """``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: the
    softmax over all ``E``, the ``k`` largest kept and divided by their
    sum, is the softmax over the ``k`` chosen logits."""
    logits = jnp.asarray(np.random.RandomState(4).normal(size=(T, E))
                         .astype(np.float32)) * 3
    ids, w = softmax_topk_route(logits.astype(jnp.bfloat16), K)
    assert ids.dtype == jnp.int32 and w.dtype == jnp.float32
    logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top, want_ids = jax.lax.top_k(probs, K)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        top / top.sum(-1, keepdims=True)), atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)


@pytest.mark.parametrize("transpose_rhs", [True, False])
def test_the_tpus_grouped_product_interpreted_is_the_plain_one(
        transpose_rhs):
    """``megablox.gmm`` in interpret mode against ``lax.ragged_dot_general``
    on the rows of the groups (the rows past them are undefined in the
    kernel, zeros in the plain form), with an empty group in the middle
    and groups that end inside a tile."""
    rng = np.random.RandomState(6)
    sizes = jnp.asarray([10, 0, 21, 7, 3], jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(64, D)).astype(np.float32))
    shape = (5, F, D) if transpose_rhs else (5, D, F)
    rhs = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    plain = moe._grouped_product(lhs, rhs, sizes, transpose_rhs)
    kernel = moe._grouped_product(lhs, rhs, sizes, transpose_rhs,
                                  interpret=True)
    assert plain.shape == kernel.shape == (64, F)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(kernel)[:n],
                               np.asarray(plain)[:n], atol=ATOL, rtol=0)
    want = np.asarray(lhs[10:31]) @ (np.asarray(rhs[2]).T if transpose_rhs
                                     else np.asarray(rhs[2]))
    np.testing.assert_allclose(np.asarray(plain)[10:31], want, atol=ATOL,
                               rtol=0)


def test_the_whole_layer_through_the_interpreted_kernel(monkeypatch):
    rng = np.random.RandomState(8)
    logits, first, held, valid = _routing("a_valid_mask", rng)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    leaves = _leaves(rng, held)
    ids, w = softmax_topk_route(logits, K)
    want, want_counts = sorted_experts_ffn(x, ids, w, *leaves, 0,
                                           jax.nn.relu, valid=valid)
    monkeypatch.setattr(moe, "_grouped_product", functools.partial(
        moe._grouped_product, interpret=True))
    got, counts = sorted_experts_ffn(x, ids, w, *leaves, 0, jax.nn.relu,
                                     valid=valid)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL, rtol=0)


@pytest.mark.parametrize("rows, tile", [(6, 16), (24, 16), (96, 32),
                                        (192, 32), (49152, 256)])
def test_row_tiles_divide_what_the_model_pads_to(rows, tile):
    assert moe._row_tile(rows) == tile


def test_a_groups_matrix_is_one_tile_at_the_published_widths():
    """2560 x 768 either way round: an output tile is visited once.  A
    larger matrix is cut along its output, in whole lane tiles."""
    assert moe._tiles(49152, 2560, 768) == (256, 2560, 768)
    assert moe._tiles(49152, 768, 2560) == (256, 768, 2560)
    assert moe._tiles(192, 2560, 768) == (32, 2560, 768)
    assert moe._tiles(49152, 7168, 2048) == (256, 2560, 768)
    assert moe._tiles(120, 32, 16) == (32, 32, 16)


def test_the_link_holds_a_router_without_bias_and_refuses_a_bad_share():
    link = SortedExperts(D, F, E, (4, 8), K, jax.nn.relu)
    assert {name: p.shape for name, p in link.namedparams()} == {
        "/router": (E, D), "/w_gate": (8, F, D), "/w_up": (8, F, D),
        "/w_down": (8, F, D)}
    x = jnp.asarray(np.random.RandomState(0).normal(size=(T, D))
                    .astype(np.float32))
    logits = link.logits(x)
    assert logits.shape == (T, E) and logits.dtype == jnp.float32
    y, counts = link(x, logits)
    ids, w = softmax_topk_route(logits, K)
    want, _ = sorted_experts_ffn(x, ids, w, link.w_gate.array,
                                 link.w_up.array, link.w_down.array, 4,
                                 jax.nn.relu)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    assert counts.shape == (8,)
    with pytest.raises(ValueError, match="held"):
        SortedExperts(D, F, E, (12, 8), K, jax.nn.relu)
