"""``sorted_experts_ffn`` (the routed products grouped by sorting) held to
a plain loop over tokens and their chosen experts on the same inputs,
over the routings that break a sort: even, every copy on one expert, an
expert with none, a share that starts past expert 0 and a ``valid``
mask; ``HeldExperts`` (sigmoid routing with a selection bias, then that
function) at the two share cells' ``(held, k, experts)``; the shares'
sum is the whole layer; the router is the softmax over all kept at the
chosen; the Pallas grouped product the TPU takes, interpreted, is the
plain ragged product the CPU takes; and the tiles it is given divide the
cells' matrices.  float32 throughout: the forms add the same terms in
another order, so they agree to rounding (1e-5 on sums of 16-48 terms of
size 1).  (Until PR 47 the reference was ``held_experts_ffn``, the same
contract grouped by masking, which that PR deleted.)"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.parallel import moe
from chainermn_tpu.parallel.moe import (HeldExperts, SortedExperts,
                                        sigmoid_topk_route,
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


def plain_experts_ffn(x, ids, weights, w_gate, w_up, w_down, first,
                      activation=jax.nn.silu, valid=None):
    """The contract, token by token and copy by copy in float32: ``(y,
    counts)``, a token outside ``valid`` neither computed nor counted."""
    x, weights = np.asarray(x, np.float32), np.asarray(weights, np.float32)
    w_gate, w_up, w_down = (np.asarray(a, np.float32)
                            for a in (w_gate, w_up, w_down))
    ids, held = np.asarray(ids), w_gate.shape[0]
    y = np.zeros_like(x)
    counts = np.zeros(held, np.int32)
    for t in range(x.shape[0]):
        if valid is not None and not bool(valid[t]):
            continue
        for j, e in enumerate(ids[t] - first):
            if 0 <= e < held:
                counts[e] += 1
                hidden = np.asarray(activation(jnp.asarray(
                    w_gate[e] @ x[t]))) * (w_up[e] @ x[t])
                y[t] += weights[t, j] * (hidden @ w_down[e])
    return y, counts


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
def test_sorted_with_silu_is_the_plain_loop(case):
    rng = np.random.RandomState(CASES.index(case))
    logits, first, held, valid = _routing(case, rng)
    x = jnp.asarray(rng.normal(size=(T, D)).astype(np.float32))
    w_gate, w_up, w_down = _leaves(rng, held)
    ids, w = softmax_topk_route(logits, K)
    want, want_counts = plain_experts_ffn(x, ids, w, w_gate, w_up, w_down,
                                          first, valid=valid)
    got, counts = jax.jit(functools.partial(
        sorted_experts_ffn, first=first, activation=jax.nn.silu))(
            x, ids, w, w_gate, w_up, w_down, valid=valid)
    assert counts.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)
    # a token outside ``valid`` is not computed at all
    keep = np.ones(T, bool) if valid is None else np.asarray(valid)
    assert not np.asarray(got)[~keep].any()
    assert np.abs(want[keep]).max() > 0.1
    if case == "all_on_one":
        assert int(counts[5]) == T
    if case == "one_expert_idle":
        assert int(counts[7]) == 0
    if case in ("random", "even", "all_on_one", "one_expert_idle"):
        assert int(counts.sum()) == T * K       # nothing dropped


# (held, k, experts) of kimi-k2.6-share and laguna-s-2.1-share, at narrow
# widths; a decode step's tokens, a suffix prefill's, and a padded prompt
SHARES = {"kimi": (12, 8, 384, 2.827), "laguna": (16, 10, 256, 2.5)}


def _share(name, first):
    held, k, n_experts, scale = SHARES[name]
    link = HeldExperts(D, F, n_experts, (first, held), k, routed_scale=scale)
    rng = np.random.RandomState(held)
    # a selection bias that draws tokens onto the share, as a trained
    # one moves them between experts: a tenth of the copies land here
    bias = rng.normal(0, 0.05, n_experts).astype(np.float32)
    bias[first:first + held] += 0.15
    link.router_bias.array = jnp.asarray(bias)
    return link


@pytest.mark.parametrize("tokens, masked", [(16, False), (96, False),
                                            (96, True)],
                         ids=["decode", "suffix", "valid_mask"])
@pytest.mark.parametrize("name, first", [("kimi", 0), ("laguna", 32)])
def test_held_experts_is_the_plain_loop_at_the_share_cells_routing(
        name, first, tokens, masked):
    link = _share(name, first)
    rng = np.random.RandomState(tokens)
    x = jnp.asarray(rng.normal(size=(tokens, D)).astype(np.float32))
    valid = jnp.arange(tokens) < 70 if masked else None
    got, counts = jax.jit(lambda x: link(x, valid=valid))(x)
    ids, w = sigmoid_topk_route(x, link.router.array,
                                link.router_bias.array, link.k,
                                link.routed_scale)
    want, want_counts = plain_experts_ffn(
        x, ids, w, link.w_gate.array, link.w_up.array, link.w_down.array,
        first, valid=valid)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)
    # most copies go to experts held elsewhere, and some land here
    assert 0 < int(counts.sum()) < tokens * link.k // 2
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("name", sorted(SHARES))
def test_a_share_that_receives_no_copy_returns_zeros(name):
    link = _share(name, 0)
    bias = np.zeros(link.n_experts, np.float32)
    bias[:link.count] = -10.0           # nobody chooses a held expert
    link.router_bias.array = jnp.asarray(bias)
    x = jnp.asarray(np.random.RandomState(3).normal(size=(16, D))
                    .astype(np.float32))
    y, counts = jax.jit(link)(x)
    assert not np.asarray(counts).any() and not np.asarray(y).any()
    assert y.shape == x.shape and y.dtype == x.dtype


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
    """``ops.grouped_matmul.gmm`` interpreted against
    ``lax.ragged_dot_general`` on the rows of the groups (the rows past
    them are undefined in the kernel, zeros in the plain form), with an
    empty group in the middle and groups that end inside a tile."""
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
    """2560 x 768 either way round: an output tile is visited once
    (SmallThinker's tiles, as PR 44 swept them; PR 47 made the rule one
    over ``K`` and ``N`` and these are what it must still give)."""
    assert moe._tiles(49152, 2560, 768) == (256, 2560, 768)
    assert moe._tiles(49152, 768, 2560) == (256, 768, 2560)
    assert moe._tiles(192, 2560, 768) == (32, 2560, 768)
    assert moe._tiles(192, 768, 2560) == (32, 768, 2560)
    assert moe._tiles(12, 2560, 768) == (16, 2560, 768)
    assert moe._tiles(120, 32, 16) == (32, 32, 16)


@pytest.mark.parametrize("rows", [8, 128, 512, 4096, 32768, 107520])
@pytest.mark.parametrize("K, N", [(7168, 2048), (2048, 7168),
                                  (3072, 1024), (1024, 3072)])
def test_a_larger_matrix_is_cut_into_tiles_that_divide_it(rows, K, N):
    """Kimi's and Laguna's experts, at a decode step's and a prefill's
    rows: whole lane tiles that divide ``K`` and ``N``, no more elements
    than SmallThinker's whole matrix, and with the rows' and the
    output's tiles inside what its largest call takes."""
    tm, tk, tn = moe._tiles(rows, K, N)
    assert tm == moe._row_tile(rows)
    assert K % tk == 0 and N % tn == 0
    assert tk % 128 == 0 and tn % 128 == 0
    assert 2560 * 768 // 2 < tk * tn <= 2560 * 768
    assert moe._tile_bytes(tm, tk, tn) <= moe._tile_bytes(256, 768, 2560) \
        < 14 * 2 ** 20


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
