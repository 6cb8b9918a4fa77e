"""The gated delta rule (ISSUE 33): the chunked form a prompt runs
against the token recurrence it must equal, in float32 on seeded
operands: with an initial state, with ``beta`` above 1 (negative
eigenvalues of the transition), with decays strong enough that
``exp(-G)`` alone would overflow, across lengths that are not chunk
multiples; the pass over chunks as the Pallas kernel in interpret mode;
the snapshots against the recurrence's own states at the strides; and
the decode step over states in the cache's layout."""

import numpy as np
import pytest

import jax.numpy as jnp

from chainermn_tpu.ops import gated_delta as gd

H, DK, DV = 3, 16, 32
TOL = 1e-5


def operands(T, seed=0, decay=1.0, beta_scale=2.0):
    """Seeded (q, k, v, g, beta) as a layer would make them: unit keys,
    queries of norm ``1 / sqrt(dk)``, log decays about ``-decay · 0.05``
    a token, ``beta`` in ``(0, beta_scale)``."""
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.randn(T, H, DK)) / np.sqrt(DK)
    k = unit(rng.randn(T, H, DK))
    v = rng.randn(T, H, DV)
    A = np.exp(rng.uniform(np.log(0.5), np.log(16), H))
    g = -decay * A * np.log1p(np.exp(rng.randn(T, H) - 3))
    beta = beta_scale / (1 + np.exp(-rng.randn(T, H)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def gap(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


@pytest.mark.parametrize("T", [1, 16, 63, 64, 65, 200, 256])
@pytest.mark.parametrize("with_state", [False, True])
def test_chunked_form_is_the_token_recurrence(T, with_state):
    ops = operands(T, seed=T)
    state = jnp.asarray(np.random.RandomState(1).randn(H, DK, DV),
                        jnp.float32) if with_state else None
    want, states = gd.gated_delta_recurrence(*ops, state)
    got, snaps = gd.gated_delta_chunked(*ops, state)
    assert got.shape == (T, H, DV) and snaps.shape == (1, H, DK, DV)
    assert gap(got, want) < TOL
    assert gap(snaps[0], states[-1]) < TOL


def test_beta_above_one_is_in_the_numbers():
    """``linear_allow_neg_eigval``: with the factor 2 half the writes
    overshoot, and the chunked form follows the recurrence there too."""
    ops = operands(192, seed=5)
    assert float((ops[4] > 1).mean()) > 0.3
    want, _ = gd.gated_delta_recurrence(*ops)
    got, _ = gd.gated_delta_chunked(*ops)
    assert gap(got, want) < TOL
    halved, _ = gd.gated_delta_recurrence(*ops[:4], ops[4] / 2)
    assert gap(halved, want) > 1000 * TOL


def test_decays_that_overflow_exp_of_minus_g_stay_finite():
    """A head that forgets all of its state in a few tokens: the running
    sum of ``g`` inside a chunk passes -100, so ``exp(-G)`` is beyond
    float32 and only the masked differences are safe."""
    q, k, v, g, beta = operands(128, seed=2, decay=40.0)
    assert float(jnp.cumsum(g, 0)[63].min()) < -100
    want, _ = gd.gated_delta_recurrence(q, k, v, g, beta)
    got, snaps = gd.gated_delta_chunked(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(snaps)).all()
    assert gap(got, want) < TOL


@pytest.mark.parametrize("T,stride", [(256, 64), (200, 64), (320, 128),
                                      (100, 128)])
@pytest.mark.parametrize("interpret", [False, True])
def test_snapshots_are_the_recurrences_states_at_the_strides(T, stride,
                                                             interpret):
    ops = operands(T, seed=T + stride)
    state = jnp.asarray(np.random.RandomState(3).randn(H, DK, DV),
                        jnp.float32)
    want, states = gd.gated_delta_recurrence(*ops, state)
    got, snaps = gd.gated_delta_chunked(*ops, state, stride=stride,
                                        interpret=interpret)
    n = -(-T // stride)
    assert snaps.shape == (n, H, DK, DV)
    assert gap(got, want) < TOL
    for i in range(n):
        assert gap(snaps[i], states[min((i + 1) * stride, T) - 1]) < TOL


def test_padding_of_zero_g_and_zero_beta_leaves_the_state():
    """What the serving prefills rely on: positions past the prompt
    carry ``g = 0`` and ``beta = 0`` and change nothing, so the state
    after a padded bucket is the state at the prompt's end."""
    T, pad = 90, 38
    q, k, v, g, beta = operands(T + pad, seed=9)
    live = (jnp.arange(T + pad) < T)[:, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    _, states = gd.gated_delta_recurrence(q[:T], k[:T], v[:T], g[:T],
                                          beta[:T])
    _, snaps = gd.gated_delta_chunked(q, k, v, g, beta, stride=64)
    assert gap(snaps[0], states[63]) < TOL
    assert gap(snaps[1], states[T - 1]) < TOL


def test_a_stride_that_is_no_chunk_multiple_is_refused():
    with pytest.raises(ValueError, match="multiple of the chunk"):
        gd.gated_delta_chunked(*operands(128), stride=96)


def test_kernel_in_interpret_mode_is_the_scan_in_bfloat16_operands():
    """bfloat16 operands, float32 state: the kernel and the scan run the
    same body, so they agree to rounding of the last product."""
    ops = tuple(a.astype(jnp.bfloat16) if i < 3 else a
                for i, a in enumerate(operands(192, seed=4)))
    a, sa = gd.gated_delta_chunked(*ops, stride=64)
    b, sb = gd.gated_delta_chunked(*ops, stride=64, interpret=True)
    assert a.dtype == jnp.bfloat16 and sa.dtype == jnp.float32
    assert gap(a.astype(jnp.float32), b.astype(jnp.float32)) < 1e-2
    assert gap(sa, sb) < 1e-2
    want, _ = gd.gated_delta_recurrence(*ops)
    assert gap(a.astype(jnp.float32), want) < 5e-2


@pytest.mark.parametrize("B", [1, 4])
def test_decode_step_over_the_caches_layout_is_one_recurrence_step(B):
    q, k, v, g, beta = operands(B, seed=B)
    rng = np.random.RandomState(8)
    S = jnp.asarray(rng.randn(B, DK, H * DV), jnp.float32)
    o, S_new = gd.gated_delta_step(S, q, k, v, g, beta)
    assert o.shape == (B, H, DV) and S_new.shape == S.shape
    for b in range(B):
        heads = jnp.moveaxis(S[b].reshape(DK, H, DV), 1, 0)
        want, states = gd.gated_delta_recurrence(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], beta[b:b + 1],
            heads)
        assert gap(o[b], want[0]) < TOL
        assert gap(jnp.moveaxis(S_new[b].reshape(DK, H, DV), 1, 0),
                   states[0]) < TOL
