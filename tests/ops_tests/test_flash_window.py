"""Grouped K/V heads and a causal window in the serving forward and in the
paged attention that reads the cache back (ISSUE 31): the band's tile
walk against a brute-force mask, the kernels in interpret mode against
attention with the K/V heads repeated in memory, and the paged functions
against a dense softmax over the same pages."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops.paged_attention import (paged_decode_attention,
                                               paged_prefill_attention)

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def brute(q, k, v, window=None, scale=None):
    """Causal attention with every K/V head repeated for its queries."""
    B, H, T, D = q.shape
    r = H // k.shape[1]
    k, v = jnp.repeat(k, r, axis=1), jnp.repeat(v, r, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (scale or D ** -0.5)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("tq,bq,bk,window", [
    (2048, 256, 256, 512), (1024, 128, 256, 512), (1024, 256, 128, 200),
    (512, 128, 128, 64), (512, 256, 256, 1), (768, 256, 256, 4096)])
def test_band_tile_walk_against_a_brute_force_mask(tq, bq, bk, window):
    i, j = np.arange(tq)[:, None], np.arange(tq)[None, :]
    seen = (j <= i) & (i - j < window)
    walk = fa._causal_tile_walk(tq, tq, bq, bk, window)
    assert len(set((qi, ki) for qi, ki, _ in walk)) == len(walk)
    walked = np.zeros((tq // bq, tq // bk), bool)
    for qi, ki, masked in walk:
        tile = seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
        assert tile.any()              # no tile wholly outside the band
        assert masked == (not tile.all())
        walked[qi, ki] = True
    for qi in range(tq // bq):         # and none of the band left out
        for ki in range(tq // bk):
            if not walked[qi, ki]:
                assert not seen[qi * bq:(qi + 1) * bq,
                                ki * bk:(ki + 1) * bk].any()
    # the kernel's straight-line walk covers each query tile's tiles
    n_band = fa._band_tiles(tq, bq, bk, window)
    for qi in range(tq // bq):
        first, _ = fa._band_k_tiles(qi, bq, bk, window)
        mine = [ki for q, ki, _ in walk if q == qi]
        assert mine == list(range(first, first + len(mine)))
        assert len(mine) <= n_band


def test_a_band_of_512_costs_3_tiles_a_query_tile_at_256():
    walk = fa._causal_tile_walk(10752, 10752, 256, 256, 512)
    assert fa._band_tiles(10752, 256, 256, 512) == 3
    assert len(walk) == 3 * 42 - 3         # the first two tiles' are cut
    causal = fa._causal_tile_walk(10752, 10752, 256, 256)
    assert len(causal) == 42 * 43 // 2


def _qkv(B, H, G, T, D, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(B, G, T, D)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(B, G, T, D)).astype(np.float32)))


@pytest.mark.parametrize("B,H,G,T,window,blocks", [
    (1, 6, 2, 256, None, (64, 64)),      # grouped, causal: _flash_kernel
    (2, 4, 2, 128, None, (64, 32)),      # batch rows pick their own heads
    (1, 6, 2, 256, 96, (64, 64)),        # the window kernel
    (1, 9, 3, 256, 64, (32, 64)),
    (2, 4, 4, 128, 40, (32, 32)),        # a window without groups
    (1, 4, 2, 128, 512, (64, 64)),       # a window wider than the prompt
])
def test_kernels_in_interpret_mode_against_repeated_heads(B, H, G, T,
                                                         window, blocks):
    q, k, v = _qkv(B, H, G, T, 32)
    got = fa.flash_attention(q, k, v, causal=True, block_q=blocks[0],
                             block_k=blocks[1], interpret=True,
                             window=window)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(brute(q, k, v, window)),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        np.asarray(fa.xla_grouped_attention(q, k, v, window=window)),
        np.asarray(brute(q, k, v, window)), atol=2e-5, rtol=0)


def test_the_windowed_form_lowers_under_a_name_of_its_own():
    q, k, v = _qkv(1, 4, 2, 128, 32)

    def names(window):
        return jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, interpret=True, window=window)) \
            .lower(q, k, v).as_text()
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=8)
    # (interpret mode inlines the kernel: the name is the chip's, and
    # tests/test_chip_compile.py finds it in the compiled prefill)
    assert names(None) != names(16)


# -- reading the cache back --------------------------------------------------

S, G, D = 4, 2, 8


def _pools(P, seed=1):
    """``(the pool [L, P, S, 2 * G * D] of a token's K then its V, the K
    half, the V half)``, a token's heads side by side in each."""
    rng = np.random.RandomState(seed)
    k, v = (jnp.asarray(rng.normal(size=(2, P, S, G * D))
                        .astype(np.float32)) for _ in range(2))
    return jnp.concatenate([k, v], -1), k, v


def _dense(q, k, v, seen):
    """``q [H, D]`` over ``k``, ``v`` ``[K, G, D]`` where ``seen [K]``."""
    r = q.shape[0] // G
    k, v = np.repeat(k, r, axis=1), np.repeat(v, r, axis=1)
    s = np.einsum("hd,khd->hk", q, k) * D ** -0.5
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hk,khd->hd", p, v)


@pytest.mark.parametrize("window", [None, 8])
def test_grouped_paged_decode_against_a_dense_softmax(window):
    H, N, B = 6, 10, 3
    kp, k_all, v_all = _pools(32)
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(32)[:B * N].reshape(B, N)
                     .astype(np.int32))
    ctx = jnp.asarray([37, 0, 5], jnp.int32)      # lane 1 idles
    got = np.asarray(paged_decode_attention(
        q, kp, None, bt, ctx, window=window, layer=1, kv_heads=G))
    for b in range(B):
        if ctx[b] == 0:
            assert not got[b].any()
            continue
        k = np.asarray(k_all[1][bt[b]]).reshape(N * S, G, D)
        v = np.asarray(v_all[1][bt[b]]).reshape(N * S, G, D)
        pos = np.arange(N * S)
        seen = pos < int(ctx[b])
        if window is not None:
            seen &= pos > int(ctx[b]) - 1 - window
        np.testing.assert_allclose(got[b], _dense(np.asarray(q[b]), k, v,
                                                  seen), atol=2e-5)
    if window is not None:
        # entries below the window are never read: point them anywhere
        scrambled = bt.at[0, :(37 - 1 - window + 1) // S].set(31)
        again = np.asarray(paged_decode_attention(
            q, kp, None, scrambled, ctx, window=window, layer=1, kv_heads=G))
        np.testing.assert_array_equal(again[0], got[0])


@pytest.mark.parametrize("window,start", [(None, 12), (8, 12), (8, 0),
                                          (8, 24)])
def test_grouped_paged_prefill_against_a_dense_softmax(window, start):
    H, N, T = 6, 10, 8
    kp, k_all, v_all = _pools(16, seed=3)
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.normal(size=(T, H, D)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(16)[:N].astype(np.int32))
    got = np.asarray(paged_prefill_attention(
        q, kp, None, bt, jnp.int32(start), jnp.int32(T), window=window,
        layer=0, kv_heads=G))
    k = np.asarray(k_all[0][bt]).reshape(N * S, G, D)
    v = np.asarray(v_all[0][bt]).reshape(N * S, G, D)
    pos = np.arange(N * S)
    for t in range(T):
        seen = pos <= start + t
        if window is not None:
            seen &= pos > start + t - window
        np.testing.assert_allclose(got[t], _dense(np.asarray(q[t]), k, v,
                                                  seen), atol=2e-5)


def test_suffix_queries_go_in_blocks_under_the_score_budget(monkeypatch):
    from chainermn_tpu.ops import paged_attention as pa
    H, N, T = 6, 10, 32
    kp, _, _ = _pools(16, seed=5)
    q = jnp.asarray(np.random.RandomState(6).normal(size=(T, H, D))
                    .astype(np.float32))
    bt = jnp.arange(N, dtype=jnp.int32)
    args = (q, kp, None, bt, jnp.int32(4), jnp.int32(T))
    whole = np.asarray(paged_prefill_attention(*args, layer=0, kv_heads=G))
    monkeypatch.setattr(pa, "_PREFILL_SCORE_ELEMS", 16 * H * N * S)
    text = jax.jit(lambda *a: paged_prefill_attention(*a, layer=0, kv_heads=G)) \
        .lower(*args).as_text()
    assert "stablehlo.while" in text          # two blocks of 16 queries
    blocks = np.asarray(paged_prefill_attention(*args, layer=0, kv_heads=G))
    np.testing.assert_allclose(blocks, whole, atol=2e-6)


@pytest.mark.parametrize("window", [None, 16])
def test_decode_kernel_in_interpret_mode_against_the_gather_form(window):
    """The Pallas decode step (pages copied in place, chunk by chunk)
    against the XLA form, on the CPU's emulation of the chip's copies:
    lanes of three contexts (one idle, one past a chunk's end), a block
    table in another order than the pool."""
    from jax.experimental.pallas import tpu as pltpu

    from chainermn_tpu.ops import paged_attention as pa
    rng = np.random.RandomState(0)
    L, P, page, heads, groups, d, B, N = 2, 80, 8, 6, 2, 128, 3, 24
    pool = jnp.asarray(rng.normal(size=(L, P, page, 2 * groups * d))
                       .astype(np.float32)).astype(jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, heads, d)).astype(np.float32)) \
        .astype(jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(P)[:B * N].reshape(B, N)
                     .astype(np.int32))
    ctx = jnp.asarray([150, 0, 9], jnp.int32)
    want = pa._grouped_decode(q, pool, bt, ctx, d ** -0.5, window, 1,
                              groups)
    got = pa.paged_decode_kernel(
        q, pool, bt, ctx, kv_heads=groups, layer=1, window=window,
        interpret=pltpu.InterpretParams())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    assert not np.asarray(got[1], np.float32).any()
