"""Flash-attention kernel vs XLA reference (interpreter mode on CPU)."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import flash_attention, xla_attention


def _data(B=2, H=2, T=128, D=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (B, H, T, D))
                             .astype(np.float32))
    return mk(), mk(), mk()


def test_flash_matches_xla():
    q, k, v = _data()
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_causal_matches_xla():
    q, k, v = _data(seed=1)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_irregular_shapes_fall_back():
    q, k, v = _data(T=100, seed=2)  # not divisible by blocks
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_backward_kernels_match_xla_grads():
    """Pallas flash backward (dq/dk/dv kernels) vs XLA autodiff, causal
    and non-causal, all three gradients."""
    import jax
    from chainermn_tpu.ops.flash_attention import _flash_diff
    for causal in (False, True):
        q, k, v = _data(T=128, D=32, seed=3 + causal)

        def loss_flash(q, k, v):
            return jnp.sum(_flash_diff(q, k, v, causal, None, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                err_msg=f"d{name} causal={causal}")


def test_flash_fwd_lse_matches_softmax_normalizer():
    from chainermn_tpu.ops.flash_attention import flash_attention_fwd
    q, k, v = _data(T=64, D=16, seed=5)
    out, lse = flash_attention_fwd(q, k, v, causal=False, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) \
        / np.sqrt(q.shape[-1])
    lse_ref = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), lse_ref, rtol=1e-4,
                               atol=1e-4)


def test_flash_vjp_irregular_shape_fallback():
    import jax
    from chainermn_tpu.ops.flash_attention import _flash_diff
    q, k, v = _data(T=100, seed=6)  # not block-divisible → XLA both ways
    g = jax.grad(lambda q: jnp.sum(_flash_diff(q, k, v, True, None,
                                               True) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(
        xla_attention(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-4, atol=2e-5)


def test_attention_with_lse_matches_reference():
    """(out, lse) primitive: both dispatch paths agree with the XLA
    reference; lse is the true softmax normalizer."""
    from chainermn_tpu.ops.flash_attention import (
        attention_with_lse, _blockwise_attention_lse_jnp, _flash_lse_diff,
        xla_attention)
    q, k, v = _data(B=1, H=2, T=128, D=32, seed=11)
    for causal in (False, True):
        ref = xla_attention(q, k, v, causal=causal)
        out_j, lse_j = _blockwise_attention_lse_jnp(q, k, v, causal,
                                                    1.0 / np.sqrt(32),
                                                    block_k=32)
        np.testing.assert_allclose(np.asarray(out_j), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        out_f, lse_f = _flash_lse_diff(q, k, v, causal, 1.0 / np.sqrt(32),
                                       True)  # interpret mode
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse_f), np.asarray(lse_j),
                                   rtol=1e-4, atol=1e-5)


def test_flash_lse_cotangent_grads_match_jnp():
    """The g_lse -> delta - g_lse folding in the backward kernels: grads
    of a function of BOTH outputs (out, lse) must match the blockwise jnp
    path (ring attention's merge weights depend on lse)."""
    from chainermn_tpu.ops.flash_attention import (
        _blockwise_attention_lse_jnp, _flash_lse_diff)
    q, k, v = _data(B=1, H=2, T=128, D=32, seed=12)
    scale = 1.0 / np.sqrt(32)

    def loss_flash(q, k, v):
        out, lse = _flash_lse_diff(q, k, v, True, scale, True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_jnp(q, k, v):
        out, lse = _blockwise_attention_lse_jnp(q, k, v, True, scale,
                                                block_k=32)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gj = jax.grad(loss_jnp, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_blockwise_jnp_irregular_length_stays_blockwise():
    """Tk not divisible by the block: padding + masking, not a full-width
    block (the full-width fallback would materialize [Tq, Tk])."""
    from chainermn_tpu.ops.flash_attention import (
        _blockwise_attention_lse_jnp, xla_attention)
    q, k, v = _data(B=1, H=2, T=64, D=16, seed=13)
    k, v = k[:, :, :56], v[:, :, :56]  # Tk=56, block 32 -> pad to 64
    out, _ = _blockwise_attention_lse_jnp(q, k, v, False, 0.25, block_k=32)
    ref = xla_attention(q, k, v, scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # and the jaxpr contains no [Tq, Tk_pad]-wide intermediate beyond block
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: _blockwise_attention_lse_jnp(q, k, v, False, 0.25,
                                                     block_k=32))(q, k, v)
    shapes = []
    def walk(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                shapes.append(getattr(var.aval, "shape", ()))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
    walk(jaxpr.jaxpr)
    assert not any(len(s) >= 2 and s[-1] > 32 and s[-2] == 64
                   for s in shapes), shapes


def test_flash_bf16_matches_fp32_reference():
    """bf16 storage dtype: kernel keeps bf16 into the MXU dots with fp32
    accumulators/softmax — output must track the fp32 reference within
    bf16 rounding, and gradients must flow."""
    q, k, v = _data(T=128, D=32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(qb, kb, vb, causal=True, block_q=64, block_k=64,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.02)

    from chainermn_tpu.ops.flash_attention import _flash_diff

    def loss(q, k, v):
        return _flash_diff(q, k, v, True, None, True).astype(
            jnp.float32).sum()

    def loss_ref(q, k, v):
        return xla_attention(q, k, v, causal=True).sum()

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(qb, kb, vb)
    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, r in ((gq, rq), (gk, rk), (gv, rv)):
        np.testing.assert_allclose(np.asarray(g, dtype=np.float32),
                                   np.asarray(r), rtol=0.1, atol=0.05)


def _ref_out_lse(q, k, v, causal):
    """(out, lse) in float32 with the [Tq, Tk] scores spelled out."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v), lse


@pytest.mark.parametrize("walk", ["unrolled", "looped"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(64, 128), (128, 64), (64, 64)])
def test_lse_forward_with_tiles_under_t(monkeypatch, blocks, causal, dtype,
                                        walk):
    """The log-sum-exp forward with tiles under T, block_q != block_k in
    both orders: out and lse against the plain softmax, with the walk
    unrolled in one program a head and looped over one query tile a
    program (what a long sequence gets)."""
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    if walk == "looped":
        monkeypatch.setattr(fa, "_STATIC_WALK_ELEMS", 0)
    q, k, v = (x.astype(dtype) for x in _data(B=1, T=256, D=16, seed=21))
    assert fa._static_walk(256, 256, *blocks, causal) == (walk == "unrolled")
    out, lse = fa.flash_attention_fwd(q, k, v, causal=causal,
                                      block_q=blocks[0], block_k=blocks[1],
                                      interpret=True)
    ref, ref_lse = _ref_out_lse(q, k, v, causal)
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=0.05, atol=0.02)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-4, atol=1e-4 if dtype == jnp.float32
                               else 0.02)


@pytest.mark.parametrize("t,bq,bk", [
    (1024, 256, 256), (1024, 128, 512), (1024, 512, 128), (1024, 1024, 1024),
    (256, 64, 128), (256, 128, 64), (384, 128, 128), (512, 512, 128)])
def test_causal_tile_walk_is_the_lower_triangle(t, bq, bk):
    """The walk against a brute-force mask: no tile wholly above the
    diagonal is computed, every other tile is, and a tile is masked
    exactly where the diagonal crosses it; the backward's bounds list
    the same tiles."""
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    visible = np.tril(np.ones((t, t), bool))
    want = {}
    for qi in range(t // bq):
        for ki in range(t // bk):
            tile = visible[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            if tile.any():
                want[(qi, ki)] = not tile.all()
    walk = fa._causal_tile_walk(t, t, bq, bk)
    assert len(walk) == len(set(walk))
    assert {(qi, ki): masked for qi, ki, masked in walk} == want
    seen_from_keys = {}
    for ki in range(t // bk):
        first, full = fa._causal_q_tiles(ki, bq, bk, t // bq)
        for qi in range(first, t // bq):
            seen_from_keys[(qi, ki)] = qi < full
    assert seen_from_keys == want


def test_committed_tiles_skip_the_masked_half():
    """GPT-2-medium's training shape: the committed tiles compute at most
    0.65 of the square (1024 x 1024 tiles computed all of it), and
    the head's walk is unrolled."""
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    for leg, blocks in fa._CAUSAL_BLOCK_TABLE[(1024, 64)].items():
        walk = fa._causal_tile_walk(1024, 1024, *blocks)
        share = len(walk) * blocks[0] * blocks[1] / 1024 ** 2
        assert share <= 0.65, (leg, blocks, share)
        assert fa._static_walk(1024, 1024, *blocks, True), leg
    assert len(fa._causal_tile_walk(1024, 1024, 1024, 1024)) == 1


@pytest.mark.parametrize("t,want", [(64, 128), (128, 128), (256, 256),
                                    (512, 512), (1024, 1024), (4096, 1024)])
def test_serving_forward_resolves_the_parents_tiles(monkeypatch, t, want):
    """`flash_attention` (serving prefills: GPT-2 buckets of 64-512 at
    D = 64, Kimi at 4096) resolves what it did before the training
    kernels got a swept table: the largest candidate that divides T,
    whatever D or causal (it is shown neither)."""
    from chainermn_tpu.ops.flash_attention import _flash_blocks
    monkeypatch.delenv("CHAINERMN_TPU_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("CHAINERMN_TPU_FLASH_BLOCK_K", raising=False)
    assert _flash_blocks(tq=t, tk=t) == (want, want)


def test_adaptive_block_defaults():
    """Tile defaults are shape-adaptive (largest candidate dividing T),
    explicit args win."""
    from chainermn_tpu.ops.flash_attention import _adaptive_block, \
        _flash_blocks

    assert _adaptive_block(8192) == 1024
    assert _adaptive_block(1024) == 1024
    assert _adaptive_block(1536) == 512   # 1536 % 1024 != 0
    assert _adaptive_block(384) == 128
    assert _adaptive_block(64) == 128     # legacy clamp path (min(b, T))
    assert _adaptive_block(None) == 128   # no shape info: legacy default
    assert _flash_blocks(tq=2048, tk=8192) == (1024, 1024)
    assert _flash_blocks(256, None, tq=2048, tk=1536) == (256, 512)
    # the log-sum-exp forward: the swept table where the call's shape was
    # swept (causal, Tq == Tk == 1024, D = 64), else the same default
    from chainermn_tpu.ops.flash_attention import _CAUSAL_BLOCK_TABLE, \
        _flash_tiles
    assert _CAUSAL_BLOCK_TABLE == {
        (1024, 64): {"fwd": (256, 256), "bwd": (256, 256)}}
    assert _flash_tiles("fwd", 1024, 1024, 64, True) == (256, 256)
    for tq, tk, d, causal in ((1024, 1024, 64, False),
                              (1024, 1024, 128, True),
                              (1024, 2048, 64, True),
                              (2048, 2048, 64, True)):
        assert _flash_tiles("fwd", tq, tk, d, causal) == (1024, 1024)
    assert _flash_tiles("fwd", 1024, 1024, 64, True, block_q=512) \
        == (512, 256)


def _traced_kernels(fa):
    """[(kernel name, grid, dot output shapes)] of the causal
    [1, 2, 1024, 64] bfloat16 forward + backward through the custom VJP,
    traced and not run: the tiles show as the score products' shapes."""
    def loss(q, k, v):
        return jnp.sum(fa._flash_diff(q, k, v, True, None, True)
                       .astype(jnp.float32))

    x = jax.ShapeDtypeStruct((1, 2, 1024, 64), jnp.bfloat16)
    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            subs = [getattr(p, "jaxpr", p) for v in e.params.values()
                    for p in (v if isinstance(v, (tuple, list)) else (v,))]
            subs = [j for j in subs if hasattr(j, "eqns")]
            if e.primitive.name == "pallas_call":
                dots = sorted({tuple(o.aval.shape) for k in subs
                               for ke in k.eqns
                               if ke.primitive.name == "dot_general"
                               for o in ke.outvars})
                found.append((e.params["name"],
                              tuple(e.params["grid_mapping"].grid), dots))
            else:
                for j in subs:
                    walk(j)

    walk(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        x, x, x).jaxpr)
    return found


_RETIRED_FLASH_NAMES = ("CHAINERMN_TPU_FLASH_BLOCK_Q",
                        "CHAINERMN_TPU_FLASH_BLOCK_K",
                        "CHAINERMN_TPU_FLASH_BWD_BLOCK_Q",
                        "CHAINERMN_TPU_FLASH_BWD_BLOCK_K",
                        "CHAINERMN_TPU_FLASH_BWD")


@pytest.mark.parametrize("name", _RETIRED_FLASH_NAMES)
def test_tiles_ignore_the_environment(monkeypatch, name):
    """The tile names and the backward switch PR 29 retired are not
    read: set (the switch before the module is loaded again, since it
    used to be read at import), the tiles resolved and the kernels
    traced are those of a clean environment, with the fused kernel the
    only backward."""
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    for retired in _RETIRED_FLASH_NAMES:
        monkeypatch.delenv(retired, raising=False)
    clean = _traced_kernels(fa)
    assert [k[0] for k in clean] == ["_flash_kernel_lse",
                                    "_flash_bwd_fused_kernel"]
    # 256 x 256 tiles, unrolled in one program a head
    assert all(grid == (2, 1) and (256, 256) in dots
               for _, grid, dots in clean)
    monkeypatch.setenv(
        name, "split" if name == "CHAINERMN_TPU_FLASH_BWD" else "64")
    fa = importlib.reload(fa)
    assert fa._flash_tiles("fwd", 1024, 1024, 64, True) == (256, 256)
    assert fa._flash_tiles("bwd", 1024, 1024, 64, True) == (256, 256)
    assert fa._flash_blocks(tq=1024, tk=1024) == (1024, 1024)
    assert _traced_kernels(fa) == clean
