"""Fused flash-attention backward (ISSUE 4 tentpole).

Interpret-mode (CPU tier-1) coverage:

* grad parity of the fused one-pass dq/dkv kernel vs the
  ``_blockwise_attention_lse_jnp`` reference over a (T, causal,
  tile-shape, dtype) grid — including ragged T where the backward's
  own tiles do not divide and the kernel must fall back to the forward
  tiles;
* backward tile resolution (swept table, adaptive default, explicit
  args).

Ring/Ulysses consumer coverage lives in
tests/parallel_tests/test_long_context.py (the kernels there run under
shard_map via CHAINERMN_TPU_FLASH_INTERPRET=1).
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from .test_flash_attention import _ref_out_lse

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def _data(B=1, H=2, T=128, D=16, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (B, H, T, D))
                             .astype(np.float32)).astype(dtype)
    return mk(), mk(), mk()


def _grads(loss, q, k, v):
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (T, (block_q, block_k)) forward tiles, passed as arguments — 192/160
# are the ragged rows: no default candidate (1024/512/256/128) divides
# them, so the backward exercises its forward-tile fallback branch; the
# 64/128 rows resolve backward tiles through _adaptive_block.
_GRID = [
    (64, (32, 32)),
    (128, (64, 64)),
    (128, (64, 32)),
    (192, (64, 64)),
    (160, (32, 32)),
]


@pytest.mark.parametrize("T,blocks", _GRID)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bwd_grad_parity_vs_blockwise(T, blocks, causal, dtype):
    """Full-grid grad parity: fused backward (interpret mode) vs the
    differentiable blockwise jnp reference, for a loss touching BOTH
    outputs (out and lse — the g_lse→delta folding included)."""
    bq, bk = blocks
    q, k, v = _data(T=T, seed=T + causal, dtype=dtype)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss(out, lse):
        return jnp.sum(out.astype(jnp.float32) ** 2) \
            + jnp.sum(jnp.sin(lse))

    # the custom VJPs take no tiles: the same forward, cotangents and
    # backward they chain, with the grid's tiles as arguments
    out, lse = fa.flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                      block_q=bq, block_k=bk,
                                      interpret=True)
    g, g_lse = jax.grad(loss, argnums=(0, 1))(out, lse)
    gf = fa.flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                scale=scale, block_q=bq, block_k=bk,
                                interpret=True, g_lse=g_lse)
    gr = _grads(lambda q, k, v: loss(*fa._blockwise_attention_lse_jnp(
        q, k, v, causal, scale, block_k=32)), q, k, v)
    if dtype == jnp.float32:
        rtol, atol = 2e-4, 1e-5
    else:
        rtol, atol = 0.1, 0.05
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32),
            np.asarray(b, dtype=np.float32), rtol=rtol, atol=atol,
            err_msg=f"d{name} T={T} blocks={blocks} causal={causal} "
                    f"dtype={dtype.__name__}")


@pytest.mark.parametrize("walk", ["unrolled", "looped"])
@pytest.mark.parametrize("with_g_lse", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(64, 128), (128, 64)])
def test_fused_bwd_with_tiles_under_t(monkeypatch, blocks, causal, dtype,
                                      with_g_lse, walk):
    """All three gradients against autodiff of the plain softmax, with
    backward tiles under T and block_q != block_k in both orders, with
    and without a cotangent on lse, the walk unrolled (dq in values) and
    looped (dq in the VMEM scratch, across the key-tile grid axis)."""
    if walk == "looped":
        monkeypatch.setattr(fa, "_STATIC_WALK_ELEMS", 0)
    q, k, v = _data(T=256, seed=31 + causal, dtype=dtype)
    g = _data(T=256, seed=33, dtype=dtype)[0]
    g_lse = jnp.cos(jnp.arange(2 * 256, dtype=jnp.float32)
                    .reshape(1, 2, 256)) if with_g_lse else None
    out, lse = fa.flash_attention_fwd(q, k, v, causal=causal,
                                      block_q=128, block_k=128,
                                      interpret=True)
    got = fa.flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                 block_q=128, block_k=128, interpret=True,
                                 g_lse=g_lse, bwd_block_q=blocks[0],
                                 bwd_block_k=blocks[1])
    _, vjp = jax.vjp(lambda q, k, v: _ref_out_lse(q, k, v, causal),
                     *(x.astype(jnp.float32) for x in (q, k, v)))
    want = vjp((g.astype(jnp.float32),
                g_lse if with_g_lse else jnp.zeros_like(lse)))
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=0.1, atol=0.05)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), err_msg=name, **tol)


@pytest.mark.parametrize("walk", ["unrolled", "looped"])
def test_fused_bwd_writes_no_partial_dq_plane(monkeypatch, walk):
    """dq leaves the kernel once, in the gradient's dtype: the backward
    is ONE pallas_call whose outputs are dq, dk, dv as ``[B·H, T, D]`` in
    the operands' dtype, no ``[B·H, n_k, Tq, D]`` float32 plane, and
    nothing after the kernel sums anything."""
    if walk == "looped":
        monkeypatch.setattr(fa, "_STATIC_WALK_ELEMS", 0)
    q, k, v = _data(T=256, seed=41, dtype=jnp.bfloat16)
    g = _data(T=256, seed=42, dtype=jnp.bfloat16)[0]
    out, lse = fa.flash_attention_fwd(q, k, v, causal=True, interpret=True)
    jaxpr = jax.make_jaxpr(lambda *a: fa.flash_attention_bwd(
        *a, causal=True, interpret=True, bwd_block_q=64,
        bwd_block_k=64))(q, k, v, out, lse, g).jaxpr

    def flat(jx):
        # the kernel's call is a jit of its own: look through it
        for e in jx.eqns:
            if e.primitive.name == "jit":
                yield from flat(e.params["jaxpr"].jaxpr)
            else:
                yield e

    eqns = list(flat(jaxpr))
    names = [e.primitive.name for e in eqns]
    at = names.index("pallas_call")
    assert names.count("pallas_call") == 1
    call = eqns[at]
    assert call.params["name"] == "_flash_bwd_fused_kernel"
    assert [(o.aval.shape, o.aval.dtype) for o in call.outvars] == \
        [((2, 256, 16), jnp.bfloat16)] * 3
    assert "reduce_sum" not in names[at:]


def test_bwd_block_resolution():
    """Explicit args > swept causal table > adaptive default."""
    def bwd(tq, tk, d=None, causal=False, block_q=None, block_k=None):
        return fa._flash_tiles("bwd", tq, tk, d, causal, block_q, block_k)

    # the lengths no chip sweep has visited: the adaptive default
    for t in (1024, 2048, 8192, 16384):
        assert bwd(t, t) == (1024, 1024)
    # the swept shape: causal, Tq == Tk == 1024, D = 64 (and only it)
    assert bwd(1024, 1024, 64, True) == (256, 256)
    for tq, tk, d, causal in ((1024, 1024, 64, False),
                              (1024, 1024, 128, True),
                              (2048, 2048, 64, True),
                              (1024, 2048, 64, True)):
        assert bwd(tq, tk, d, causal) == (1024, 1024)
    assert bwd(512, 512) == (512, 512)
    assert bwd(192, 192) == (128, 128)
    # explicit args win, one at a time
    assert bwd(8192, 8192, block_q=64) == (64, 1024)
    assert bwd(1024, 1024, 64, True, block_k=512) == (256, 512)


def test_fused_bwd_kernel_count_and_single_exp():
    """Structural pin of the recompute-once property: the backward
    lowers to exactly ONE pallas_call that spends exactly ONE exp a tile
    it walks (its two loop bodies, masked and unmasked, hold one each).
    Uses the same jaxpr census the tier-1 budget gate runs
    (tools/flash_sweep.py) — here pinned against absolute expectations,
    there against the committed tools/flash_budgets.json structure
    section."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools"))
    import flash_sweep

    assert flash_sweep.bwd_kernel_census(fa) == \
        {"_flash_bwd_fused_kernel": {"loop_bodies": 2, "exp_per_tile": 1}}
