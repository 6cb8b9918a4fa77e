"""The rows form of the two training flash kernels: q, k and v read as
column blocks of the qkv GEMM's own ``[B, T, 3·H·D]`` output (two heads
a 128-lane block at D = 64), the result written as ``[B, T, H·D]``.

Interpret mode on the CPU.  A head's arithmetic is the heads-first
form's (same kernels, same tile bodies, the head's lanes alone differ),
so the two forms are held together at the tightest tolerances this
directory uses, for both dtypes; the cotangent is checked for its
``(3, H, D)`` column order against plain autodiff; shapes that cannot be
read as rows resolve to the heads-first path; and the jaxpr of a
``MultiHeadAttention`` step on the rows path holds no ``transpose``, no
``pad`` and one kernel a direction.
"""

import importlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

# tests/ops_tests' tightest: test_flash_bwd_fused.py's float32 pair
TIGHT = dict(rtol=2e-4, atol=1e-5)


def _qkv(B, T, H, D, dtype, seed=0):
    rng = np.random.RandomState(seed)
    qkv = rng.normal(0, 1, (B, T, 3 * H * D)).astype(np.float32)
    w = rng.normal(0, 1, (B, T, H * D)).astype(np.float32)
    return jnp.asarray(qkv).astype(dtype), jnp.asarray(w).astype(dtype)


def _weighted(out, w):
    return jnp.sum((out * w).astype(jnp.float32))


def _rows(qkv, w, H, causal):
    return _weighted(fa._flash_rows_diff(qkv, H, causal, None, True), w)


def _heads_first(qkv, w, H, causal):
    q, k, v = fa.split_heads(qkv, H)
    return _weighted(fa.merge_heads(
        fa._flash_diff(q, k, v, causal, None, True)), w)


def _f32(x):
    return np.asarray(x, dtype=np.float32)


@pytest.mark.parametrize("walk", ["unrolled", "looped"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("H,D", [(4, 64), (2, 128)],
                         ids=["two_heads_a_block", "one_head_a_block"])
def test_rows_form_is_the_heads_first_form(monkeypatch, H, D, causal, dtype,
                                           walk):
    """Forward and all three gradients (the three thirds of the qkv
    cotangent), an odd batch, tiles under T so both walks have several
    tiles to walk."""
    if walk == "looped":
        monkeypatch.setattr(fa, "_STATIC_WALK_ELEMS", 0)
    monkeypatch.setattr(fa, "_BLOCK_CANDIDATES", (64,))
    B, T = 3, 192
    qkv, w = _qkv(B, T, H, D, dtype, seed=H + causal)
    assert fa._rows_heads(qkv, H) == 128 // D
    got, d_got = jax.value_and_grad(_rows)(qkv, w, H, causal)
    want, d_want = jax.value_and_grad(_heads_first)(qkv, w, H, causal)
    assert d_got.shape == (B, T, 3 * H * D) and d_got.dtype == dtype
    np.testing.assert_allclose(_f32(got), _f32(want), **TIGHT)
    for i, name in enumerate(("dq", "dk", "dv")):
        third = slice(i * H * D, (i + 1) * H * D)
        np.testing.assert_allclose(_f32(d_got[..., third]),
                                   _f32(d_want[..., third]), err_msg=name,
                                   **TIGHT)
    # and the outputs themselves, not only their weighted sum (the same
    # kernel in its two forms; `_flash_diff` undifferentiated runs the
    # serving forward, another kernel)
    out = fa._flash_rows_diff(qkv, H, causal, None, True)
    ref, lse = fa.flash_attention_fwd(*fa.split_heads(qkv, H),
                                      causal=causal, interpret=True)
    assert out.shape == (B, T, H * D) and out.dtype == dtype
    np.testing.assert_allclose(_f32(out), _f32(fa.merge_heads(ref)), **TIGHT)
    stat = fa.flash_self_attention_fwd(qkv, H, causal=causal,
                                       interpret=True)[1]
    assert stat.shape == (B, H, T, 1)
    np.testing.assert_allclose(_f32(stat[..., 0]), _f32(lse), **TIGHT)


@pytest.mark.parametrize("causal", [True, False])
def test_rows_cotangent_is_in_3_h_d_order(causal):
    """Against plain autodiff of the jnp reference through the model's
    own split: a gradient in another column order would not match."""
    H, D = 4, 64
    qkv, w = _qkv(2, 128, H, D, jnp.float32, seed=5)

    def reference(qkv):
        q, k, v = fa.split_heads(qkv, H)
        return _weighted(fa.merge_heads(
            fa.xla_attention(q, k, v, causal=causal)), w)

    got = jax.grad(_rows)(qkv, w, H, causal)
    want = jax.grad(reference)(qkv)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("B,T,H,D,why", [
    (2, 128, 4, 48, "a 128-lane block does not hold whole heads"),
    (2, 128, 3, 64, "three heads of 64 do not fill whole blocks"),
    (2, 200, 4, 64, "T does not tile"),
])
def test_shapes_that_cannot_take_rows_resolve_to_the_heads_first_path(
        monkeypatch, B, T, H, D, why):
    monkeypatch.setenv("CHAINERMN_TPU_FLASH_INTERPRET", "1")
    qkv, w = _qkv(B, T, H, D, jnp.float32)
    assert fa._rows_heads(qkv, H) == 0, why
    monkeypatch.setattr(fa, "_flash_rows_diff", None)   # never reached
    got = fa.self_attention(qkv, H, causal=True)
    want = fa.merge_heads(fa.xla_attention(*fa.split_heads(qkv, H),
                                           causal=True))
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-4, atol=2e-5)


def test_off_the_chip_self_attention_is_the_xla_path():
    """No kernel runs on the CPU unless interpret mode is asked for: the
    numerics of every CPU caller stay those of ``xla_attention``."""
    qkv, _ = _qkv(2, 128, 4, 64, jnp.float32)
    got = fa.self_attention(qkv, 4, causal=True)
    want = fa.merge_heads(fa.xla_attention(*fa.split_heads(qkv, 4),
                                           causal=True))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_on_the_chip_a_call_that_cannot_take_rows_says_so_once(monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_WARNED_FALLBACK", set())
    # the heads-first kernels would compile for the chip: stand in
    monkeypatch.setattr(fa, "attention", fa.xla_attention)
    qkv, _ = _qkv(2, 128, 4, 48, jnp.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fa.self_attention(qkv, 4, causal=True)
        fa.self_attention(qkv, 4, causal=True)
    msgs = [str(w.message) for w in caught
            if str(w.message).startswith("flash attention:")]
    assert len(msgs) == 1, msgs
    assert "[2, 128, 576]" in msgs[0] and "rows" in msgs[0]


# -- the call site ------------------------------------------------------------

def _flat(jaxpr):
    """Every equation, looked for inside jits, custom VJPs and the
    like (not inside a kernel's own body)."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _flat(sub)


def _attention_step(attn, state, x):
    from chainermn_tpu.core.link import apply_state

    def loss(params, x):
        out, _ = apply_state(attn, {"params": params, "state": {}}, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)
    return jax.make_jaxpr(jax.value_and_grad(loss))(state["params"], x)


def test_attention_layer_on_the_rows_path_moves_nothing(monkeypatch):
    """``value_and_grad`` over ``MultiHeadAttention.forward``: no
    ``transpose`` of an activation, no ``pad``, one forward and one
    backward kernel, operands and results in the GEMMs' own shapes."""
    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.models.transformer import MultiHeadAttention
    monkeypatch.setenv("CHAINERMN_TPU_FLASH_INTERPRET", "1")
    B, T, H, D = 3, 128, 4, 64
    attn = MultiHeadAttention(H * D, H, seed=0)
    x = jnp.asarray(np.random.RandomState(0).normal(
        0, 1, (B, T, H * D)).astype(np.float32))
    eqns = list(_flat(_attention_step(attn, extract_state(attn), x).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "pad" not in names
    # a Linear's backward transposes its WEIGHT ([in, out] <-> [out,
    # in]); nothing with a batch or a sequence in it is transposed
    moved = [e.invars[0].aval.shape for e in eqns
             if e.primitive.name == "transpose"]
    assert all(len(s) == 2 and B * T not in s for s in moved), moved
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(c.params["name"] for c in calls) == \
        ["_flash_bwd_fused_kernel", "_flash_kernel_lse"]
    by_name = {c.params["name"]: c for c in calls}
    fwd, bwd = by_name["_flash_kernel_lse"], by_name["_flash_bwd_fused_kernel"]
    assert [v.aval.shape for v in fwd.invars] == [(B, T, 3 * H * D)] * 3
    assert [v.aval.shape for v in fwd.outvars] == \
        [(B, T, H * D), (B, H, T, 1)]
    assert [v.aval.shape for v in bwd.invars] == \
        [(B, T, 3 * H * D)] * 3 + [(B, T, H * D)] * 2 + [(B, H, T, 1)]
    assert [v.aval.shape for v in bwd.outvars] == [(B, T, H * D)] * 3


def test_a_bound_sequence_axis_keeps_the_heads_first_path(monkeypatch):
    """Ring and Ulysses exchange KV blocks or heads: with the axis bound
    the layer splits the heads as it always did and never asks for
    rows."""
    from chainermn_tpu import parallel
    from chainermn_tpu.models import transformer
    monkeypatch.setenv("CHAINERMN_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(transformer, "_axis_bound", lambda comm: True)
    monkeypatch.setattr(transformer, "self_attention", None)
    seen = []

    def exchange(comm, q, k, v, causal):
        seen.append((q.shape, k.shape, v.shape))
        return fa.attention(q, k, v, causal=causal)

    monkeypatch.setattr(parallel, "ulysses_attention", exchange)
    B, T, H, D = 2, 128, 4, 64
    attn = transformer.MultiHeadAttention(H * D, H, seed=0, sp_comm=object(),
                                          sp_mode="ulysses")
    out = attn(jnp.ones((B, T, H * D), jnp.float32))
    assert out.shape == (B, T, H * D)
    assert seen == [((B, H, T, D),) * 3]
