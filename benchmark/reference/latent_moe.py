"""Plain reference for the DeepSeek-V3 block (``model_type`` ``kimi_k2``
too) as ONE CHIP'S SHARE of an expert-parallel group: float32
``jax.numpy``, every product at ``highest``, no kernel, no cache, the
expanded attention only, the experts as a plain loop over the held ones.
Imports nothing of the program.

The layer equations (``x`` one token's hidden state; every norm is
``x / sqrt(mean(x²) + eps) · g``):

* block: ``h += MLA(norm(h))``, then ``h += FFN(norm(h))``; final norm,
  untied head.
* MLA: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` → heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv = norm(c_kv)``, ``k_rope
  = RoPE(k_r)`` (one for all heads); ``[k_nope | v] = c_kv W_kvb`` per
  head; ``scores = (q_nope·k_nope + RoPE(q_rope)·k_rope) · scale``,
  causal softmax, ``Σ p·v`` through ``W_o``.  YaRN as DeepSeek-V3's
  code: ``scale = (nope + rope)^-1/2 · (0.1 · mscale_all_dim ·
  ln(factor) + 1)²``; inverse frequencies blend ``θ^(-2i/d)`` and the
  same ÷ factor by the linear ramp between the correction dimensions of
  ``beta_fast`` and ``beta_slow``.  Rotary dimensions pair up as
  ``(2i, 2i+1)`` (the configuration's ``assumed``).
* routed experts: ``s = sigmoid(x W_g)`` over ALL experts; the ``k``
  chosen are the top ``k`` of ``s + b``; weights ``s`` over the chosen,
  divided by their sum, times ``routed_scaling_factor``; ``FFN(x) = Σ
  w_e · down_e(silu(gate_e x) ⊙ up_e x) + Shared(x)``.  The first
  ``first_k_dense_replace`` layers are one SwiGLU and no router.
* the share: only the terms of the held experts (``share.index ·
  n_routed_experts`` onwards, ``n_routed_experts`` of them) are added;
  the shared expert is whole.

Computed in blocks so that it fits beside its float32 weights at the
published widths: one compiled layer at a time (the alike layers share
one program), the attention one head at a time, the dense layer's width
in slices.

Parameters arrive as ``{path: array}`` under the names the benchmark's
weight maker uses; matrices are stored ``(out, in)``, the experts'
stacked ``[held, out, in]`` (``w_down``: ``[held, in, out]``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ._precision import matmul, operand

_ATTN = ("ln1/gamma", "attn/q_a/W", "attn/q_norm/gamma", "attn/q_b/W",
         "attn/kv_a/W", "attn/kv_norm/gamma", "attn/kv_b/W", "attn/o/W",
         "ln2/gamma")
_DENSE = _ATTN + ("mlp/gate/W", "mlp/up/W", "mlp/down/W")
_ROUTED = _ATTN + ("experts/router", "experts/router_bias",
                   "experts/w_gate", "experts/w_up", "experts/w_down",
                   "shared/gate/W", "shared/up/W", "shared/down/W")
_DENSE_SLICES = 8


def _shape(config):
    """The static numbers a layer needs, hashable for ``jit``."""
    rs = config["rope_scaling"]
    return (config["num_attention_heads"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["rms_norm_eps"],
            config["rope_theta"], rs["factor"],
            rs["original_max_position_embeddings"], rs["beta_fast"],
            rs["beta_slow"], rs["mscale_all_dim"],
            config["num_experts_per_tok"],
            config["routed_scaling_factor"],
            config["share"]["index"] * config["n_routed_experts"])


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return jnp.asarray(plain * (1 - ramp) + plain / factor * ramp,
                       jnp.float32)


def _rope(x, inv_freq):
    """``x`` [T, ..., d], position = row: rotate pairs (2i, 2i+1)."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _attention(h, p, shape, precision):
    (H, rank, nope, rope, vd, eps, theta, factor, original, beta_fast,
     beta_slow, mscale_all, _, _, _) = shape
    T = h.shape[0]
    inv_freq = _inv_freq(rope, theta, factor, original, beta_fast,
                         beta_slow)
    m = 0.1 * mscale_all * math.log(factor) + 1.0 if factor > 1 else 1.0
    scale = (nope + rope) ** -0.5 * m * m
    x = _norm(h, p["ln1/gamma"], eps)
    c_q = _norm(matmul(x, p["attn/q_a/W"].T, precision),
                p["attn/q_norm/gamma"], eps)
    q = matmul(c_q, p["attn/q_b/W"].T, precision).reshape(T, H, nope + rope)
    kv_a = matmul(x, p["attn/kv_a/W"].T, precision)
    c_kv = _norm(kv_a[:, :rank], p["attn/kv_norm/gamma"], eps)
    k_rope = _rope(kv_a[:, rank:], inv_freq)                     # [T, rope]
    kv = matmul(c_kv, p["attn/kv_b/W"].T, precision).reshape(
        T, H, nope + vd)
    q_rope = _rope(q[..., nope:], inv_freq)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(_, qkv):
        q_n, q_r, k_n, v = qkv
        scores = (matmul(q_n, k_n.T, precision)
                  + matmul(q_r, k_rope.T, precision)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return None, matmul(probs, v, precision)
    by_head = [jnp.swapaxes(a, 0, 1) for a in
               (q[..., :nope], q_rope, kv[..., :nope], kv[..., nope:])]
    _, out = jax.lax.scan(head, None, by_head)                # [H, T, vd]
    att = jnp.swapaxes(out, 0, 1).reshape(T, H * vd)
    return operand(h + matmul(att, p["attn/o/W"].T, precision), precision)


def _swiglu(x, gate, up, down, precision):
    a = matmul(x, gate.T, precision)
    return matmul(a * jax.nn.sigmoid(a) * matmul(x, up.T, precision),
                  down.T, precision)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _dense_layer(h, p, shape, precision):
    h = _attention(h, p, shape, precision)
    x = _norm(h, p["ln2/gamma"], shape[5])
    # the width in slices: a SwiGLU is a sum over its hidden units
    width = p["mlp/gate/W"].shape[0]
    step = -(-width // _DENSE_SLICES)
    y = 0.0
    for lo in range(0, width, step):
        sl = slice(lo, lo + step)
        y = y + _swiglu(x, p["mlp/gate/W"][sl], p["mlp/up/W"][sl],
                        p["mlp/down/W"][:, sl], precision)
    return operand(h + y, precision)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _routed_layer(h, p, shape, precision):
    k, routed_scale, first = shape[12:]
    h = _attention(h, p, shape, precision)
    x = _norm(h, p["ln2/gamma"], shape[5])
    s = jax.nn.sigmoid(matmul(x, p["experts/router"].T, precision))
    _, ids = jax.lax.top_k(s + p["experts/router_bias"], k)
    chosen = jnp.take_along_axis(s, ids, -1)
    weights = chosen / chosen.sum(-1, keepdims=True) * routed_scale
    y = _swiglu(x, p["shared/gate/W"], p["shared/up/W"],
                p["shared/down/W"], precision)
    for e in range(p["experts/w_gate"].shape[0]):       # the held experts
        w_e = jnp.where(ids == first + e, weights, 0.0).sum(-1)
        y = y + w_e[:, None] * _swiglu(
            x, p["experts/w_gate"][e], p["experts/w_up"][e],
            p["experts/w_down"][e].T, precision)
    return operand(h + y, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, gain, head, eps, precision):
    return matmul(_norm(h, gain, eps), head.T, precision)


def _layer_params(params, i, leaves):
    return {leaf: params[f"/blocks/{i}/{leaf}"] for leaf in leaves}


def sequence_logits(config, params, tokens, precision="float32"):
    """The serving reference's entry: logits [T, V] of one whole
    sequence (prompt and served tokens, padded by the caller; the mask
    is causal, so padding behind a position cannot reach it)."""
    shape = _shape(config)
    h = params["/embed/W"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        if i < config["first_k_dense_replace"]:
            h = _dense_layer(h, _layer_params(params, i, _DENSE), shape,
                             precision)
        else:
            h = _routed_layer(h, _layer_params(params, i, _ROUTED), shape,
                              precision)
    return _head(h, params["/ln_f/gamma"], params["/head/W"],
                 config["rms_norm_eps"], precision)
