"""Plain reference for the ``laguna`` block as ONE CHIP'S SHARE of an
expert-parallel group: float32 ``jax.numpy``, every product at
``highest``, no kernel, no cache, the experts as a plain loop over the
held ones.  Imports nothing of the program.

The layer equations (``x`` one token's hidden state; every norm is
``x / sqrt(mean(x²) + eps) · g``; layer ``l`` has ``H_l =
num_attention_heads_per_layer[l]`` query heads over ``G =
num_key_value_heads`` K/V heads of ``D = head_dim``):

* block: ``h += Attn(norm(h))``, then ``h += FFN(norm(h))``; final norm,
  untied head.
* attention: ``q = x W_q`` → ``[H_l, D]``, ``k = x W_k``, ``v = x W_v`` →
  ``[G, D]``, no biases; ``q`` and ``k`` each through a norm over ``D``
  with a learned gain; then rotary positions: on a ``full_attention``
  layer the first ``partial_rotary_factor · D`` dimensions of a head
  with YaRN frequencies (``θ^(-2i/d)`` blended with the same ÷ factor by
  the linear ramp between the correction dimensions of ``beta_fast`` and
  ``beta_slow``), cos and sin times ``attention_factor``; on a
  ``sliding_attention`` layer all of a head with plain ``θ^(-2i/d)``.
  Rotated dimensions pair up as ``(2i, 2i+1)``.  Scores ``q_i · k_j /
  sqrt(D)`` for ``j <= i`` and, on a sliding layer, ``i - j <
  sliding_window``; query head ``a`` reads K/V head ``a // (H_l / G)``;
  softmax; ``Σ p · v``.
* gate (``gating: per-head``): ``g = softplus(x W_g)`` → ``[H_l]``, one
  number a head, times the head's output ahead of ``W_o``.
* FFN: ``mlp_layer_types[l] == "dense"``: ``W_d(silu(W_g x) ⊙ W_u x)``.
  Otherwise ``s = sigmoid(x W_r)`` over ALL experts, the top
  ``num_experts_per_tok`` of ``s`` (plus a selection bias, zeros here),
  weights ``s`` over the chosen, divided by their sum, times
  ``moe_routed_scaling_factor``; ``Σ w_e · down_e(silu(gate_e x) ⊙ up_e
  x) + Shared(x)``.
* the share: only the terms of the held experts (``share.index ·
  num_experts`` onwards, ``num_experts`` of them) are added; the shared
  expert is whole.

Computed in blocks so that it fits beside its float32 weights at the
published widths: one compiled layer at a time (the alike layers share
one program), the attention one K/V head and one block of queries at a
time (48 heads x 10752² scores do not fit whole; a sliding layer's block
over the rows its window reaches alone), the dense layer's width in
slices.

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

_ATTN = ("ln1/gamma", "attn/q/W", "attn/k/W", "attn/v/W", "attn/gate/W",
         "attn/o/W", "attn/q_norm/gamma", "attn/k_norm/gamma", "ln2/gamma")
_DENSE = _ATTN + ("mlp/gate/W", "mlp/up/W", "mlp/down/W")
_ROUTED = _ATTN + ("experts/router", "experts/router_bias",
                   "experts/w_gate", "experts/w_up", "experts/w_down",
                   "shared/gate/W", "shared/up/W", "shared/down/W")
_DENSE_SLICES = 8
_QUERY_BLOCK = 512


def _shape(config, layer):
    """The static numbers layer ``layer`` needs, hashable for ``jit``."""
    sliding = config["layer_types"][layer] == "sliding_attention"
    rope = config["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    window = config["sliding_window"] if sliding else None
    yarn = None if rope["rope_type"] == "default" else (
        rope["factor"], rope["original_max_position_embeddings"],
        rope["beta_fast"], rope["beta_slow"])
    return (config["num_attention_heads_per_layer"][layer],
            config["num_key_value_heads"], config["head_dim"],
            config["rms_norm_eps"], window, rope["rope_theta"],
            rope["partial_rotary_factor"], yarn,
            rope.get("attention_factor", 1.0),
            config["num_experts_per_tok"],
            config["moe_routed_scaling_factor"],
            config["share"]["index"] * config["num_experts"])


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _inv_freq(dim, theta, yarn):
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    if yarn is None:
        return jnp.asarray(plain, jnp.float32)
    factor, original, beta_fast, beta_slow = yarn

    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return jnp.asarray(plain * (1 - ramp) + plain / factor * ramp,
                       jnp.float32)


def _rope(x, inv_freq, rot, factor):
    """``x`` [T, heads, D], position = row: the first ``rot`` dimensions
    of each head rotated in pairs (2i, 2i+1), cos and sin times
    ``factor``."""
    T = x.shape[0]
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq)[:, None]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    even, odd = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _attention(h, p, shape, precision):
    H, G, D, eps, window, theta, partial, yarn, factor = shape[:9]
    T = h.shape[0]
    r = H // G
    rot = int(D * partial)
    inv_freq = _inv_freq(rot, theta, yarn)
    x = _norm(h, p["ln1/gamma"], eps)
    q = _norm(matmul(x, p["attn/q/W"].T, precision).reshape(T, H, D),
              p["attn/q_norm/gamma"], eps)
    k = _norm(matmul(x, p["attn/k/W"].T, precision).reshape(T, G, D),
              p["attn/k_norm/gamma"], eps)
    v = matmul(x, p["attn/v/W"].T, precision).reshape(T, G, D)
    q = _rope(q, inv_freq, rot, factor)
    k = _rope(k, inv_freq, rot, factor)
    qb = math.gcd(T, _QUERY_BLOCK)
    span = qb + (window or 0)
    kpos = jnp.arange(T)

    def kv_head(_, qkv):
        q_g, k_g, v_g = qkv              # [r, T, D], [T, D], [T, D]

        def block(_, rows):
            q_b, qpos = rows             # [r, qb, D], [qb]
            k_b, v_b, pos = k_g, v_g, kpos
            if window is not None and span < T:
                # a sliding layer's block sees no key before its first
                # query's window: take those rows alone
                lo = jnp.clip(qpos[0] - window, 0, T - span)
                k_b = jax.lax.dynamic_slice_in_dim(k_g, lo, span)
                v_b = jax.lax.dynamic_slice_in_dim(v_g, lo, span)
                pos = lo + jnp.arange(span)
            scores = matmul(q_b, k_b.T, precision) * D ** -0.5
            seen = pos[None, :] <= qpos[:, None]
            if window is not None:
                seen &= qpos[:, None] - pos[None, :] < window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return None, matmul(probs, v_b, precision)
        _, out = jax.lax.scan(
            block, None,
            (jnp.moveaxis(q_g.reshape(r, T // qb, qb, D), 1, 0),
             kpos.reshape(T // qb, qb)))
        return None, jnp.moveaxis(out, 0, 1).reshape(r, T, D)
    by_head = (jnp.moveaxis(q, 0, 1).reshape(G, r, T, D),
               jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1))
    _, out = jax.lax.scan(kv_head, None, by_head)         # [G, r, T, D]
    att = jnp.moveaxis(out.reshape(H, T, D), 0, 1)         # [T, H, D]
    att = att * jax.nn.softplus(
        matmul(x, p["attn/gate/W"].T, precision))[..., None]
    return operand(h + matmul(att.reshape(T, H * D), p["attn/o/W"].T,
                              precision), precision)


def _swiglu(x, gate, up, down, precision):
    a = matmul(x, gate.T, precision)
    return matmul(a * jax.nn.sigmoid(a) * matmul(x, up.T, precision),
                  down.T, precision)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _dense_layer(h, p, shape, precision):
    h = _attention(h, p, shape, precision)
    x = _norm(h, p["ln2/gamma"], shape[3])
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
    k, routed_scale, first = shape[9:]
    h = _attention(h, p, shape, precision)
    x = _norm(h, p["ln2/gamma"], shape[3])
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
    h = params["/embed/W"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        shape = _shape(config, i)
        if config["mlp_layer_types"][i] == "dense":
            h = _dense_layer(h, _layer_params(params, i, _DENSE), shape,
                             precision)
        else:
            h = _routed_layer(h, _layer_params(params, i, _ROUTED), shape,
                              precision)
    return _head(h, params["/ln_f/gamma"], params["/head/W"],
                 config["rms_norm_eps"], precision)
