"""Plain reference for the ``smallthinker`` block: float32 ``jax.numpy``,
every product at ``highest``, no kernel, no cache, the experts as a
plain loop over all of them.  Imports nothing of the program.

The layer equations (``h`` one token's residual state; every norm is
``x / sqrt(mean(x²) + eps) · g``; ``H = num_attention_heads`` query
heads over ``G = num_key_value_heads`` K/V heads of ``D = head_dim``,
the same on every layer):

1. ``r = W_r h``: ``moe_num_primary_experts`` logits from the layer's
   input as it arrives, AHEAD of the attention and of ``norm1``.
2. ``h' = h + W_o Attn(norm1(h))``: ``q = W_q x`` → ``[H, D]``, ``k``,
   ``v`` → ``[G, D]``, no bias, no norm on heads, no gate; query head
   ``a`` reads K/V head ``a // (H / G)``; scores ``q_i · k_j / sqrt(D)``
   for ``j <= i`` and, where ``sliding_window_layout[l]`` is 1, ``i - j
   < sliding_window_size``; rotary positions (``rope_theta^(-2i/D)``
   over all ``D`` dimensions, pairs ``(2i, 2i+1)``) where
   ``rope_layout[l]`` is 1 and NO positions where it is 0; softmax; ``Σ
   p · v``.
3. ``h'' = h' + Σ_{e in top-k(r)} p_e · W_down,e (relu(W_gate,e x') ⊙
   W_up,e x')`` with ``x' = norm2(h')``, ``k =
   moe_num_active_primary_experts`` and ``p`` the softmax over the ``k``
   chosen logits (``moe_primary_router_apply_softmax`` with
   ``norm_topk_prob``: a softmax over all, the chosen kept and divided
   by their sum, is the same numbers).  No shared expert, no dense
   layer.
4. Final norm, a head of its own.

Departures from the source: none known.  Assumed, where the config does
not say (the configuration's ``assumed`` has each at length): the
router's input is the UN-NORMED ``h`` (``_layer``'s first line; the
catalog says "router placed before attention" and no more); no biases;
ReLU on the gate; the rotary pairing; the seeded initialisation.

Computed in blocks so that it fits beside 9.5 GB of float32 weights at
the published widths: one compiled layer at a time (the alike layers
share one program), the attention one K/V head and one block of queries
at a time (a window layer's block over the rows its window reaches
alone), and the head in blocks of rows handed back as a host array
(``[8704, 151936]`` float32 is 5.3 GB and does not fit beside the
weights).

Parameters arrive as ``{path: array}`` under the names the benchmark's
weight maker uses; matrices are stored ``(out, in)``, the experts'
stacked ``[experts, out, in]`` (``w_down``: ``[experts, in, out]``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ._precision import matmul, operand

_LEAVES = ("ln1/gamma", "attn/q/W", "attn/k/W", "attn/v/W", "attn/o/W",
           "ln2/gamma", "experts/router", "experts/w_gate", "experts/w_up",
           "experts/w_down")
_QUERY_BLOCK = 512
_HEAD_ROWS = 1088           # 8 blocks of a padded 8704-token sequence


def _shape(config, layer):
    """The static numbers layer ``layer`` needs, hashable for ``jit``:
    ``(H, G, D, eps, window or None, rope_theta or None, k)``."""
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["rms_norm_eps"],
            config["sliding_window_size"]
            if config["sliding_window_layout"][layer] else None,
            config["rope_theta"] if config["rope_layout"][layer] else None,
            config["moe_num_active_primary_experts"])


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _rope(x, theta):
    """``x`` [T, heads, D], position = row: all of each head rotated in
    pairs (2i, 2i+1)."""
    T, _, D = x.shape
    inv_freq = jnp.asarray(theta ** (-np.arange(0, D, 2) / D), jnp.float32)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq)[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(h, p, shape, precision):
    """``W_o Attn(norm1(h))`` for a whole sequence ``h [T, d]``."""
    H, G, D, eps, window, theta = shape[:6]
    T = h.shape[0]
    r = H // G
    x = _norm(h, p["ln1/gamma"], eps)
    q = matmul(x, p["attn/q/W"].T, precision).reshape(T, H, D)
    k = matmul(x, p["attn/k/W"].T, precision).reshape(T, G, D)
    v = matmul(x, p["attn/v/W"].T, precision).reshape(T, G, D)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    qb = math.gcd(T, _QUERY_BLOCK)
    span = qb + (window or 0)
    kpos = jnp.arange(T)

    def kv_head(_, qkv):
        q_g, k_g, v_g = qkv              # [r, T, D], [T, D], [T, D]

        def block(_, rows):
            q_b, qpos = rows             # [r, qb, D], [qb]
            k_b, v_b, pos = k_g, v_g, kpos
            if window is not None and span < T:
                # a window layer's block sees no key before its first
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
    return matmul(att.reshape(T, H * D), p["attn/o/W"].T, precision)


def _expert(x, gate, up, down, precision):
    """``down(relu(gate x) ⊙ up x)``; ``gate``, ``up`` ``[F, d]``,
    ``down`` ``[F, d]`` (in, out)."""
    return matmul(jax.nn.relu(matmul(x, gate.T, precision))
                  * matmul(x, up.T, precision), down, precision)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _layer(h, p, shape, precision):
    eps, k = shape[3], shape[6]
    logits = matmul(h, p["experts/router"].T, precision)  # ahead of norm1
    h = operand(h + _attention(h, p, shape, precision), precision)
    x = _norm(h, p["ln2/gamma"], eps)
    top, ids = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top, -1)

    def add(e, y):                      # every expert, one at a time
        w_e = jnp.where(ids == e, weights, 0.0).sum(-1)
        return y + w_e[:, None] * _expert(
            x, p["experts/w_gate"][e], p["experts/w_up"][e],
            p["experts/w_down"][e], precision)
    y = jax.lax.fori_loop(0, p["experts/w_gate"].shape[0], add,
                          jnp.zeros_like(h))
    return operand(h + y, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, gain, head, eps, precision):
    return matmul(_norm(h, gain, eps), head.T, precision)


def _layer_params(params, i):
    return {leaf: params[f"/blocks/{i}/{leaf}"] for leaf in _LEAVES}


def hidden_states(config, params, tokens, precision="float32"):
    """The last layer's output ``[T, d]`` for one whole sequence."""
    h = params["/embed/W"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        h = _layer(h, _layer_params(params, i), _shape(config, i),
                   precision)
    return h


def sequence_logits(config, params, tokens, precision="float32"):
    """The serving reference's entry: logits ``[T, V]`` of one whole
    sequence (prompt and served tokens, padded by the caller; every
    layer is causal, so padding behind a position cannot reach it), on
    the host."""
    h = hidden_states(config, params, tokens, precision)
    T = h.shape[0]
    step = math.gcd(T, _HEAD_ROWS)
    out = np.empty((T, params["/head/W"].shape[0]), np.float32)
    for lo in range(0, T, step):
        out[lo:lo + step] = np.asarray(_head(
            h[lo:lo + step], params["/ln_f/gamma"], params["/head/W"],
            config["rms_norm_eps"], precision))
    return out
