"""Plain reference for the ``ouro`` model: a decoder whose layers are
applied ``total_ut_steps`` times in sequence with the same parameters.
Float32 ``jax.numpy``, every product at ``highest``, two nested Python
loops (passes, then layers), no cache, no scan, no kernel.  Imports
nothing of the program.

The equations (``x`` token ids, ``d = hidden_size``, ``H =
num_attention_heads`` heads of ``D = head_dim`` over ``G =
num_key_value_heads`` K/V heads, ``R = total_ut_steps``, ``L =
num_hidden_layers``; every norm is ``x / sqrt(mean(x²) + eps) · gain``):

* ``h = E[x]`` (no position added, no scaling);
* for pass ``r = 0 .. R-1``, for layer ``l = 0 .. L-1``, the same
  parameters in every pass: ``a = norm_1(h)``; ``q = a Wq``, ``k = a
  Wk``, ``v = a Wv`` (no bias), split into heads; rotary positions over
  all ``D`` dimensions of q and k in the pairs ``(i, i + D/2)``: ``x' =
  x · cos + rotate_half(x) · sin``, ``rotate_half(x) = (-x[D/2:],
  x[:D/2])``, angle ``t · rope_theta^(-2i/D)``; query head ``a`` reads K/V
  head ``a // (H / G)``; scores ``q_i · k_j / sqrt(D)`` for ``j <= i``,
  softmax, ``Σ p · v`` over the keys and values OF THIS PASS; ``h = h +
  norm_2(concat(heads) Wo)``; ``m = norm_3(h)``; ``h = h + norm_4(W_down
  (silu(W_gate m) ⊙ W_up m))``;
* after layer ``L-1`` of EVERY pass: ``h = norm_f(h)`` (its output is
  the next pass's input) and the exit gate ``g_r = sigmoid(h w_g +
  b_g)``;
* the exit distribution ``p_r = g_r · Π_{j<r}(1 - g_j)`` for ``r < R-1``,
  ``p_{R-1} = Π_{j<R-1}(1 - g_j)``; with ``early_exit_threshold`` 1 no
  token leaves early: ``logits = h^(R-1) W_head``.

One compiled layer (all ``R · L`` applications share it), the attention a
K/V head at a time.  Parameters arrive as ``{path: array}`` under the
names the benchmark's weight maker uses; matrices are stored ``(out,
in)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._precision import matmul, operand

_LEAVES = ("ln1/gamma", "ln2/gamma", "ln3/gamma", "ln4/gamma", "attn/q/W",
           "attn/k/W", "attn/v/W", "attn/o/W", "mlp/gate/W", "mlp/up/W",
           "mlp/down/W")


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _rotary(x, theta):
    """``x [T, heads, D]`` at positions ``0 .. T-1``."""
    T, _, D = x.shape
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + half * sin


def _attention(a, p, shape, precision):
    H, G, D, theta, _ = shape
    T = a.shape[0]
    r = H // G
    q = _rotary(matmul(a, p["attn/q/W"].T, precision).reshape(T, H, D),
                theta)
    k = _rotary(matmul(a, p["attn/k/W"].T, precision).reshape(T, G, D),
                theta)
    v = matmul(a, p["attn/v/W"].T, precision).reshape(T, G, D)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    heads = []
    for g in range(G):
        for j in range(r):
            scores = matmul(q[:, g * r + j], k[:, g].T, precision) \
                * D ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            heads.append(matmul(probs, v[:, g], precision))
    return matmul(jnp.concatenate(heads, -1), p["attn/o/W"].T, precision)


@functools.partial(jax.jit, static_argnames=("shape", "precision"))
def _layer(h, p, shape, precision):
    eps = shape[-1]
    a = operand(_norm(h, p["ln1/gamma"], eps), precision)
    h = operand(h + _norm(_attention(a, p, shape, precision),
                          p["ln2/gamma"], eps), precision)
    m = operand(_norm(h, p["ln3/gamma"], eps), precision)
    up = matmul(m, p["mlp/gate/W"].T, precision)
    y = matmul(up * jax.nn.sigmoid(up)
               * matmul(m, p["mlp/up/W"].T, precision),
               p["mlp/down/W"].T, precision)
    return operand(h + _norm(y, p["ln4/gamma"], eps), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _close(h, gain, gate_w, gate_b, eps, precision):
    """The end of a pass: the final norm and the exit gate."""
    h = operand(_norm(h, gain, eps), precision)
    return h, jax.nn.sigmoid(matmul(h, gate_w.T, precision)[:, 0] + gate_b[0])


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(h, head, precision):
    return matmul(h, head.T, precision)


def exit_distribution(gates):
    """``gates``: the ``R`` passes' ``[T]`` exit gates -> ``[R, T]``."""
    out, left = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        out.append(g * left)
        left = left * (1.0 - g)
    return jnp.stack(out + [left])


def sequence_outputs(config, params, tokens, precision="float32"):
    """``(logits [T, V], exit distribution [R, T])`` of one whole
    sequence, on the device."""
    heads = config["num_attention_heads"]
    shape = (heads, config["num_key_value_heads"],
             config["head_dim"], float(config["rope_theta"]),
             config["rms_norm_eps"])
    h = params["/embed/W"][jnp.asarray(tokens)].astype(jnp.float32)
    gates = []
    for _ in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            h = _layer(h, {leaf: params[f"/blocks/{i}/{leaf}"]
                           for leaf in _LEAVES}, shape, precision)
        h, g = _close(h, params["/ln_f/gamma"], params["/gate/W"],
                      params["/gate/b"], config["rms_norm_eps"], precision)
        gates.append(g)
    return _head(h, params["/head/W"], precision), exit_distribution(gates)


def sequence_logits(config, params, tokens, precision="float32"):
    """The serving reference's entry: logits ``[T, V]`` of one whole
    sequence (prompt and served tokens, padded by the caller; every
    layer is causal, so padding behind a position cannot reach it), on
    the host."""
    return np.asarray(sequence_outputs(config, params, tokens, precision)[0])
