"""Plain reference for the ``olmo_hybrid`` block as ONE STAGE of a
pipeline: float32 ``jax.numpy``, every product at ``highest``, no kernel,
no cache, no chunked form: a linear layer is the TOKEN recurrence under
``lax.scan``.  Imports nothing of the program.

The layer equations (``x`` a layer's input row, ``d = hidden_size``;
every norm is ``x / sqrt(mean(x²) + eps) · gain``):

* block, both kinds of layer alike: ``h = x + norm(mixer(x))``, ``out =
  h + norm(W_d(silu(W_g h) ⊙ W_u h))`` (the norm on each sublayer's
  OUTPUT); a final norm, an untied head.
* ``linear_attention`` (``H = linear_num_value_heads`` heads, ``dk =
  linear_key_head_dim``, ``dv = linear_value_head_dim``): ``q~ = x Wq``,
  ``k~ = x Wk`` (``H · dk``), ``v~ = x Wv`` (``H · dv``); on every
  channel of the three a causal convolution over time of
  ``linear_conv_kernel_dim`` taps (``y_t = Σ_j w_j · x_{t - taps + 1 +
  j}``, zeros before the sequence), then SiLU; per head ``q = q' /
  sqrt(Σ q'² + 1e-6) / sqrt(dk)``, ``k`` likewise without the last
  factor; ``beta = 2 · sigmoid(x Wb)`` (``linear_allow_neg_eigval``: the
  factor 2), ``g = -exp(A_log) · softplus(x Wa + dt_bias)``, ``alpha =
  exp(g)``, one each a head.  A head's state ``S`` (``dk x dv``, zeros
  before the sequence): ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} +
  beta_t k_t v_t^T``, ``o_t = S_t^T q_t``.  Output: ``(norm_dv(o) ⊙
  silu(x Wg)) Wo``, the norm over a head's ``dv`` with one gain of
  ``dv``.
* ``full_attention``: ``q = norm(x Wq)``, ``k = norm(x Wk)`` (each norm
  over the WHOLE projection, a gain of its width), ``v = x Wv``, split
  into heads of ``hidden_size / num_attention_heads``; query head ``a``
  reads K/V head ``a // (heads / num_key_value_heads)``; scores ``q_i ·
  k_j / sqrt(D)`` for ``j <= i``, softmax, ``Σ p · v``, then ``Wo``.  No
  rotary position (``rope_theta`` is null).

Computed in blocks so that it fits beside its float32 weights at the
published widths: one compiled layer at a time (the alike layers share
one program), the attention one head and one block of queries at a
time, the SwiGLU and the head in blocks of rows, the head's logits copied
to the host a block at a time (17920 x 100352 float32 are 7.2 GB).

Parameters arrive as ``{path: array}`` under the names the benchmark's
weight maker uses; matrices are stored ``(out, in)``, the convolution's
taps ``(channels, taps)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ._precision import matmul, operand

_MLP = ("ln1/gamma", "ln2/gamma", "mlp/gate/W", "mlp/up/W", "mlp/down/W")
_LINEAR = _MLP + ("mix/q/W", "mix/k/W", "mix/v/W", "mix/a/W", "mix/b/W",
                  "mix/gate/W", "mix/o/W", "mix/norm/gamma", "mix/conv",
                  "mix/A_log", "mix/dt_bias")
_FULL = _MLP + ("mix/q/W", "mix/k/W", "mix/v/W", "mix/o/W",
                "mix/q_norm/gamma", "mix/k_norm/gamma")
_QUERY_BLOCK = 512
_ROW_BLOCKS = 16


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _row_blocks(T):
    return math.gcd(T, _ROW_BLOCKS)


def _mlp(h, p, eps, precision):
    """``h + norm(SwiGLU(h))`` in blocks of rows."""
    def block(x):
        a = matmul(x, p["mlp/gate/W"].T, precision)
        return matmul(a * jax.nn.sigmoid(a)
                      * matmul(x, p["mlp/up/W"].T, precision),
                      p["mlp/down/W"].T, precision)
    n = _row_blocks(h.shape[0])
    y = jax.lax.map(block, h.reshape(n, -1, h.shape[1])).reshape(h.shape)
    return operand(h + _norm(y, p["ln2/gamma"], eps), precision)


def delta_rule(q, k, v, g, beta, state=None):
    """The token recurrence: ``q``, ``k`` ``[T, H, dk]``, ``v`` ``[T, H,
    dv]``, ``g``, ``beta`` ``[T, H]`` -> ``(o [T, H, dv], the last
    state)``."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    hi = jax.lax.Precision.HIGHEST

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        kk = jnp.einsum("hi,hj->hij", k_t, k_t)
        S = jnp.exp(g_t)[:, None, None] * (
            S - b_t[:, None, None] * jnp.einsum("hij,hjv->hiv", kk, S,
                                                precision=hi)) \
            + b_t[:, None, None] * jnp.einsum("hi,hv->hiv", k_t, v_t)
        return S, jnp.einsum("hiv,hi->hv", S, q_t, precision=hi)
    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)
    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _linear_mixer(x, p, shape, precision):
    H, dk, dv, taps, eps = shape
    T = x.shape[0]
    rows = jnp.concatenate(
        [matmul(x, p[f"mix/{n}/W"].T, precision) for n in "qkv"], -1)
    # the causal convolution: zeros before the sequence
    xs = jnp.concatenate([jnp.zeros((taps - 1, rows.shape[1])), rows])
    w = operand(p["mix/conv"], precision)
    y = sum(xs[j:j + T] * w[:, j] for j in range(taps))
    y = operand(y * jax.nn.sigmoid(y), precision)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                            + 1e-6)
    q = unit(y[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = unit(y[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = y[:, 2 * H * dk:].reshape(T, H, dv)
    beta = 2.0 * jax.nn.sigmoid(matmul(x, p["mix/b/W"].T, precision))
    g = -jnp.exp(p["mix/A_log"]) * jax.nn.softplus(
        matmul(x, p["mix/a/W"].T, precision) + p["mix/dt_bias"])
    o, _ = delta_rule(operand(q, precision), operand(k, precision),
                      operand(v, precision), g, beta)
    z = matmul(x, p["mix/gate/W"].T, precision).reshape(T, H, dv)
    y = _norm(o, p["mix/norm/gamma"], eps) * z * jax.nn.sigmoid(z)
    return matmul(y.reshape(T, H * dv), p["mix/o/W"].T, precision)


def _full_mixer(x, p, shape, precision):
    H, G, D, eps = shape
    T = x.shape[0]
    r = H // G
    q = _norm(matmul(x, p["mix/q/W"].T, precision),
              p["mix/q_norm/gamma"], eps).reshape(T, H, D)
    k = _norm(matmul(x, p["mix/k/W"].T, precision),
              p["mix/k_norm/gamma"], eps).reshape(T, G, D)
    v = matmul(x, p["mix/v/W"].T, precision).reshape(T, G, D)
    qb = math.gcd(T, _QUERY_BLOCK)
    kpos = jnp.arange(T)

    def kv_head(_, qkv):
        q_g, k_g, v_g = qkv              # [r, T, D], [T, D], [T, D]

        def block(_, rows):
            q_b, qpos = rows             # [r, qb, D], [qb]
            scores = matmul(q_b, k_g.T, precision) * D ** -0.5
            seen = kpos[None, :] <= qpos[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return None, matmul(probs, v_g, precision)
        _, out = jax.lax.scan(
            block, None,
            (jnp.moveaxis(q_g.reshape(r, T // qb, qb, D), 1, 0),
             kpos.reshape(T // qb, qb)))
        return None, jnp.moveaxis(out, 0, 1).reshape(r, T, D)
    _, out = jax.lax.scan(
        kv_head, None, (jnp.moveaxis(q, 0, 1).reshape(G, r, T, D),
                        jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1)))
    att = jnp.moveaxis(out.reshape(H, T, D), 0, 1)
    return matmul(att.reshape(T, H * D), p["mix/o/W"].T, precision)


@functools.partial(jax.jit, static_argnames=("mixer", "shape", "precision"))
def _layer(h, p, mixer, shape, precision):
    """One block, either kind: ``mixer`` is its mixer's function."""
    eps = shape[-1]
    h = operand(h + _norm(mixer(h, p, shape, precision), p["ln1/gamma"],
                          eps), precision)
    return _mlp(h, p, eps, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, gain, head, eps, precision):
    return matmul(_norm(h, gain, eps), head.T, precision)


def _layer_params(params, i, leaves):
    return {leaf: params[f"/blocks/{i}/{leaf}"] for leaf in leaves}


def hidden_states(config, params, tokens, precision="float32"):
    """The last layer's output ``[T, d]`` for one whole sequence."""
    eps = config["rms_norm_eps"]
    heads = config["num_attention_heads"]
    linear = (config["linear_num_value_heads"],
              config["linear_key_head_dim"],
              config["linear_value_head_dim"],
              config["linear_conv_kernel_dim"], eps)
    full = (heads, config["num_key_value_heads"],
            config["hidden_size"] // heads, eps)
    h = params["/embed/W"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        if config["layer_types"][i] == "linear_attention":
            h = _layer(h, _layer_params(params, i, _LINEAR), _linear_mixer,
                       linear, precision)
        else:
            h = _layer(h, _layer_params(params, i, _FULL), _full_mixer,
                       full, precision)
    return h


def sequence_logits(config, params, tokens, precision="float32"):
    """The serving reference's entry: logits ``[T, V]`` of one whole
    sequence (prompt and served tokens, padded by the caller; every
    layer is causal, so padding behind a position cannot reach it), on
    the host."""
    h = hidden_states(config, params, tokens, precision)
    T = h.shape[0]
    n = _row_blocks(T)
    out = np.empty((T, params["/head/W"].shape[0]), np.float32)
    for rows in np.split(np.arange(T), n):
        out[rows] = np.asarray(_head(
            h[rows[0]:rows[-1] + 1], params["/ln_f/gamma"],
            params["/head/W"], config["rms_norm_eps"], precision))
    return out
