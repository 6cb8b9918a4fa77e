"""Plain reference for GPT-2 (Radford et al. 2019; the published
``config.json`` keys): float32 ``jax.numpy``, every product at
``highest``, no kernel, no cache, no batching tricks.  Imports nothing
of the program.

Departures from the published model, shared with the configuration
file's ``assumed``: the output head is a matrix of its own (not tied to
the token embedding), and there is no dropout.  The GELU is the tanh
form, as in GPT-2's ``gelu_new``.

Parameters arrive as ``{path: array}`` under the names the benchmark's
weight maker uses (``/blocks/<i>/attn/qkv/W`` ...); matrices are stored
``(out, in)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._precision import matmul, operand

_BLOCK_LEAVES = ("ln1/gamma", "ln1/beta", "attn/qkv/W", "attn/qkv/b",
                 "attn/proj/W", "attn/proj/b", "ln2/gamma", "ln2/beta",
                 "fc1/W", "fc1/b", "fc2/W", "fc2/b")


def _layer_norm(x, gamma, beta, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(h, p, n_head, precision):
    """One pre-norm block over ``h`` [T, d]."""
    T, d = h.shape
    x = _layer_norm(h, p["ln1/gamma"], p["ln1/beta"])
    qkv = matmul(x, p["attn/qkv/W"].T, precision) + p["attn/qkv/b"]
    qkv = qkv.reshape(T, 3, n_head, d // n_head)
    q, k, v = (jnp.swapaxes(qkv[:, i], 0, 1) for i in range(3))  # [H,T,D]
    scores = matmul(q, jnp.swapaxes(k, 1, 2), precision) \
        / jnp.sqrt(jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.swapaxes(matmul(probs, v, precision), 0, 1).reshape(T, d)
    h = operand(h + matmul(att, p["attn/proj/W"].T, precision)
                + p["attn/proj/b"], precision)
    x = _layer_norm(h, p["ln2/gamma"], p["ln2/beta"])
    m = _gelu(matmul(x, p["fc1/W"].T, precision) + p["fc1/b"])
    return operand(h + matmul(m, p["fc2/W"].T, precision) + p["fc2/b"],
                   precision)


def _n_layers(params):
    return 1 + max(int(k.split("/")[2]) for k in params
                   if k.startswith("/blocks/"))


def logits_one(params, tokens, n_head, precision="float32"):
    """Logits [T, V] of one sequence of token ids [T]."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    T = tokens.shape[0]
    h = params["/embed/W"][tokens] + params["/pos_embed/W"][:T]
    # the blocks are alike, so they are scanned (one compiled body); each
    # is recomputed in the backward pass, so that a gradient through 24
    # float32 layers fits beside the weights
    stacked = {leaf: jnp.stack([params[f"/blocks/{i}/{leaf}"]
                                for i in range(_n_layers(params))])
               for leaf in _BLOCK_LEAVES}
    block = jax.checkpoint(functools.partial(
        _block, n_head=n_head, precision=precision))
    h, _ = jax.lax.scan(lambda h, p: (block(h, p), None), h, stacked)
    h = _layer_norm(h, params["/ln_f/gamma"], params["/ln_f/beta"])
    return matmul(h, params["/head/W"].T, precision)


def loss_one(params, x, t, n_head, precision="float32"):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits_one(params, x, n_head, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, t[:, None], axis=-1).mean()


@functools.partial(jax.jit, static_argnames=("n_head", "precision"))
def loss_and_grad(params, xs, ts, n_head, precision="float32"):
    """Loss and gradient of the mean loss over the rows of ``xs``
    [B, T], one row at a time so that it fits whatever the batch."""
    def row(carry, xt):
        loss, grad = jax.value_and_grad(loss_one)(
            params, xt[0], xt[1], n_head, precision)
        return (carry[0] + loss,
                jax.tree.map(jnp.add, carry[1], grad)), None
    zero = (jnp.float32(0), jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params))
    (loss, grad), _ = jax.lax.scan(row, zero, (xs, ts))
    n = xs.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grad)


def batch_loss_and_grad(config, params, batch, precision="float32"):
    """The training reference's entry: ``batch`` is ``(x, t)``."""
    x, t = batch
    return loss_and_grad(params, jnp.asarray(x), jnp.asarray(t),
                         n_head=config["n_head"], precision=precision)


@functools.partial(jax.jit, static_argnames=("n_head", "precision"))
def _logits_jit(params, tokens, n_head, precision):
    return logits_one(params, tokens, n_head, precision)


def sequence_logits(config, params, tokens, precision="float32"):
    """The serving reference's entry: logits [T, V] of one whole
    sequence (prompt and served tokens, padded by the caller; the mask
    is causal, so padding behind a position cannot reach it)."""
    return _logits_jit(params, jnp.asarray(tokens),
                       n_head=config["n_head"], precision=precision)
