"""The update rules the training cells name, written from their
published equations (float32)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def adam_init(params):
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    return {"m": zeros, "v": zeros, "t": 0}


@jax.jit
def _adam(params, grads, m, v, t, alpha, b1, b2, eps):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    def step(p, m, v):
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - alpha * mhat / (jnp.sqrt(vhat) + eps)
    return jax.tree.map(step, params, m, v), m, v


def adam_update(params, grads, state, alpha=1e-3, beta1=0.9, beta2=0.999,
                eps=1e-8):
    """Kingma & Ba 2015, algorithm 1."""
    t = state["t"] + 1
    params, m, v = _adam(params, grads, state["m"], state["v"],
                         jnp.float32(t), alpha, beta1, beta2, eps)
    return params, {"m": m, "v": v, "t": t}


def momentum_init(params):
    return {"v": jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)}


@jax.jit
def _momentum(params, grads, v, lr, momentum):
    v = jax.tree.map(lambda v, g: momentum * v + g, v, grads)
    return jax.tree.map(lambda p, v: p - lr * v, params, v), v


def momentum_update(params, grads, state, lr=0.01, momentum=0.9):
    """Classical momentum: v <- mu v + g; p <- p - lr v."""
    params, v = _momentum(params, grads, state["v"], lr, momentum)
    return params, {"v": v}


OPTIMIZERS = {"adam": (adam_init, adam_update),
              "momentum_sgd": (momentum_init, momentum_update)}
