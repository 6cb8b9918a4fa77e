"""Plain reference for ResNet v1 with bottleneck blocks (He et al. 2015,
arXiv:1512.03385, table 1) as the configuration's ``block_counts`` lay
it out: float32 ``jax.numpy``, every product at ``highest``, batch
normalisation on the batch's own statistics.  Imports nothing of the
program.

Departure from the paper, shared with the configuration's ``assumed``:
a stage's stride-2 convolution is the block's 3x3, not its first 1x1.

Parameters arrive as ``{path: array}`` (``/predictor/res2/0/a/conv/W``
...), kernels stored OIHW, activations NHWC.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._precision import operand

STAGES = ("res2", "res3", "res4", "res5")


def _conv(x, w, stride, pad, precision):
    return operand(jax.lax.conv_general_dilated(
        operand(x, precision), operand(w, precision),
        window_strides=(stride, stride), padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        precision=jax.lax.Precision.HIGHEST), precision)


def _batch_norm(x, gamma, beta, eps, precision):
    mean = x.mean(axis=(0, 1, 2))
    var = jnp.square(x - mean).mean(axis=(0, 1, 2))
    if precision == "float32":
        return (x - mean) / jnp.sqrt(var + eps) * gamma + beta
    # a lower precision holds the normalisation as one scale and one
    # offset a channel (statistics still in float32), as programs that
    # compute in a narrow type do: an error there is the same at every
    # position of a channel and does not average out
    scale = gamma / jnp.sqrt(var + eps)
    return x * operand(scale, precision) \
        + operand(beta - mean * scale, precision)


def _conv_bn(params, path, x, stride, pad, eps, precision, relu=True):
    h = _conv(x, params[path + "/conv/W"], stride, pad, precision)
    h = _batch_norm(h, params[path + "/bn/gamma"], params[path + "/bn/beta"],
                    eps, precision)
    return operand(jax.nn.relu(h) if relu else h, precision)


def _bottleneck(params, path, x, stride, eps, precision):
    h = _conv_bn(params, path + "/a", x, 1, 0, eps, precision)
    h = _conv_bn(params, path + "/b", h, stride, 1, eps, precision)
    h = _conv_bn(params, path + "/c", h, 1, 0, eps, precision, relu=False)
    if path + "/shortcut/conv/W" in params:
        x = _conv_bn(params, path + "/shortcut", x, stride, 0, eps,
                     precision, relu=False)
    return operand(jax.nn.relu(h + x), precision)


def logits(params, images, block_counts, eps, precision="float32"):
    p = "/predictor"
    h = _conv_bn(params, p + "/conv1", images, 2, 3, eps, precision)
    h = jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))
    for s, (stage, n_blocks) in enumerate(zip(STAGES, block_counts)):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            h = _bottleneck(params, f"{p}/{stage}/{b}", h, stride, eps,
                            precision)
    h = h.mean(axis=(1, 2))
    return jnp.matmul(operand(h, precision),
                      operand(params[p + "/fc/W"].T, precision),
                      precision=jax.lax.Precision.HIGHEST) \
        + params[p + "/fc/b"]


def loss(params, images, labels, block_counts, eps, precision="float32"):
    """Mean softmax cross-entropy over the batch."""
    logp = jax.nn.log_softmax(
        logits(params, images, block_counts, eps, precision), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


@functools.partial(jax.jit,
                   static_argnames=("block_counts", "eps", "precision"))
def _loss_and_grad(params, images, labels, block_counts, eps, precision):
    return jax.value_and_grad(loss)(params, images, labels, block_counts,
                                    eps, precision)


def batch_loss_and_grad(config, params, batch, precision="float32"):
    """The training reference's entry: ``batch`` is ``(images, labels)``.
    The whole batch at once: its statistics normalise it."""
    x, t = batch
    return _loss_and_grad(params, jnp.asarray(x), jnp.asarray(t),
                          block_counts=tuple(config["block_counts"]),
                          eps=config["bn_eps"], precision=precision)
