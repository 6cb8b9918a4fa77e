"""Built-in links (consumed-Chainer surface: ``chainer.links``).

Reference anchors: ``chainer/links/connection/linear.py · Linear``,
``convolution_2d.py · Convolution2D``, ``deconvolution_2d.py ·
Deconvolution2D``, ``normalization/batch_normalization.py ·
BatchNormalization``, ``connection/embed_id.py · EmbedID``,
``connection/lstm.py · LSTM`` (SURVEY.md §2.8).

Parameters are initialized eagerly on host (numpy RNG for reproducibility)
and live as ``jax.Array`` leaves; every ``forward`` is a pure ``jnp``
program, so links compose under ``jax.jit`` / ``jax.grad`` via
``core.link.apply_state``.  BatchNormalization's running statistics are
*persistent* state threaded functionally through compiled steps.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.link import Chain, Link, Parameter
from ..core.config import config
from . import functions as F
from . import initializers as I

__all__ = ["Linear", "Convolution2D", "Deconvolution2D",
           "DepthwiseConvolution2D", "BatchNormalization",
           "LayerNormalization", "RMSNorm", "EmbedID", "LSTM",
           "StatelessLSTM", "GroupNormalization", "StatelessGRU", "GRU", "NStepLSTM",
           "NStepGRU", "Highway", "Maxout", "Scale", "Classifier"]

_default_rng = np.random.RandomState(817)


def _rng(seed=None):
    return _default_rng if seed is None else np.random.RandomState(seed)


class Linear(Link):
    """Fully-connected layer, weight shape (out, in) like the reference."""

    def __init__(self, in_size, out_size=None, nobias=False,
                 initialW=None, initial_bias=None, seed=None):
        super().__init__()
        if out_size is None:
            in_size, out_size = None, in_size
        self.in_size = in_size
        self.out_size = out_size
        self.nobias = nobias
        self._initW = I._get_initializer(initialW, I.LeCunNormal())
        self._initb = I._get_initializer(initial_bias, I.Zero())
        self._seed = seed
        with self.init_scope():
            self.W = Parameter()
            if not nobias:
                self.b = Parameter()
        if in_size is not None:
            self._init_params(in_size)

    def _init_params(self, in_size):
        rng = _rng(self._seed)
        self.in_size = in_size
        shape = (self.out_size, in_size)
        self.W.draw(shape, np.float32,
                    lambda: self._initW(shape, np.float32, rng))
        if not self.nobias:
            self.b.draw((self.out_size,), np.float32, lambda: self._initb(
                (self.out_size,), np.float32, rng))

    def forward(self, x, n_batch_axes=1):
        if self.W.array is None:
            in_size = int(np.prod(x.shape[n_batch_axes:]))
            self._init_params(in_size)
        W, b = self.W.array, None if self.nobias else self.b.array
        if x.dtype in (jnp.bfloat16, jnp.float16) and W.dtype != x.dtype:
            # mixed precision convention: parameters stored fp32, compute
            # follows the activation dtype (bf16 matmuls on the MXU)
            W = W.astype(x.dtype)
            b = None if b is None else b.astype(x.dtype)
        return F.linear(x, W, b, n_batch_axes=n_batch_axes)


class Convolution2D(Link):
    """2-D convolution, kernel (out, in, kh, kw) regardless of layout.

    ``layout`` selects the ACTIVATION layout: "NCHW" (reference default)
    or "NHWC" (TPU-native channels-last — see F.convolution_2d).  Kernel
    storage stays OIHW either way, so checkpoints are layout-portable.
    """

    def __init__(self, in_channels, out_channels=None, ksize=None, stride=1,
                 pad=0, nobias=False, initialW=None, initial_bias=None,
                 dilate=1, groups=1, seed=None, layout="NCHW"):
        super().__init__()
        if ksize is None:
            # Chainer-style remap: Convolution2D(out_channels, ksize)
            in_channels, out_channels, ksize = None, in_channels, out_channels
        self.in_channels = in_channels
        self.layout = layout
        self.out_channels = out_channels
        self.ksize = ksize
        self.stride = stride
        self.pad = pad
        self.dilate = dilate
        self.groups = groups
        self.nobias = nobias
        self._initW = I._get_initializer(initialW, I.HeNormal())
        self._initb = I._get_initializer(initial_bias, I.Zero())
        self._seed = seed
        with self.init_scope():
            self.W = Parameter()
            if not nobias:
                self.b = Parameter()
        if in_channels is not None:
            self._init_params(in_channels)

    def _init_params(self, in_channels):
        rng = _rng(self._seed)
        kh, kw = (self.ksize, self.ksize) if np.isscalar(self.ksize) else self.ksize
        self.in_channels = in_channels
        shape = (self.out_channels, in_channels // self.groups, kh, kw)
        self.W.array = jnp.asarray(self._initW(shape, np.float32, rng))
        if not self.nobias:
            self.b.array = jnp.asarray(self._initb((self.out_channels,), np.float32, rng))

    def forward(self, x):
        if self.W.array is None:
            self._init_params(x.shape[3] if self.layout == "NHWC"
                              else x.shape[1])
        return F.convolution_2d(x, self.W.array,
                                None if self.nobias else self.b.array,
                                self.stride, self.pad, self.dilate,
                                self.groups, layout=self.layout)


class Deconvolution2D(Link):
    """Transposed convolution, kernel (in, out, kh, kw) like the reference."""

    def __init__(self, in_channels, out_channels, ksize, stride=1, pad=0,
                 nobias=False, outsize=None, initialW=None, initial_bias=None,
                 seed=None):
        super().__init__()
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.ksize = ksize
        self.stride = stride
        self.pad = pad
        self.outsize = outsize
        self.nobias = nobias
        rng = _rng(seed)
        kh, kw = (ksize, ksize) if np.isscalar(ksize) else ksize
        initW = I._get_initializer(initialW, I.HeNormal())
        initb = I._get_initializer(initial_bias, I.Zero())
        with self.init_scope():
            self.W = Parameter(initW((in_channels, out_channels, kh, kw), np.float32, rng))
            if not nobias:
                self.b = Parameter(initb((out_channels,), np.float32, rng))

    def forward(self, x):
        return F.deconvolution_2d(x, self.W.array,
                                  None if self.nobias else self.b.array,
                                  self.stride, self.pad, self.outsize)


class DepthwiseConvolution2D(Link):
    def __init__(self, in_channels, channel_multiplier, ksize, stride=1,
                 pad=0, nobias=False, initialW=None, initial_bias=None,
                 seed=None):
        super().__init__()
        self.stride = stride
        self.pad = pad
        self.nobias = nobias
        rng = _rng(seed)
        kh, kw = (ksize, ksize) if np.isscalar(ksize) else ksize
        initW = I._get_initializer(initialW, I.HeNormal())
        initb = I._get_initializer(initial_bias, I.Zero())
        with self.init_scope():
            self.W = Parameter(initW((channel_multiplier, in_channels, kh, kw), np.float32, rng))
            if not nobias:
                self.b = Parameter(initb((channel_multiplier * in_channels,), np.float32, rng))

    def forward(self, x):
        return F.depthwise_convolution_2d(x, self.W.array,
                                          None if self.nobias else self.b.array,
                                          self.stride, self.pad)


class BatchNormalization(Link):
    """Batch normalization with running statistics as persistent state.

    Reference: ``chainer/links/normalization/batch_normalization.py``.
    In train mode, batch moments normalize and the exponential moving
    averages are updated (functionally — the new values are collected by
    ``bind_state`` and threaded out of the jitted step).  In test mode the
    stored averages are used.  ``comm`` hooks (multi-node sync BN) live in
    ``chainermn_tpu.links.batch_normalization`` (SURVEY §2.3).
    """

    def __init__(self, size, decay=0.9, eps=2e-5, dtype=np.float32,
                 use_gamma=True, use_beta=True, initial_gamma=None,
                 initial_beta=None, axis=None):
        super().__init__()
        self.decay = decay
        self.eps = eps
        self.axis = axis
        with self.init_scope():
            if use_gamma:
                ig = I._get_initializer(initial_gamma, I.One())
                self.gamma = Parameter(ig((size,), dtype))
            if use_beta:
                ib = I._get_initializer(initial_beta, I.Zero())
                self.beta = Parameter(ib((size,), dtype))
        self.use_gamma = use_gamma
        self.use_beta = use_beta
        self.size = size
        self.add_persistent("avg_mean", jnp.zeros((size,), dtype))
        self.add_persistent("avg_var", jnp.ones((size,), dtype))
        self.add_persistent("N", 0)

    def _gamma_beta(self, dtype):
        gamma = self.gamma.array if self.use_gamma else jnp.ones((self.size,), dtype)
        beta = self.beta.array if self.use_beta else jnp.zeros((self.size,), dtype)
        return gamma, beta

    def _moments(self, x, axis):
        """Single-pass batch moments (``F.batch_moments``): mean and
        E[x²] accumulate over ONE fp32-accumulated read of the
        activation instead of the two-pass mean/var loop — the BN-stat
        fusions were the largest non-conv HBM row in the r5 ResNet
        trace.  The multi-node subclass overrides ``_sync_moments`` to
        pmean the two accumulators across ranks before the variance is
        formed."""
        x32 = x.astype(jnp.float32)
        mean = x32.mean(axis=axis)
        sq_mean = jnp.mean(x32 * x32, axis=axis)
        mean, sq_mean = self._sync_moments(mean, sq_mean, x)
        return mean, jnp.maximum(sq_mean - jnp.square(mean), 0.0)

    def _sync_moments(self, mean, sq_mean, x):
        """Cross-rank moment hook (identity here; the multi-node sync BN
        pmeans both accumulators over its communicator axis)."""
        del x
        return mean, sq_mean

    def _moment_count(self, x, axis):
        """Number of elements each moment reduces over (the multi-node
        subclass multiplies by communicator size: stats cover the global
        batch)."""
        m = 1
        for a in axis:
            m *= x.shape[a]
        return m

    def forward(self, x, finetune=False):
        axis = self.axis
        if axis is None:
            axis = (0,) + tuple(range(2, x.ndim))
        gamma, beta = self._gamma_beta(x.dtype)
        if config.train:
            mean, var = self._moments(x, axis)
            y = F._apply_bn(x, gamma, beta, mean, var, self.eps, axis)
            if finetune:
                self.N = self.N + 1
                decay = 1.0 - 1.0 / self.N
            else:
                decay = self.decay
            # functional EMA update — collected via bind_state.  Running
            # variance accumulates the UNBIASED batch variance (× m/(m-1)),
            # matching the reference's adjustment in
            # `chainer/links/normalization/batch_normalization.py`.
            m = self._moment_count(x, axis)
            unbiased = var * (m / max(m - 1, 1))
            self.avg_mean = decay * self.avg_mean + (1 - decay) * mean
            self.avg_var = decay * self.avg_var + (1 - decay) * unbiased
            return y
        return F._apply_bn(x, gamma, beta, jnp.asarray(self.avg_mean),
                           jnp.asarray(self.avg_var), self.eps, axis)


class GroupNormalization(Link):
    def __init__(self, groups, size, eps=1e-5):
        super().__init__()
        self.groups = groups
        self.eps = eps
        with self.init_scope():
            self.gamma = Parameter(jnp.ones((size,)))
            self.beta = Parameter(jnp.zeros((size,)))

    def forward(self, x):
        n, c = x.shape[0], x.shape[1]
        g = self.groups
        xg = x.reshape((n, g, c // g) + x.shape[2:])
        axes = tuple(range(2, xg.ndim))
        mean = xg.mean(axis=axes, keepdims=True)
        var = xg.var(axis=axes, keepdims=True)
        xg = (xg - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps))
        x = xg.reshape(x.shape)
        shape = [1, c] + [1] * (x.ndim - 2)
        return x * self.gamma.array.reshape(shape) + self.beta.array.reshape(shape)


class LayerNormalization(Link):
    def __init__(self, size, eps=1e-5):
        super().__init__()
        self.eps = eps
        with self.init_scope():
            self.gamma = Parameter(jnp.ones((size,)))
            self.beta = Parameter(jnp.zeros((size,)))

    def forward(self, x):
        return F.layer_normalization(x, self.gamma.array, self.beta.array, self.eps)


class RMSNorm(Link):
    """Root-mean-square normalization with a learned gain and no bias
    (Zhang & Sennrich 2019): ``x / sqrt(mean(x²) + eps) · gamma``, the
    statistics in float32 whatever the activation dtype, the result in
    the activation's dtype."""

    def __init__(self, size, eps=1e-5):
        super().__init__()
        self.eps = eps
        with self.init_scope():
            self.gamma = Parameter()
        self.gamma.draw((size,), np.float32,
                        lambda: np.ones((size,), np.float32))

    def forward(self, x):
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + self.eps)
        return (x32 * inv * self.gamma.array.astype(jnp.float32)) \
            .astype(x.dtype)


class EmbedID(Link):
    """Embedding lookup (reference: ``L.EmbedID``)."""

    ignore_label = None

    def __init__(self, in_size, out_size, initialW=None, ignore_label=None,
                 seed=None):
        super().__init__()
        self.ignore_label = ignore_label
        rng = _rng(seed)
        initW = I._get_initializer(initialW, I.Normal(1.0))
        with self.init_scope():
            self.W = Parameter()
        self.W.draw((in_size, out_size), np.float32,
                    lambda: initW((in_size, out_size), np.float32, rng))

    def forward(self, x):
        return F.embed_id(x, self.W.array, self.ignore_label)


class StatelessLSTM(Chain):
    """One LSTM step: (c, h, x) -> (c, h).  Reference: ``L.StatelessLSTM``.

    The gate weight layout packs [input, forget, cell, output] gates into a
    single matmul — the MXU-friendly formulation (one large GEMM per step,
    scanned with ``lax.scan`` for sequences).
    """

    def __init__(self, in_size, out_size, seed=None):
        super().__init__()
        self.out_size = out_size
        with self.init_scope():
            self.upward = Linear(in_size, 4 * out_size, seed=seed)
            self.lateral = Linear(out_size, 4 * out_size, nobias=True,
                                  seed=None if seed is None else seed + 1)

    def forward(self, c, h, x):
        gates = self.upward(x)
        if h is not None:
            gates = gates + self.lateral(h)
        i, f, g, o = jnp.split(gates, 4, axis=1)
        i = F.sigmoid(i)
        f = F.sigmoid(f + 1.0)  # forget-gate bias +1 (reference init convention)
        g = F.tanh(g)
        o = F.sigmoid(o)
        if c is None:
            c = jnp.zeros((x.shape[0], self.out_size), x.dtype)
        c_next = f * c + i * g
        h_next = o * F.tanh(c_next)
        return c_next, h_next


class LSTM(StatelessLSTM):
    """Stateful LSTM holding (c, h) between calls (reference: ``L.LSTM``).

    Statefulness is eager-mode convenience; inside jitted programs prefer
    ``StatelessLSTM`` + ``lax.scan`` (see ``models/seq2seq.py``).
    ``_volatile_attrs`` lets ``bind_state`` restore (c, h) after traced
    calls so tracers never leak into the link.
    """

    _volatile_attrs = ("c", "h")

    def __init__(self, in_size, out_size, seed=None):
        super().__init__(in_size, out_size, seed=seed)
        self.c = None
        self.h = None

    def reset_state(self):
        self.c = None
        self.h = None

    def set_state(self, c, h):
        self.c = c
        self.h = h

    def forward(self, x):
        self.c, self.h = super().forward(self.c, self.h, x)
        return self.h


# RNN family lives in nn/rnn.py (imported late: it consumes Linear above)
from .rnn import StatelessGRU, GRU, NStepLSTM, NStepGRU  # noqa: E402


class Highway(Link):
    """Highway layer (reference: ``L.Highway``)."""

    def __init__(self, in_out_size, nobias=False, activate=None, seed=None):
        super().__init__()
        self.activate = activate or F.relu
        s = (lambda k: None if seed is None else seed + k)
        with self.init_scope():
            self.plain = Linear(in_out_size, in_out_size, nobias=nobias,
                                seed=s(0))
            self.transform = Linear(in_out_size, in_out_size,
                                    nobias=nobias,
                                    initial_bias=I.Constant(-1.0), seed=s(1))

    def forward(self, x):
        h = self.activate(self.plain(x))
        t = F.sigmoid(self.transform(x))
        return h * t + x * (1 - t)


class Maxout(Link):
    """Fully-connected maxout (reference: ``L.Maxout``)."""

    def __init__(self, in_size, out_size, pool_size, seed=None):
        super().__init__()
        self.out_size = out_size
        self.pool_size = pool_size
        with self.init_scope():
            self.linear = Linear(in_size, out_size * pool_size, seed=seed)

    def forward(self, x):
        h = self.linear(x)
        return jnp.max(h.reshape(-1, self.out_size, self.pool_size), axis=2)


class Scale(Link):
    """Elementwise scale + optional shift (reference: ``L.Scale``)."""

    def __init__(self, axis=1, W_shape=None, bias_term=False):
        super().__init__()
        self.axis = axis
        with self.init_scope():
            self.W = Parameter(jnp.ones(W_shape))
            if bias_term:
                self.bias = Parameter(jnp.zeros(W_shape))
        self.bias_term = bias_term

    def forward(self, x):
        shape = [1] * x.ndim
        for i, s in enumerate(self.W.array.shape):
            shape[self.axis + i] = s
        y = x * self.W.array.reshape(shape)
        if self.bias_term:
            y = y + self.bias.array.reshape(shape)
        return y


def __getattr__(name):
    # L.Classifier lives with the models (avoids a circular import);
    # exposed here for chainer-parity `L.Classifier(...)` call sites
    if name == "Classifier":
        from ..models.mlp import Classifier
        return Classifier
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
