"""Neural-network functions (consumed-Chainer surface: ``chainer.functions``).

Reference anchors: ``chainer/functions/ · relu, softmax_cross_entropy,
convolution_2d, max_pooling_2d, batch_normalization, ...`` (SURVEY.md §2.8).
All functions are pure ``jnp`` programs: differentiable by ``jax.grad``,
fusible by XLA, layout NCHW to match the reference's convention (XLA
re-layouts internally for the MXU; the API contract is what matters here).
Stochastic functions (``dropout``) take an explicit ``key`` — the idiomatic
JAX replacement for the reference's hidden global RNG; if omitted, a
fresh per-step subkey comes from the compiled train step's key scope
(``core.rng``), falling back to a host-drawn key in eager use.
"""

from __future__ import annotations

import builtins
import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import role
from ..ops import softmax_cotangent

__all__ = [
    "relu", "leaky_relu", "elu", "sigmoid", "tanh", "softplus", "gelu", "silu",
    "softmax", "log_softmax", "softmax_cross_entropy", "sigmoid_cross_entropy",
    "mean_squared_error", "mean_absolute_error", "huber_loss", "accuracy",
    "dropout", "linear", "embed_id",
    "convolution_2d", "deconvolution_2d", "depthwise_convolution_2d",
    "max_pooling_2d", "average_pooling_2d", "unpooling_2d",
    "global_average_pooling_2d", "resize_images",
    "batch_normalization", "fixed_batch_normalization", "batch_moments",
    "layer_normalization",
    "concat", "stack", "hstack", "vstack", "split_axis", "separate",
    "average", "select_item", "absolute", "maximum", "minimum", "swish",
    "normalize", "local_response_normalization", "squared_error",
    "reshape", "flatten", "transpose", "expand_dims", "squeeze", "tile",
    "broadcast_to", "sum", "mean", "max", "min", "argmax", "sqrt", "exp",
    "log", "clip", "matmul", "batch_matmul", "where", "pad",
]


# -- activations -----------------------------------------------------------

def relu(x):
    return jnp.maximum(x, 0)


def leaky_relu(x, slope=0.2):
    return jnp.where(x >= 0, x, slope * x)


def elu(x, alpha=1.0):
    return jnp.where(x >= 0, x, alpha * (jnp.exp(x) - 1))


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def softplus(x, beta=1.0):
    return jax.nn.softplus(beta * x) / beta


def gelu(x):
    return jax.nn.gelu(x)


def silu(x):
    return jax.nn.silu(x)


def softmax(x, axis=1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=1):
    return jax.nn.log_softmax(x, axis=axis)


# -- losses ----------------------------------------------------------------

@role("loss")
def softmax_cross_entropy(x, t, ignore_label=-1, reduce="mean",
                          normalize=True, class_weight=None):
    """Softmax + NLL with ignore-label masking.

    Matches the reference semantics (``F.softmax_cross_entropy``): ``t`` holds
    int class ids; entries equal to ``ignore_label`` contribute zero loss and
    are excluded from the normalizer; ``class_weight`` ([n_classes]) scales
    each example's loss by its target class's weight.

    The rows' work is ``ops.softmax_cotangent.weighted_nll``: plain
    ``jnp`` and plain autodiff, except on a TPU for ``[N, V]`` logits
    that ``ops.softmax_cotangent.fits`` (bfloat16, whole blocks of 16
    rows, at least 4096 classes), which go through a backward rule of the loss's
    own.  What that guarantees: the cotangent of ``x``, ``(softmax(x) -
    onehot(t)) * w / count`` in float32 rounded once to ``x``'s dtype,
    exists as ONE array of ``x``'s shape and dtype, written by the
    forward pass from its one read of the logits, and whatever consumes
    it (the two backward GEMMs of a language model's head) takes a plain
    operand where each used to rebuild the softmax from the logits in
    its prologue; the logits themselves are laid out by rows.  What it
    gives up: it is a ``jax.custom_vjp``, so ``jax.jvp`` (forward mode)
    through the loss raises there; no caller in this tree differentiates
    the loss forwards.  Its values are plain autodiff's to the last
    place or two of the dtype (another ``exp``, another order of sums),
    not to the bit, and a target that is neither ``ignore_label`` nor a
    class gives its row no loss there.  ``t`` gets no cotangent.
    """
    mask = t != ignore_label
    t_safe = jnp.where(mask, t, 0)
    a = mask.astype(jnp.float32)
    if class_weight is not None:
        a = a * jnp.asarray(class_weight, jnp.float32)[t_safe]
    if reduce != "no":
        a = a / (jnp.maximum(mask.sum(), 1) if normalize else x.shape[0])
    # under a mean the rows' cotangent has to reach the rule as plain ones
    # (it then has nothing to multiply): the mask is in ``a``, not here
    nll = softmax_cotangent.weighted_nll(x, t_safe, a)
    return nll if reduce == "no" else nll.sum()


def sigmoid_cross_entropy(x, t, reduce="mean"):
    t = t.astype(x.dtype)
    loss = jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
    if reduce == "no":
        return loss
    return loss.mean()


def mean_squared_error(x, t):
    return jnp.mean((x - t) ** 2)


def mean_absolute_error(x, t):
    return jnp.mean(jnp.abs(x - t))


def huber_loss(x, t, delta=1.0, reduce="sum_along_second_axis"):
    d = x - t
    abs_d = jnp.abs(d)
    loss = jnp.where(abs_d <= delta, 0.5 * d * d, delta * (abs_d - 0.5 * delta))
    if reduce == "no":
        return loss
    return loss.sum(axis=1)


def accuracy(y, t, ignore_label=None):
    pred = jnp.argmax(y, axis=1)
    if ignore_label is not None:
        mask = (t != ignore_label)
        correct = jnp.where(mask, pred == t, False)
        return correct.sum() / jnp.maximum(mask.sum(), 1)
    return jnp.mean((pred == t).astype(jnp.float32))


# -- stochastic ------------------------------------------------------------

def dropout(x, ratio=0.5, key=None, train: bool | None = None):
    from ..core.config import config
    if train is None:
        train = config.train
    if not train or ratio == 0.0:
        return x
    if key is None:
        # per-step key pushed by the compiled train step (core.rng);
        # outside any step scope, fall back to a host-drawn key (eager
        # use — matches the reference's hidden global RNG)
        from ..core import rng as rng_module
        key = rng_module.next_key()
    if key is None:
        key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
    keep = 1.0 - ratio
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


# -- linear / embedding ----------------------------------------------------

def linear(x, W, b=None, n_batch_axes=1):
    if n_batch_axes > 1:
        batch_shape = x.shape[:n_batch_axes]
        x = x.reshape((int(np.prod(batch_shape)), -1))
    elif x.ndim > 2:
        x = x.reshape((x.shape[0], -1))
        batch_shape = None
    else:
        batch_shape = None
    y = x @ W.T
    if b is not None:
        y = y + b
    if n_batch_axes > 1:
        y = y.reshape(batch_shape + (W.shape[0],))
    return y


def embed_id(x, W, ignore_label=None):
    if ignore_label is not None:
        safe = jnp.where(x == ignore_label, 0, x)
        emb = W[safe]
        return jnp.where((x == ignore_label)[..., None], 0.0, emb)
    return W[x]


# -- convolutions -----------------------------------------------------------
#
# Kernel storage is always OIHW (the reference layout — checkpoints stay
# portable); the ACTIVATION layout is a per-call choice.  "NCHW" is the
# reference's layout; "NHWC" is the TPU-native layout (channels-last maps
# directly onto the MXU's lane dimension, so XLA inserts no layout-change
# transposes between conv, BN, and elementwise ops).

def _pair(v):
    return (v, v) if np.isscalar(v) else tuple(v)


def _spatial_dims(layout):
    """(h_dim, w_dim, channel_dim) for a 4-D activation layout string."""
    if layout == "NCHW":
        return 2, 3, 1
    if layout == "NHWC":
        return 1, 2, 3
    raise ValueError(f"unsupported activation layout {layout!r}")


def convolution_2d(x, W, b=None, stride=1, pad=0, dilate=1, groups=1,
                   layout="NCHW"):
    sy, sx = _pair(stride)
    ph, pw = _pair(pad)
    dy, dx = _pair(dilate)
    y = lax.conv_general_dilated(
        x, W,
        window_strides=(sy, sx),
        padding=((ph, ph), (pw, pw)),
        rhs_dilation=(dy, dx),
        dimension_numbers=(layout, "OIHW", layout),
        feature_group_count=groups,
    )
    if b is not None:
        y = y + (b[None, :, None, None] if layout == "NCHW"
                 else b[None, None, None, :])
    return y


def deconvolution_2d(x, W, b=None, stride=1, pad=0, outsize=None):
    """Transposed convolution; kernel (in_ch, out_ch, kh, kw) like the
    reference (``L.Deconvolution2D``).

    Implemented as the literal transpose of the corresponding forward
    convolution (the reference's definition) via ``jax.vjp`` — XLA lowers
    this to a single transposed-conv kernel, and the kernel-layout
    conventions can't drift from the conv they transpose.
    """
    sy, sx = _pair(stride)
    ph, pw = _pair(pad)
    in_ch, out_ch, kh, kw = W.shape
    n, _, h, w = x.shape
    if outsize is None:
        oh, ow = sy * (h - 1) + kh - 2 * ph, sx * (w - 1) + kw - 2 * pw
    else:
        oh, ow = outsize

    # analytic shape check: the forward conv of (oh, ow) must give (h, w)
    if (oh + 2 * ph - kh) // sy + 1 != h or (ow + 2 * pw - kw) // sx + 1 != w \
            or oh + 2 * ph < kh or ow + 2 * pw < kw:
        raise ValueError(
            f"invalid outsize {(oh, ow)} for input {(h, w)} with "
            f"k={(kh, kw)} s={(sy, sx)} p={(ph, pw)}")

    def fwd(a):  # [N, out_ch, oh, ow] → [N, in_ch, h, w]
        return lax.conv_general_dilated(
            a, W, (sy, sx), ((ph, ph), (pw, pw)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    # fwd is linear in its input — linear_transpose traces it once and
    # never evaluates the discarded primal
    f_t = jax.linear_transpose(
        fwd, jax.ShapeDtypeStruct((n, out_ch, oh, ow), x.dtype))
    (y,) = f_t(x)
    if b is not None:
        y = y + b[None, :, None, None]
    return y


def depthwise_convolution_2d(x, W, b=None, stride=1, pad=0):
    # W: (channel_multiplier, in_channels, kh, kw) in the reference
    cm, ic, kh, kw = W.shape
    Wg = W.transpose(1, 0, 2, 3).reshape(ic * cm, 1, kh, kw)
    return convolution_2d(x, Wg, b, stride, pad, groups=ic)


# -- pooling ---------------------------------------------------------------

def _pool_geometry(kh, kw, sy, sx, pads, layout):
    """(window_dims, window_strides, padding) for a 4-D pooling op in
    either activation layout; ``pads`` is ((ph_lo, ph_hi), (pw_lo, pw_hi))."""
    hd, wd, _ = _spatial_dims(layout)
    dims, strides, padding = [1] * 4, [1] * 4, [(0, 0)] * 4
    dims[hd], dims[wd] = kh, kw
    strides[hd], strides[wd] = sy, sx
    padding[hd], padding[wd] = pads
    return tuple(dims), tuple(strides), tuple(padding)


#: Backward lowering for float max pooling: "argmax" (default) stores the
#: per-window argmax in the forward and scatters the cotangent through it
#: in ONE fused pass; "xla" keeps the reduce_window VJP, whose
#: `select-and-scatter` re-compares the whole input against the output on
#: the backward pass (an unfusible HBM-bound op — the 0.75 ms/step row in
#: the r5 ResNet trace).  Env knob for A/B and fallback; tests pin the
#: two paths equal.
_MAXPOOL_VJP = os.environ.get("CHAINERMN_TPU_MAXPOOL_VJP", "argmax")


def max_pooling_2d(x, ksize, stride=None, pad=0, cover_all=True,
                   layout="NCHW"):
    kh, kw = _pair(ksize)
    sy, sx = _pair(stride if stride is not None else ksize)
    ph, pw = _pair(pad)
    hd, wd, _ = _spatial_dims(layout)
    if cover_all:
        # reference semantics: pad enough that every element is covered
        h, w = x.shape[hd], x.shape[wd]
        # NB: this module shadows builtin max with the F.max alias
        eh = builtins.max(0, (-(h + 2 * ph - kh) % sy)) if sy > 1 else 0
        ew = builtins.max(0, (-(w + 2 * pw - kw) % sx)) if sx > 1 else 0
    else:
        eh = ew = 0
    pads = ((ph, ph + eh), (pw, pw + ew))
    if _MAXPOOL_VJP == "argmax" and kh * kw <= 255 \
            and jnp.issubdtype(x.dtype, jnp.floating):
        # uint8 argmax storage caps the window at 255 taps; larger
        # windows (never seen in practice) keep the XLA path
        return _max_pool_argmax(x, (kh, kw), (sy, sx), pads,
                                (x.shape[hd], x.shape[wd]), layout)
    return _max_pool_xla(x, (kh, kw), (sy, sx), pads, layout)


def _max_pool_xla(x, kdims, sdims, pads, layout):
    """Plain reduce_window max (XLA differentiates it via
    select-and-scatter) — the pre-argmax lowering, kept as the integer
    path, the >255-tap fallback, and the equivalence-test reference."""
    kh, kw = kdims
    sy, sx = sdims
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    dims, strides, padding = _pool_geometry(kh, kw, sy, sx, pads, layout)
    return lax.reduce_window(x, neg, lax.max, dims, strides, padding)


def _window_taps(x_p, kh, kw, sy, sx, oh, ow, hd, wd):
    """(offset, strided slice of the padded input) per window tap — each
    slice is an output-shaped view; XLA fuses the whole chain into one
    pass over the input."""
    nd = x_p.ndim
    for i in range(kh):
        for j in range(kw):
            start = [0] * nd
            limit = list(x_p.shape)
            strides = [1] * nd
            start[hd], start[wd] = i, j
            limit[hd] = i + sy * (oh - 1) + 1
            limit[wd] = j + sx * (ow - 1) + 1
            strides[hd], strides[wd] = sy, sx
            yield i * kw + j, lax.slice(x_p, start, limit, strides)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _max_pool_argmax(x, kdims, sdims, pads, hw, layout):
    """Max pooling whose VJP scatters through STORED argmax indices.

    Forward: the max itself comes from the same fused ``reduce_window``
    as the XLA path (bit-identical values); a fused compare chain over
    the k·k strided window taps additionally materializes each window's
    (first) argmax as a uint8 plane.  Backward: one pass summing the
    k·k dilated placements of ``where(idx == tap, g, 0)`` — all pads and
    adds, fully fusible — instead of XLA's ``select-and-scatter``, which
    re-reads the entire input AND output to re-discover the argmax.
    Gradients match the XLA lowering bit-exactly for tie-free inputs.
    With EXACT ties (realistic in bf16) the two lowerings diverge: this
    path routes the whole cotangent to the FIRST maximum in window order
    (the argmax convention, and the reference Chainer's), while XLA's
    packed select-and-gather picks a tied winner by tangent bit pattern
    — effectively arbitrary.  Deterministic-first is the better
    contract, so the divergence is intentional; NaN windows likewise
    route to tap 0 here where XLA propagates.
    """
    y, _ = _max_pool_argmax_fwd_impl(x, kdims, sdims, pads, layout)
    return y


def _max_pool_argmax_fwd_impl(x, kdims, sdims, pads, layout):
    kh, kw = kdims
    sy, sx = sdims
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    hd, wd, _ = _spatial_dims(layout)
    dims, strides, padding = _pool_geometry(kh, kw, sy, sx, pads, layout)
    y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, padding)
    pad_cfg = [(0, 0, 0)] * x.ndim
    pad_cfg[hd] = (ph_lo, ph_hi, 0)
    pad_cfg[wd] = (pw_lo, pw_hi, 0)
    x_p = lax.pad(x, jnp.array(-jnp.inf, x.dtype), pad_cfg)
    oh, ow = y.shape[hd], y.shape[wd]
    best = idx = None
    for o, tap in _window_taps(x_p, kh, kw, sy, sx, oh, ow, hd, wd):
        if best is None:
            best, idx = tap, jnp.zeros(tap.shape, jnp.uint8)
        else:
            take = tap > best  # strict >: first max wins, like argmax
            best = jnp.where(take, tap, best)
            idx = jnp.where(take, jnp.uint8(o), idx)
    return y, idx


def _max_pool_argmax_fwd(x, kdims, sdims, pads, hw, layout):
    y, idx = _max_pool_argmax_fwd_impl(x, kdims, sdims, pads, layout)
    # residual: ONE uint8 output-shaped plane (vs select-and-scatter
    # keeping the full input AND output live into the backward)
    return y, idx


def _max_pool_argmax_bwd(kdims, sdims, pads, hw, layout, idx, g):
    kh, kw = kdims
    sy, sx = sdims
    (ph_lo, ph_hi), (pw_lo, pw_hi) = pads
    h_in, w_in = hw
    hd, wd, _ = _spatial_dims(layout)
    oh, ow = g.shape[hd], g.shape[wd]
    hp = h_in + ph_lo + ph_hi
    wp = w_in + pw_lo + pw_hi
    zero = jnp.array(0, g.dtype)
    dx_p = None
    for i in range(kh):
        for j in range(kw):
            o = i * kw + j
            contrib = jnp.where(idx == jnp.uint8(o), g, zero)
            # transpose of the forward's strided slice: dilate by the
            # stride, offset by the tap position
            pad_cfg = [(0, 0, 0)] * g.ndim
            pad_cfg[hd] = (i, hp - (i + sy * (oh - 1) + 1), sy - 1)
            pad_cfg[wd] = (j, wp - (j + sx * (ow - 1) + 1), sx - 1)
            placed = lax.pad(contrib, zero, pad_cfg)
            dx_p = placed if dx_p is None else dx_p + placed
    start = [0] * dx_p.ndim
    limit = list(dx_p.shape)
    start[hd], start[wd] = ph_lo, pw_lo
    limit[hd], limit[wd] = ph_lo + h_in, pw_lo + w_in
    return (lax.slice(dx_p, start, limit),)


_max_pool_argmax.defvjp(_max_pool_argmax_fwd, _max_pool_argmax_bwd)


def average_pooling_2d(x, ksize, stride=None, pad=0, layout="NCHW"):
    kh, kw = _pair(ksize)
    sy, sx = _pair(stride if stride is not None else ksize)
    ph, pw = _pair(pad)
    dims, strides, padding = _pool_geometry(
        kh, kw, sy, sx, ((ph, ph), (pw, pw)), layout)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
    # reference divides by the full window size (count_include_pad=True);
    # the scale stays in x.dtype (weak-typed), so a bf16 activation is
    # read and written as bf16 — no f32 round-trip through HBM
    return summed / (kh * kw)


def unpooling_2d(x, ksize, stride=None, pad=0, outsize=None, cover_all=True):
    """Inverse of sum-pooling: each value scatter-adds over its k×k window.

    Reference semantics (``F.unpooling_2d``): output size
    ``s*(in-1)+k-2p`` (minus ``s-1`` under ``cover_all``).  Implemented as
    the VJP of sum-pooling — the transposed scatter-add XLA compiles to a
    single fused kernel.
    """
    kh, kw = _pair(ksize)
    sy, sx = _pair(stride if stride is not None else ksize)
    ph, pw = _pair(pad)
    h, w = x.shape[2], x.shape[3]
    if outsize is None:
        oh = sy * (h - 1) + kh - 2 * ph - (sy - 1 if cover_all else 0)
        ow = sx * (w - 1) + kw - 2 * pw - (sx - 1 if cover_all else 0)
    else:
        oh, ow = outsize
    if (sy, sx) == (kh, kw) and (ph, pw) == (0, 0) and (oh, ow) == (h * kh, w * kw):
        return jnp.repeat(jnp.repeat(x, kh, axis=2), kw, axis=3)
    # trailing pad so that pooling the (oh, ow) plane yields exactly (h, w)
    prh = (h - 1) * sy + kh - oh - ph
    prw = (w - 1) * sx + kw - ow - pw

    def pool(y):
        return lax.reduce_window(
            y, 0.0, lax.add,
            window_dimensions=(1, 1, kh, kw),
            window_strides=(1, 1, sy, sx),
            padding=((0, 0), (0, 0), (ph, prh), (pw, prw)))

    zeros = jnp.zeros(x.shape[:2] + (oh, ow), x.dtype)
    _, vjp = jax.vjp(pool, zeros)
    (y,) = vjp(x)
    return y


def global_average_pooling_2d(x, layout="NCHW"):
    # one reduction in x.dtype: bf16 activations pool as bf16 (half the
    # HBM read of an f32 upcast); heads needing f32 cast the RESULT
    # (a [N, C] vector), as models/resnet.py does before its fc
    hd, wd, _ = _spatial_dims(layout)
    return x.mean(axis=(hd, wd))


def resize_images(x, output_shape):
    n, c, _, _ = x.shape
    oh, ow = output_shape
    return jax.image.resize(x, (n, c, oh, ow), method="bilinear")


# -- normalization ---------------------------------------------------------

def batch_moments(x, axis):
    """Single-pass batch moments: mean and E[x²] accumulate side by side
    over ONE read of ``x`` (fp32 accumulation regardless of activation
    dtype), ``var = E[x²] − mean²`` clamped at 0 against fp32
    cancellation.  The two-pass formulation this replaces (mean, then
    mean of squared deviations) read the activation three times — for a
    ResNet the BN-stat loop fusions were the largest non-conv HBM row in
    the r5 trace.  The VJP is also one pass (d/dx of both sums is a
    fused axpy), where the two-pass var backward re-read x.  Same
    formulation as the multi-node sync BN, which pmeans the two
    accumulators — so single- and multi-node BN now share their numerics.
    """
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=axis)
    sq_mean = jnp.mean(x32 * x32, axis=axis)
    var = jnp.maximum(sq_mean - jnp.square(mean), 0.0)
    return mean, var


def batch_normalization(x, gamma, beta, eps=2e-5, axis=None):
    if axis is None:
        axis = (0,) + tuple(range(2, x.ndim))
    mean, var = batch_moments(x, axis)
    return _apply_bn(x, gamma, beta, mean, var, eps, axis)


def fixed_batch_normalization(x, gamma, beta, mean, var, eps=2e-5, axis=None):
    if axis is None:
        axis = (0,) + tuple(range(2, x.ndim))
    return _apply_bn(x, gamma, beta, mean, var, eps, axis)


def _apply_bn(x, gamma, beta, mean, var, eps, axis):
    # Fold the normalization into a per-channel scale/shift computed in
    # fp32 (tiny vectors), applied in x.dtype: one fused mul-add over the
    # activation instead of sub/mul/mul/add — and when x is bf16 the big
    # elementwise op stays bf16 (half the HBM traffic), while all the
    # statistics math stays fp32.
    f32 = jnp.float32
    inv = lax.rsqrt(var.astype(f32) + eps)
    a = gamma.astype(f32) * inv
    shape = [1] * x.ndim
    kept = [d for d in range(x.ndim) if d not in axis]
    for d in kept:
        shape[d] = x.shape[d]
    if x.dtype == f32:
        # fp32 activations keep the unfolded (x - mean) * a + beta form:
        # when |mean| >> std the folded ``x*a + (beta - mean*a)`` loses
        # precision to cancellation, and fp32 gains nothing from folding
        # (the fusion win is bf16 HBM traffic only).
        m = mean.astype(f32).reshape(shape)
        a = a.reshape(shape)
        b = beta.astype(f32).reshape(shape)
        return (x - m) * a + b
    b = beta.astype(f32) - mean.astype(f32) * a
    a = a.reshape(shape).astype(x.dtype)
    b = b.reshape(shape).astype(x.dtype)
    return x * a + b


def layer_normalization(x, gamma, beta, eps=1e-5):
    # statistics in fp32 (bf16 mean/var of wide rows loses precision),
    # output in the activation dtype — same discipline as _apply_bn
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    var = x32.var(axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps) * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)
    return y.astype(x.dtype)


# -- shape / array ops (thin jnp aliases, reference names) ------------------

def concat(xs, axis=1):
    return jnp.concatenate(list(xs), axis=axis)


def stack(xs, axis=0):
    return jnp.stack(list(xs), axis=axis)


def hstack(xs):
    return jnp.hstack(list(xs))


def vstack(xs):
    return jnp.vstack(list(xs))


def split_axis(x, indices_or_sections, axis):
    return tuple(jnp.split(x, indices_or_sections, axis=axis))


def separate(x, axis=0):
    return tuple(jnp.moveaxis(x, axis, 0))


def reshape(x, shape):
    return jnp.reshape(x, shape)


def flatten(x):
    return jnp.reshape(x, (-1,))


def transpose(x, axes=None):
    return jnp.transpose(x, axes)


def expand_dims(x, axis):
    return jnp.expand_dims(x, axis)


def squeeze(x, axis=None):
    return jnp.squeeze(x, axis)


def tile(x, reps):
    return jnp.tile(x, reps)


def broadcast_to(x, shape):
    return jnp.broadcast_to(x, shape)


def sum(x, axis=None, keepdims=False):
    return jnp.sum(x, axis=axis, keepdims=keepdims)


def mean(x, axis=None, keepdims=False):
    return jnp.mean(x, axis=axis, keepdims=keepdims)


def max(x, axis=None, keepdims=False):
    return jnp.max(x, axis=axis, keepdims=keepdims)


def min(x, axis=None, keepdims=False):
    return jnp.min(x, axis=axis, keepdims=keepdims)


def argmax(x, axis=None):
    return jnp.argmax(x, axis=axis)


def sqrt(x):
    return jnp.sqrt(x)


def exp(x):
    return jnp.exp(x)


def log(x):
    return jnp.log(x)


def clip(x, x_min, x_max):
    return jnp.clip(x, x_min, x_max)


def matmul(a, b, transa=False, transb=False):
    if transa:
        a = jnp.swapaxes(a, -1, -2)
    if transb:
        b = jnp.swapaxes(b, -1, -2)
    return a @ b


def batch_matmul(a, b, transa=False, transb=False):
    if a.ndim == 2:
        a = a[:, :, None]
    if b.ndim == 2:
        b = b[:, :, None]
    return matmul(a, b, transa, transb)


def where(cond, x, y):
    return jnp.where(cond, x, y)


def pad(x, pad_width, mode="constant", **kwargs):
    return jnp.pad(x, pad_width, mode=mode, **kwargs)


# -- additional reference-surface functions ---------------------------------

def average(x, axis=None, weights=None, keepdims=False):
    """Weighted mean (reference: ``F.average``)."""
    if weights is None:
        return jnp.mean(x, axis=axis, keepdims=keepdims)
    return jnp.average(x, axis=axis, weights=weights)


def select_item(x, t):
    """x[i, t[i]] for each row (reference: ``F.select_item``)."""
    return jnp.take_along_axis(x, t[:, None], axis=1).squeeze(1)


def absolute(x):
    return jnp.abs(x)


def maximum(a, b):
    return jnp.maximum(a, b)


def minimum(a, b):
    return jnp.minimum(a, b)


def swish(x, beta=1.0):
    return x * jax.nn.sigmoid(beta * x)


def normalize(x, eps=1e-5, axis=1):
    """L2 normalization along ``axis`` (reference: ``F.normalize``)."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True)) + eps
    return x / norm


def local_response_normalization(x, n=5, k=2.0, alpha=1e-4, beta=0.75):
    """Cross-channel LRN on NCHW (reference: ``F.local_response_
    normalization``; AlexNet-era)."""
    sq = x * x
    half = n // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    # note: this module shadows builtin sum with the reference F.sum alias
    window = padded[:, 0:x.shape[1]]
    for i in range(1, n):
        window = window + padded[:, i:i + x.shape[1]]
    return x / (k + alpha * window) ** beta


def squared_error(x, t):
    return (x - t) ** 2


def log_softmax_cross_entropy_components(x, t, ignore_label=-1):
    """(per-example nll, valid mask) — building block for custom losses."""
    nll = softmax_cross_entropy(x, t, ignore_label=ignore_label, reduce="no")
    return nll, t != ignore_label


# -- elementwise math aliases (reference F.* long tail) ---------------------

def sin(x):
    return jnp.sin(x)


def cos(x):
    return jnp.cos(x)


def tan(x):
    return jnp.tan(x)


def arcsin(x):
    return jnp.arcsin(x)


def arccos(x):
    return jnp.arccos(x)


def arctan(x):
    return jnp.arctan(x)


def arctan2(x1, x2):
    return jnp.arctan2(x1, x2)


def sinh(x):
    return jnp.sinh(x)


def cosh(x):
    return jnp.cosh(x)


def erf(x):
    return jax.scipy.special.erf(x)


def erfc(x):
    return jax.scipy.special.erfc(x)


def floor(x):
    return jnp.floor(x)


def ceil(x):
    return jnp.ceil(x)


def sign(x):
    return jnp.sign(x)


def square(x):
    return jnp.square(x)


def rsqrt(x):
    return lax.rsqrt(x)


def log2(x):
    return jnp.log2(x)


def log10(x):
    return jnp.log10(x)


def log1p(x):
    return jnp.log1p(x)


def expm1(x):
    return jnp.expm1(x)


def cumsum(x, axis=None):
    return jnp.cumsum(x, axis=axis)


def cumprod(x, axis=None):
    return jnp.cumprod(x, axis=axis)


def prod(x, axis=None, keepdims=False):
    return jnp.prod(x, axis=axis, keepdims=keepdims)


def logsumexp(x, axis=None):
    return jax.scipy.special.logsumexp(x, axis=axis)


def fmod(x, divisor):
    return jnp.fmod(x, divisor)


def fix(x):
    # jnp.fix is deprecated (removed in jax 0.10); trunc is identical
    # (round toward zero)
    return jnp.trunc(x)


def relu6(x):
    return jnp.clip(x, 0, 6)


def hard_sigmoid(x):
    return jnp.clip(x * 0.2 + 0.5, 0.0, 1.0)


def softmin(x, axis=1):
    return jax.nn.softmax(-x, axis=axis)


def crelu(x, axis=1):
    return jnp.concatenate([jnp.maximum(x, 0), jnp.maximum(-x, 0)],
                           axis=axis)


def flip(x, axis):
    return jnp.flip(x, axis)


def fliplr(x):
    return jnp.fliplr(x)


def flipud(x):
    return jnp.flipud(x)


def rollaxis(x, axis, start=0):
    return jnp.rollaxis(x, axis, start)


def swapaxes(x, axis1, axis2):
    return jnp.swapaxes(x, axis1, axis2)


def moveaxis(x, source, destination):
    return jnp.moveaxis(x, source, destination)


def repeat(x, repeats, axis=None):
    return jnp.repeat(x, repeats, axis=axis)


def diagonal(x, offset=0, axis1=0, axis2=1):
    return jnp.diagonal(x, offset, axis1, axis2)


def cast(x, typ):
    return x.astype(typ)


def identity(*xs):
    return xs[0] if len(xs) == 1 else xs


def scale(x, y, axis=1):
    shape = [1] * x.ndim
    for i, s in enumerate(jnp.shape(y)):
        shape[axis + i] = s
    return x * jnp.reshape(y, shape)


def bias(x, y, axis=1):
    shape = [1] * x.ndim
    for i, s in enumerate(jnp.shape(y)):
        shape[axis + i] = s
    return x + jnp.reshape(y, shape)


def matmul_nn(a, b):
    return a @ b


def tensordot(a, b, axes=2):
    return jnp.tensordot(a, b, axes=axes)


def einsum(subscripts, *operands):
    return jnp.einsum(subscripts, *operands)
