"""The softmax's row sums and the cross-entropy's cotangent from ONE read
of the logits (ISSUE 39, form 2).

``F.softmax_cross_entropy`` over ``[N, V]`` logits needs, for each row,
``sum(exp(x - max))`` going forwards and ``(softmax(x) - onehot(t)) * a``
going backwards (``a`` the row's weight: mask, class weight, one over the
count).  Left to XLA that is two passes over the logits, and the second
either rides in the prologue of both of the head's backward GEMMs (GPT-2
medium's step on a v5e, Adam's update in the epilogue of the first: they
then run at 5.03 and 2.89 ms where 3.98 and 2.25 are had with a plain
operand laid out by rows) or is a pass of its own,
a read and a write of the whole array (1.22 ms beside the 0.70 of the
first).  The kernel here does both from one read, 1.26 ms (PERF.md
section 6, PR 39): a block of rows is brought into VMEM once, its
exponentials are kept there in float32 while their row sums are taken,
and the cotangent is written from them.  It is bound by the read and the
write (412 MB each at 654 GB/s).

The onehot costs no pass over the block: before the exponentials are
scaled, row ``r``'s entry at its target has the row's sum taken off,
``e[r, t_r] -= s_r``, so that ``e * (a / s)`` is ``(e / s - onehot) * a``.

The row maximum comes from outside (XLA takes it in the epilogue of the
GEMM that makes the logits), as does the target's logit (a gather of N
numbers).

:func:`weighted_nll` is the one way in, for ``F.softmax_cross_entropy``:
the kernel on a TPU for logits that :func:`fits`, plain ``jnp`` and plain
autodiff everywhere else.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu

LANES = 128
# rows a block, classes a step of the walks inside it: from a sweep on the
# chip at [4096, 50257] bfloat16 (PERF.md section 6, PR 39): the kernel is
# at its bytes from 65536 numbers a step on, and 16 rows are one packed
# bfloat16 tile and the least VMEM
ROWS = 16
CHUNK = 4096
VMEM_BUDGET = 64 << 20


def _lane_tiles(classes):
    """``classes`` up to whole tiles of lanes."""
    return -(-classes // LANES) * LANES


def vmem_bytes(classes, itemsize):
    """What a block of rows takes: logits and cotangent double buffered,
    the exponentials in float32, and room for the rest."""
    return ROWS * _lane_tiles(classes) * (4 * itemsize + 4) + (4 << 20)


def fits(shape, dtype):
    """Whether the kernel takes logits of this shape: two axes, whole
    blocks of rows, at least one whole step of classes, bfloat16, and a
    block that fits the fast memory (``VMEM_BUDGET`` is half a v5e
    core's).  The gate is what a v5e measured (PERF.md section 6, PR
    39), the vocabulary of a step under the optimizer, where Adam rides
    in the epilogue of the head's weight-gradient GEMM: bfloat16 logits
    4.7 % quicker than the plain form at ``[4096, 50257]``, 10.9 % at
    ``[8192, 32768]``, inside 2.5 % either way at four shapes from
    ``[4096, 4096]`` to ``[1024, 160000]``; float32 logits 6.8 % and
    27 % SLOWER, so they keep the plain form.  (A bare ``value_and_grad``
    of a head, no optimizer in the program, is 4-19 % slower with the
    kernel: there the GEMMs hide their softmax prologue.)"""
    return (len(shape) == 2 and shape[0] % ROWS == 0 and shape[1] >= CHUNK
            and jnp.dtype(dtype) == jnp.bfloat16
            and vmem_bytes(shape[1], 2) <= VMEM_BUDGET)


def _softmax_cotangent_kernel(t_ref, x_ref, m_ref, a_ref, s_ref, c_ref,
                              e_ref):
    rows, classes = x_ref.shape
    n_full, tail = divmod(classes, CHUNK)
    m = m_ref[...]
    last = slice(n_full * CHUNK, classes)

    def full(j):
        return pl.ds(pl.multiple_of(j * CHUNK, CHUNK), CHUNK)

    def exps(cols):
        e = jnp.exp(x_ref[:, cols].astype(jnp.float32) - m)
        e_ref[:, cols] = e
        return e

    acc = lax.fori_loop(0, n_full, lambda j, acc: acc + exps(full(j)),
                        jnp.zeros((rows, CHUNK), jnp.float32))
    s = jnp.sum(acc, axis=1, keepdims=True)
    if tail:
        s = s + jnp.sum(exps(last), axis=1, keepdims=True)
    s_ref[...] = s

    # the onehot: e[r, t_r] -= s_r, on the one lane tile that holds it
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    first = pl.program_id(0) * rows
    for r in range(rows):
        t = t_ref[first + r]
        base = pl.multiple_of((t // LANES) * LANES, LANES)
        tile = (pl.ds(r, 1), pl.ds(base, LANES))
        e_ref[tile] = jnp.where(lane == t - base,
                                e_ref[tile] - s[r:r + 1], e_ref[tile])

    scale = a_ref[...] / s

    def put(cols):
        c_ref[:, cols] = (e_ref[:, cols] * scale).astype(c_ref.dtype)

    def body(j, carry):
        put(full(j))
        return carry

    lax.fori_loop(0, n_full, body, 0)
    if tail:
        put(last)


def softmax_sums_and_cotangent(x, m, t, a, *, interpret=False):
    """``(s, c)`` for logits ``x [N, V]``, their row maxima ``m [N, 1]``
    (float32), targets ``t [N]`` (int32, inside ``[0, V)``) and row
    weights ``a [N, 1]`` (float32): ``s [N, 1] = sum(exp(x - m))`` in
    float32 and ``c [N, V] = (exp(x - m) / s - onehot(t)) * a`` in
    ``x``'s dtype, its arithmetic in float32.  The shape is one that
    :func:`fits`: ``N`` whole blocks of ``ROWS``, ``V`` at least one
    ``CHUNK`` and otherwise any (the last, partial step of a walk is its
    own)."""
    n, classes = x.shape
    block = lambda i, t: (i, 0)
    vec = pl.BlockSpec((ROWS, 1), block)
    mat = pl.BlockSpec((ROWS, classes), block)
    return pl.pallas_call(
        _softmax_cotangent_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // ROWS,),
            in_specs=[mat, vec, vec], out_specs=[vec, mat],
            # whole lane tiles: the onehot's fix-up takes one
            scratch_shapes=[pltpu.VMEM((ROWS, _lane_tiles(classes)),
                                       jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, classes), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(classes, x.dtype.itemsize)),
        name="_softmax_cotangent_kernel",
        interpret=interpret,
    )(t, x, m, a)


def _nll(x, t):
    """``logsumexp(x) - x[t]`` over axis 1 in plain ``jnp``, float32."""
    # fp32 statistics even for bf16 logits.  The target's logit is a masked
    # row sum, not a gather: a gather cannot fuse into the producer of its
    # operand, so XLA would write the whole float32 log-softmax for it; the
    # comparison against an iota rides in the pass that sums the exponentials
    x = x.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=1)
    classes = lax.broadcasted_iota(t.dtype, x.shape, 1)
    picked = jnp.sum(
        jnp.where(classes == jnp.expand_dims(t, 1), x, 0.0), axis=1)
    return lse - picked


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _weighted_nll_one_read(x, t, a, interpret):
    # with no gradient asked for (evaluation) nothing of the logits' shape
    # is written: the kernel is the rule's forward pass alone
    return a * _nll(x, t)


def _weighted_nll_fwd(x, t, a, interpret):
    from jax.experimental.layout import Layout, with_layout_constraint
    # the kernel reads rows; left alone XLA lays a [4096, 50257] array out
    # columns first and copies it for the kernel.  Rows are also the form
    # in which the head's two backward GEMMs are quickest
    x = with_layout_constraint(x, Layout(major_to_minor=(0, 1)))
    m = jnp.max(x, axis=1, keepdims=True).astype(jnp.float32)
    picked = jnp.take_along_axis(x, t[:, None], axis=1)[:, 0]
    s, c = softmax_sums_and_cotangent(x, m, t, a[:, None],
                                      interpret=interpret)
    nll = (m[:, 0] + jnp.log(s[:, 0])) - picked.astype(jnp.float32)
    return a * nll, (c, nll, t)


def _weighted_nll_bwd(interpret, res, g):
    c, nll, t = res
    # float32 and ONE rounding more, whatever the rows' cotangent; under a
    # mean it is ones and XLA drops product and casts alike
    dx = (c.astype(jnp.float32) * g[:, None]).astype(c.dtype)
    return dx, np.zeros(t.shape, jax.dtypes.float0), g * nll


_weighted_nll_one_read.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


def weighted_nll(x, t, a, *, interpret=False):
    """``a * (logsumexp(x) - x[t])`` in float32 for logits ``x`` with the
    classes along axis 1, targets ``t`` and float32 weights ``a`` over
    the other axes.

    On a TPU (or with ``interpret``, the CPU tests' way to the kernel),
    for ``x [N, V]`` that :func:`fits`, through a ``jax.custom_vjp``
    around :func:`softmax_sums_and_cotangent`: going forwards under
    differentiation ONE read of the logits gives the row sums and the
    cotangent of ``x`` for a cotangent of ones, ``(softmax(x) -
    onehot(t)) * a`` rounded once to ``x``'s dtype, as one array by rows;
    going backwards that array times the rows' cotangent (in float32,
    one rounding more; nothing at all under a mean) is the cotangent of
    ``x``.  A target outside ``[0, V)`` there gives its row no loss and
    no gradient.  Everywhere else plain ``jnp`` and plain autodiff,
    where such a target picks no logit."""
    if (interpret or _on_tpu()) and fits(x.shape, x.dtype):
        inside = (t >= 0) & (t < x.shape[1])
        return _weighted_nll_one_read(
            x, jnp.where(inside, t, 0).astype(jnp.int32),
            jnp.where(inside, a, 0.0), interpret)
    return a * _nll(x, t)
