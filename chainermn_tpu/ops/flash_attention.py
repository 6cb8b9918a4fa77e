"""Fused flash attention — Pallas TPU kernel.

N2/N3-class component (SURVEY.md §2.5): where the reference hand-wrote
CUDA kernels for its hot paths, the TPU rebuild's escape hatch beyond
XLA fusion is Pallas.  Attention is the canonical case: the fused kernel
keeps the [Tq, Tk] score matrix out of HBM entirely — scores live in VMEM
tiles, softmax runs online (running max/normalizer), and the MXU sees one
[BQ, D]×[D, Tk-block] matmul stream per query tile.

``attention(q, k, v)`` dispatches: Pallas kernel on TPU backends, a
jnp reference elsewhere (CPU tests run the kernel in interpreter mode to
pin kernel↔reference equivalence).

The backward is ONE fused one-pass kernel
(:func:`_flash_bwd_fused_kernel`): each (qi, ki) attention tile is
recomputed once — s = q·kᵀ, p = exp(s − lse) — and feeds all three
gradients (dk/dv accumulate across the query loop, dq on the chip
across the key tiles; each leaves the kernel once).

Causal calls walk the lower triangle only: a kernel skips the tiles
wholly above the diagonal, runs an unmasked body on the tiles wholly at
or below it and a masked body on the tiles it crosses
(:func:`_causal_k_tiles` / :func:`_causal_q_tiles`).  So tiles under the
sequence length are what makes a causal call cheap; the log-sum-exp
forward and the backward resolve theirs from a chip sweep keyed on what
the call shows (T, D, causal: :data:`_CAUSAL_BLOCK_TABLE`).  Every tile
is decided in :func:`_flash_tiles`; the next sweep passes its tiles as
arguments (`make sweep-flash`).

Ring-attention composition: ``parallel.ring_attention`` rotates KV blocks
between chips; within a chip this kernel computes each block's
contribution — ICI transfers at the outer level, VMEM tiling at the
inner.
"""

from __future__ import annotations

import collections
import functools
import math
import os
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import role

__all__ = ["attention", "flash_attention", "grouped_attention",
           "self_attention", "xla_attention", "xla_grouped_attention"]

# Both grid dims are embarrassingly parallel (independent programs per
# (batch*head, block) pair).  vmem_limit_bytes raises Mosaic's scoped-VMEM
# cap from its 16 MB default: at long T, XLA can place whole kernel
# outputs in VMEM (observed OOM on v5e at T=8192 with the default).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=100 * 1024 * 1024)
# The fused backward keeps its dq accumulator resident across the
# key-block axis, so that axis runs in order on one core ("arbitrary").
# v5e has one TensorCore a chip: the head axis carries the parallelism.
_BWD_FUSED_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=100 * 1024 * 1024)


def xla_attention(q, k, v, causal=False, scale=None):
    """jnp reference implementation (and non-TPU fallback).

    Dtype discipline: q/k/v keep their storage dtype INTO the matmuls
    (bf16 inputs ride the MXU's native bf16 path) while
    ``preferred_element_type=float32`` makes the accumulator fp32; the
    softmax itself runs in fp32 and its probabilities are cast back to
    the value dtype for the second matmul."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        qpos = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def xla_grouped_attention(q, k, v, scale=None, window=None):
    """jnp reference (and non-TPU fallback) of the grouped causal
    forward: ``q [B, H, T, D]`` over ``k``, ``v`` ``[B, G, T, D]``, query
    head ``a`` reading K/V head ``a // (H // G)``, and under a ``window``
    query i seeing keys ``(i - window, i]`` only.  No K/V head is
    repeated in memory: the query heads of a group are one batched
    product's rows."""
    B, H, T, D = q.shape
    G = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qg = q.reshape(B, G, H // G, T, D)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = lax.broadcasted_iota(jnp.int32, (T, T), 0)
    kpos = lax.broadcasted_iota(jnp.int32, (T, T), 1)
    seen = qpos >= kpos
    if window is not None:
        seen &= qpos - kpos < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, T, D).astype(q.dtype)


def _imin(a, b):
    """min for tile indices: Python ints in the tile-walk helper, traced
    int32 scalars inside the kernels."""
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _imax(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _causal_k_tiles(qi, block_q, block_k, n_kblocks):
    """Key tiles of query tile ``qi`` at a causal call (row i sees keys
    0..i): ``[0, full)`` lie wholly at or below the diagonal and need no
    mask, ``[full, last)`` are crossed by it, ``[last, n_kblocks)`` lie
    wholly above it and are never computed."""
    full = _imin((qi * block_q + 1) // block_k, n_kblocks)
    last = _imin((qi * block_q + block_q + block_k - 1) // block_k,
                 n_kblocks)
    return full, last


def _causal_q_tiles(ki, block_q, block_k, n_qblocks):
    """The same walk seen from key tile ``ki`` (the backward's loop):
    query tiles ``[0, first)`` lie wholly above the diagonal and are
    never computed, ``[first, full)`` are crossed by it,
    ``[full, n_qblocks)`` lie wholly at or below it and need no mask."""
    first = _imin((ki * block_k) // block_q, n_qblocks)
    full = _imin(((ki + 1) * block_k + block_q - 2) // block_q, n_qblocks)
    return first, full


def _band_k_tiles(qi, block_q, block_k, window):
    """A causal call under a ``window`` (row i sees keys ``(i - window,
    i]``): key tiles ``[0, first)`` of query tile ``qi`` lie wholly below
    the band and are never computed, ``[first, inside)`` are crossed by
    its lower edge, the tiles from ``inside`` on lie wholly above that
    edge (:func:`_causal_k_tiles` says which of them the diagonal
    crosses)."""
    first = _imax(qi * block_q - (window - 1), 0) // block_k
    inside = _imax(qi * block_q + block_q - 1 - window + block_k, 0) \
        // block_k
    return first, inside


def _causal_tile_walk(tq, tk, block_q, block_k, window=None):
    """``[(qi, ki, masked)]``: the tiles a causal call computes with
    these blocks, from the forward's bounds; with a ``window``, the
    tiles of the band alone (:func:`_band_k_tiles`).  Pure Python: the
    tests hold it against a brute-force mask and against the backward's
    bounds, and count the share of the square the committed tiles cost."""
    walk = []
    for qi in range(tq // block_q):
        full, last = _causal_k_tiles(qi, block_q, block_k, tk // block_k)
        first, inside = (0, 0) if window is None else \
            _band_k_tiles(qi, block_q, block_k, window)
        walk += [(qi, ki, ki >= full or ki < inside)
                 for ki in range(first, last)]
    return walk


def _scale_folds(scale):
    """Whether ``scale`` is a power of two (1/sqrt(D) at D = 16, 64,
    256): multiplying a bfloat16 or float32 operand by it is then exact,
    so it goes into a ``[block, D]`` operand once instead of into every
    ``[block_q, block_k]`` score tile."""
    return math.frexp(scale)[0] == 0.5


def _fold_scale(x, scale):
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


#: A head whose whole tile walk covers at most this many scores (the
#: square of T = 1024) is walked by ONE program, its loops unrolled at
#: trace time: straight-line code lets the scheduler run one tile's MXU
#: work under another's softmax, each query tile's first key tile needs
#: no rescaling, and there is one grid step a head.  At the cell's shape
#: that alone took the forward from 0.32 to 0.20 ms a call before any
#: tile was skipped (chip sweep, PR 28: tools/flash_budgets.json).  A
#: longer walk keeps one tile row a program and `fori_loop`s: unrolled
#: it would be megabytes of code.
_STATIC_WALK_ELEMS = 1024 * 1024


def _static_walk(tq, tk, block_q, block_k, causal):
    tiles = len(_causal_tile_walk(tq, tk, block_q, block_k)) if causal \
        else (tq // block_q) * (tk // block_k)
    return tiles * block_q * block_k <= _STATIC_WALK_ELEMS


def _loop(lo, hi, body, carry, unroll):
    """``fori_loop``, or (a static walk, whose bounds are Python ints)
    the same loop unrolled at trace time."""
    if unroll:
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _take(ref, idx, h, heads):
    """Head ``h`` of a block's rows ``idx``.  Both training kernels read
    and write a head through this and :func:`_put` alone, so a head's
    products, accumulators and statistics are the same whether it has
    the block to itself (heads-first form: the block as it is) or shares
    a 128-lane column block of ``[B, T, H·D]`` rows with its neighbours.
    There the head is the whole block with the other heads' lanes
    zeroed: every product is then 128 lanes wide and exactly zero
    outside the head's own lanes (a NaN or an infinity in one head of a
    block reaches its neighbours, nothing else does), which costs the
    MXU what a 64-wide one does and no lane is moved.  Taking the head's
    64 lanes by a slice instead read 0.261 against 0.154 ms a forward
    call and 0.380 against 0.359 a backward at GPT-2-medium's shape (my
    chip run, PR 30: tools/flash_budgets.json)."""
    x = ref[idx, :]
    if heads == 1:
        return x
    d = x.shape[-1] // heads
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x,
                     jnp.zeros_like(x))


def _put(ref, idx, h, value):
    """Head ``h``'s result, zero outside its own lanes, into the rows
    ``idx`` of a block: the heads of a block add up."""
    if h == 0:
        ref[idx, :] = value
    else:
        ref[idx, :] += value


def _flash_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, *, heads,
                      block_q, block_k, causal, scale, static_walk):
    """Forward kernel variant that also writes the log-sum-exp row
    statistics (softmax normalizer) needed by the backward kernels.

    A block holds ``heads`` heads side by side in its lanes
    (:func:`_take`): one in the heads-first form, ``128 // D`` in
    the rows form, where the block is a column block of the qkv GEMM's
    own output.  They are walked one after the other, each exactly as a
    block of its own would be.

    A program holds one query tile, or every query tile of its heads
    where the walk is static (:data:`_STATIC_WALK_ELEMS`).  Two loop
    bodies over a query tile's key tiles (:func:`_causal_k_tiles`):
    unmasked for the tiles wholly at or below the diagonal (every tile
    of a non-causal call), masked for those it crosses.  Neither guards
    against an empty row with ``isfinite``: this kernel numbers queries
    and keys from 0, so every row sees key 0, key tile 0 is the first
    one walked (in whichever body), and the running max is finite from
    there on; exp(-inf) = 0 does the rest.  A non-causal call (ring
    blocks, ``Tq != Tk``) masks nothing at all.  `_flash_kernel` keeps
    its guards."""
    n_kblocks = k_ref.shape[0] // block_k
    fold = _scale_folds(scale)

    def q_tile(h, qi, rows):
        # dtype discipline: blocks go into the dots in their STORAGE
        # dtype (bf16 rides the MXU's native path; an f32 upcast would
        # force the 3-pass f32 matmul emulation) with fp32 accumulators
        # via preferred_element_type; the online-softmax state stays fp32
        q = _take(q_ref, rows, h, heads)
        if fold:
            q = _fold_scale(q, scale)
        q_pos = (qi * block_q
                 + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))

        def tile(ki, carry, masked):
            cols = pl.ds(ki * block_k, block_k)
            s = jax.lax.dot_general(q, _take(k_ref, cols, h, heads),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if not fold:
                s = s * scale
            if masked:
                k_pos = (ki * block_k
                         + lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
                s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
            m_new = jnp.max(s, axis=-1, keepdims=True)
            if carry is not None:
                m, l, acc = carry
                m_new = jnp.maximum(m, m_new)
            p = jnp.exp(s - m_new)
            l_new = jnp.sum(p, axis=-1, keepdims=True)
            acc_new = jax.lax.dot_general(
                p.astype(v_ref.dtype), _take(v_ref, cols, h, heads),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if carry is not None:
                corr = jnp.exp(m - m_new)
                l_new = l * corr + l_new
                acc_new = acc * corr + acc_new
            return m_new, l_new, acc_new

        if causal:
            full, last = _causal_k_tiles(qi, block_q, block_k, n_kblocks)
        else:
            full = last = n_kblocks
        # a static walk knows its first tile and starts from it; a loop
        # starts from the empty state, which exp(-inf) = 0 rescales away
        carry = None if static_walk else (
            jnp.full((block_q, 1), -jnp.inf, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, q.shape[-1]), jnp.float32))
        carry = _loop(0, full, functools.partial(tile, masked=False), carry,
                      static_walk)
        if causal:
            carry = _loop(full, last, functools.partial(tile, masked=True),
                          carry, static_walk)
        m, l, acc = carry
        _put(o_ref, rows, h, (acc / l).astype(o_ref.dtype))
        # lse is [heads, rows, 1]: Mosaic requires the block's trailing
        # dims to divide (8, 128) or equal the array dims — a trailing
        # singleton qualifies, a squeezed 1-D block does not
        lse_ref[h, rows, :] = m + jnp.log(l)

    for h in range(heads):
        for j in range(q_ref.shape[0] // block_q):
            q_tile(h, j if static_walk else pl.program_id(1),
                   pl.ds(j * block_q, block_q))


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                            *rest, heads, block_q, block_k, causal, scale,
                            static_walk, with_g_lse):
    """Fused backward: ONE pass over the (qi, ki) tiles per key tile.

    Each (qi, ki) tile is recomputed ONCE (the s = q·kᵀ dot, the mask,
    exp(s − lse), the g·vᵀ dot) and all three gradient contributions
    leave together:

        dv  += pᵀ g           (carried over this key tile's query loop)
        dk  += dsᵀ q          (carried over this key tile's query loop)
        dq[qi] += ds·k        (float32, on the chip, across the key tiles)

    ``rest`` is ``[g_lse_ref,] dq_ref, dk_ref, dv_ref[, dq_acc_ref]``.
    ``delta_i = rowsum(g_i · out_i)`` is made here from the blocks the
    kernel holds anyway (once a query tile where the walk is static,
    once a tile otherwise), less the log-sum-exp's own cotangent where
    the call has one (``with_g_lse``: since ∂lse_i/∂s_ij = p_ij, its
    whole contribution is ``ds += g_lse_i · p``).  A block holds
    ``heads`` heads side by side (:func:`_take`), walked one after
    the other, as in the forward.

    A program holds one key tile, or every key tile of its heads where
    the walk is static (:data:`_STATIC_WALK_ELEMS`); dq then adds up in
    values, one a query tile (through a VMEM scratch the same walk read
    0.03 ms a call slower at the cell's shape: my chip runs, PR 28).
    Otherwise the grid is
    ``(batch·blocks, key tiles)`` with the key-tile axis ``arbitrary``:
    the programs of one block run in order on one core, so a
    ``[heads, Tq, D]`` float32 VMEM scratch (``dq_acc_ref``) is zeroed
    at the block's first key tile, every later one adds to it, and the
    last writes dq out (``dq_ref``'s block ignores the key-tile index,
    so it goes to HBM when the block changes).  Either way dq leaves the
    kernel once, scaled and in the gradient's dtype: no per-key-tile
    float32 dq plane in HBM, no zero-fill of one, no XLA sum over it.

    Two loop bodies over the query tiles (:func:`_causal_q_tiles`), as in
    the forward: the tiles the diagonal crosses pay two iotas, a compare
    and a select on p; the others pay nothing.  ``lse`` is finite for
    every row (see `_flash_kernel_lse`), so no ``isfinite`` either.  Per
    tile: 5 MXU dots + 1 exp (a dq pass and a dkv pass of their own
    would run 8 + 2; docs/performance.md section 6).
    """
    g_lse_ref = rest[0] if with_g_lse else None
    dq_ref, dk_ref, dv_ref, *dq_acc_ref = rest[with_g_lse:]
    tq = q_ref.shape[0]
    n_qblocks = tq // block_q
    n_here = k_ref.shape[0] // block_k
    fold = _scale_folds(scale)

    def dq_out(dq):
        return (dq if fold else dq * scale).astype(dq_ref.dtype)

    def head(h):
        # a static walk's dq and delta, by query tile
        dq_tiles = [None] * n_qblocks
        delta_tiles = [None] * n_qblocks

        def delta(qi, rows, g_blk):
            if static_walk and delta_tiles[qi] is not None:
                return delta_tiles[qi]
            dl = jnp.sum(g_blk.astype(jnp.float32)
                         * _take(o_ref, rows, h, heads)
                         .astype(jnp.float32),
                         axis=-1, keepdims=True)
            if with_g_lse:
                dl = dl - g_lse_ref[h, rows, :]
            if static_walk:
                delta_tiles[qi] = dl
            return dl

        def k_tile(ki, cols):
            # storage dtype into the dots (see fwd kernel)
            v = _take(v_ref, cols, h, heads)
            # with scale folded into k both s and this tile's dq come
            # out scaled
            k = _take(k_ref, cols, h, heads)
            if fold:
                k = _fold_scale(k, scale)
            k_pos = (ki * block_k
                     + lax.broadcasted_iota(jnp.int32, (1, block_k), 1))

            if not static_walk:
                @pl.when(ki == 0)
                def _():
                    dq_acc_ref[0][h] = jnp.zeros(dq_acc_ref[0].shape[1:],
                                                 jnp.float32)

            def tile(qi, carry, masked):
                rows = pl.ds(qi * block_q, block_q)
                q_blk = _take(q_ref, rows, h, heads)
                g_blk = _take(g_ref, rows, h, heads)
                s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if not fold:
                    s = s * scale
                # ONCE; lse arrives [bq, 1]
                p = jnp.exp(s - lse_ref[h, rows, :])
                if masked:
                    q_pos = (qi * block_q + lax.broadcasted_iota(
                        jnp.int32, (block_q, 1), 0))
                    p = jnp.where(q_pos >= k_pos, p, 0.0)
                dv = jax.lax.dot_general(
                    p.astype(g_blk.dtype), g_blk, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                gv = jax.lax.dot_general(g_blk, v, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = (p * (gv - delta(qi, rows, g_blk))).astype(q_blk.dtype)
                dk = jax.lax.dot_general(
                    ds, q_blk, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                if not static_walk:
                    dq_acc_ref[0][h, rows, :] += dq
                elif dq_tiles[qi] is None:
                    dq_tiles[qi] = dq
                else:
                    dq_tiles[qi] += dq
                if carry is not None:
                    dk, dv = carry[0] + dk, carry[1] + dv
                return dk, dv

            # as in the forward: a static walk starts from its first tile
            carry = None if static_walk else (
                jnp.zeros(k.shape, jnp.float32),) * 2
            if causal:
                first, full = _causal_q_tiles(ki, block_q, block_k,
                                              n_qblocks)
                carry = _loop(first, full,
                              functools.partial(tile, masked=True), carry,
                              static_walk)
            else:
                full = 0
            carry = _loop(full, n_qblocks,
                          functools.partial(tile, masked=False), carry,
                          static_walk)
            # a causal key tile past the last query row is seen by nobody
            dk, dv = carry if carry is not None else (
                jnp.zeros(k.shape, jnp.float32),) * 2
            # ds came from s = scale · q·kᵀ, so dk = scale · Σ dsᵀq; dq
            # likewise, unless the folded k already carried the scale
            # into ds·k
            _put(dk_ref, cols, h, (dk * scale).astype(dk_ref.dtype))
            _put(dv_ref, cols, h, dv.astype(dv_ref.dtype))

            if not static_walk:
                @pl.when(ki == pl.num_programs(1) - 1)
                def _():
                    _put(dq_ref, slice(None), h, dq_out(dq_acc_ref[0][h]))

        for j in range(n_here):
            k_tile(j if static_walk else pl.program_id(1),
                   pl.ds(j * block_k, block_k))
        if static_walk:
            # every query tile sees key tile 0, so none is left None
            for qi, dq in enumerate(dq_tiles):
                _put(dq_ref, pl.ds(qi * block_q, block_q), h, dq_out(dq))

    for h in range(heads):
        head(h)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, scale,
                  q_offset_blocks):
    """One (batch*head, q-block) program: stream K/V blocks through VMEM
    with the online-softmax recurrence."""
    bq, d = q_ref.shape
    tk = k_ref.shape[0]
    qi = pl.program_id(1)

    q = q_ref[:]          # storage dtype into the dots (see _flash_kernel_lse)
    m = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    n_kblocks = tk // block_k
    q_pos = (qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0))

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, block_k]
        if causal:
            k_pos = (ki * block_k
                     + lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # only blocks that intersect the causal triangle contribute
        last_needed = jnp.minimum(
            (qi * bq + bq + block_k - 1) // block_k, n_kblocks)
    else:
        last_needed = n_kblocks
    m, l, acc = jax.lax.fori_loop(0, last_needed, body, (m, l, acc))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _band_tiles(tq, block_q, block_k, window):
    """The most key tiles any query tile's band touches (a trace-time
    number: the window kernel walks this many from its first tile)."""
    return max(collections.Counter(
        qi for qi, _, _ in _causal_tile_walk(tq, tq, block_q, block_k,
                                             window)).values())


def _flash_window_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, scale,
                         window, n_band):
    """`_flash_kernel` under a causal band: query i sees keys ``(i -
    window, i]``, numbered alike from 0 (a whole prompt over itself).
    One (head, query tile) program walks ``n_band`` key tiles from the
    first one its band touches (:func:`_band_k_tiles`), straight-line:
    the tiles wholly below the band are never read, and a step past the
    diagonal (the first query tiles, whose band is cut short by key 0)
    reads the last tile again under a mask that hides all of it.  Every
    tile is masked on both edges, so the guards against an empty row
    stay."""
    bq, d = q_ref.shape
    qi = pl.program_id(1)
    n_kblocks = k_ref.shape[0] // block_k
    q = q_ref[:]
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    first, _ = _band_k_tiles(qi, bq, block_k, window)
    m = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    for t in range(n_band):
        ki = first + t
        cols = pl.ds(jnp.minimum(ki, n_kblocks - 1) * block_k, block_k)
        s = jax.lax.dot_general(
            q, k_ref[cols, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (1, block_k), 1)
        s = jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s,
                      -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[cols, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m = m_new
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# Adaptive-default tile candidates, largest first: a tile is the largest
# candidate that divides T.  This is what the serving forward
# (`_flash_kernel`: one dynamic loop a query tile) resolves, and what a
# length no sweep has visited keeps.  Every loop iteration there costs
# about 0.4 us of latency that nothing hides (128 x 128 tiles at
# T = 1024, D = 64: 0.98 ms a forward call against 0.35 with one
# 1024 x 1024 tile; chip sweep, PR 28), so under dynamic loops the
# largest tile wins although a causal head then computes its whole
# square.  VMEM cost at 1024: the f32 score/probability tiles are 4 MB
# each, inside the kernel's 100 MB scoped-VMEM cap.
_BLOCK_CANDIDATES = (1024, 512, 256, 128)


def _adaptive_block(t):
    """Largest candidate tile that divides T (so the grid stays exact);
    falls back to the legacy 128 (clamped to T by the callers) when T
    is not a multiple of any candidate — e.g. T=64 keeps the old
    min(128, T) behavior, odd T keeps its XLA-fallback path."""
    if t is not None:
        for b in _BLOCK_CANDIDATES:
            if t % b == 0:
                return b
    return 128


#: What the chip sweep chose for a CAUSAL call with Tq == Tk == T, keyed
#: by what the call shows, (T, D): ``{"fwd": (block_q, block_k)}`` for
#: the log-sum-exp forward, ``"bwd"`` for the fused backward.  At
#: (1024, 64), GPT-2-medium's training shape ([4, 16, 1024, 64]
#: bfloat16), 256 x 256 tiles walk 10 of 16 tiles, 0.625 of the square,
#: and ran the forward in 0.16 ms a call and the backward in 0.35
#: (1024 x 1024: 0.20 and 0.45; the parent's kernels 0.37 and 0.53);
#: every pair tried is in tools/flash_budgets.json.  Only walks that
#: :func:`_static_walk` unrolls gain from tiles under T: at T = 1024
#: the backward's dynamic walk read 0.52 ms a call with one
#: 1024 x 1024 tile and 0.53-0.57 with smaller ones (chip sweep, PR 28).
#: Lengths 2048-16384 are unswept and keep :func:`_adaptive_block`.
_CAUSAL_BLOCK_TABLE = {
    (1024, 64): {"fwd": (256, 256), "bwd": (256, 256)},
}


def _flash_tiles(leg, tq, tk, d=None, causal=False, block_q=None,
                 block_k=None):
    """THE tile decision, a function of what the call shows: an explicit
    argument, else what the chip sweep chose for this ``leg`` (``"fwd"``:
    the log-sum-exp forward, ``"bwd"``: the fused backward) of a causal
    ``Tq == Tk`` call (:data:`_CAUSAL_BLOCK_TABLE`), else
    :func:`_adaptive_block`.  ``leg=None`` (the serving forward, which is
    shown neither D nor causal) is never in the table."""
    swept = _CAUSAL_BLOCK_TABLE.get((tq, d), {}).get(leg) \
        if causal and tq == tk else None
    default_q, default_k = swept or (_adaptive_block(tq),
                                     _adaptive_block(tk))
    return (default_q if block_q is None else block_q,
            default_k if block_k is None else block_k)


def _flash_blocks(block_q=None, block_k=None, tq=None, tk=None):
    """Tiles of the serving forward (`flash_attention`), and the pair a
    shape is validated with before it is given to the kernels: arguments,
    else :func:`_adaptive_block` over the given Tq/Tk."""
    return _flash_tiles(None, tq, tk, block_q=block_q, block_k=block_k)


def _on_tpu():
    return jax.default_backend() == "tpu"


def _interpret_forced():
    """CHAINERMN_TPU_FLASH_INTERPRET=1 routes the `attention` /
    `attention_with_lse` dispatchers through the Pallas kernels in
    interpreter mode on the CPU backend — how the CPU tier-1 suite
    drives the ring/Ulysses consumers through the real custom-VJP
    backward instead of the blockwise-jnp fallback.  On a tpu backend
    it is an error: the chip compiles the kernels, it never interprets
    them."""
    forced = os.environ.get("CHAINERMN_TPU_FLASH_INTERPRET", "0") == "1"
    if forced and _on_tpu():
        raise RuntimeError(
            "CHAINERMN_TPU_FLASH_INTERPRET=1 on a tpu backend: interpret "
            "mode is the CPU rehearsal path; unset it to run the "
            "compiled Pallas kernels")
    return forced


_WARNED_FALLBACK = set()


def _warn_once(key, message):
    """On the tpu backend a shape the flash kernels cannot take as asked
    is visible: one warning per ``key`` at trace time.  Off the chip the
    non-kernel path is the normal one and stays silent."""
    if not _on_tpu() or key in _WARNED_FALLBACK:
        return
    _WARNED_FALLBACK.add(key)
    warnings.warn("flash attention: " + message, UserWarning, stacklevel=4)


def _warn_fallback(q, k, path):
    _warn_once(
        (tuple(q.shape), tuple(k.shape), path),
        f"q{list(q.shape)} k{list(k.shape)} does not tile (T must be a "
        f"multiple of its block); running {path} instead of the Pallas "
        "kernels")


#: Tiles of the window kernel, where they divide T: its walk is
#: straight-line, so tiles under the window cost no loop latency, and at
#: 256 a band of 512 touches 3 tiles a query tile (2/3 of what it
#: computes lies inside the band; at 512 x 512 it would be 1/3).
_WINDOW_BLOCK = 256


@role("attn")
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False, window=None):
    """Fused attention via Pallas.  ``q``: ``[B, H, T, D]``; ``k``, ``v``:
    ``[B, G, T, D]`` with ``G`` dividing ``H``: query head ``a`` reads
    the K/V block of head ``a // (H // G)``, which is never repeated in
    memory (``G = H`` is plain multi-head attention).  ``window``: a
    causal ``Tq == Tk`` call in which query i sees keys ``(i - window,
    i]`` only; it lowers as `_flash_window_kernel`, which never walks a
    tile wholly below the band.  Default block sizes come from
    :func:`_flash_blocks`."""
    B, H, Tq, D = q.shape
    G, Tk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if window is not None:
        if not causal or Tq != Tk:
            raise ValueError("a window needs a causal Tq == Tk call")
        if block_q is None and block_k is None \
                and Tq % _WINDOW_BLOCK == 0:
            block_q = block_k = _WINDOW_BLOCK
    block_q, block_k = _flash_blocks(block_q, block_k, tq=Tq, tk=Tk)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k:
        _warn_fallback(q, k, "XLA attention")
        if window is None and G == H:
            return xla_attention(q, k, v, causal=causal, scale=scale)
        return xla_grouped_attention(q, k, v, scale=scale, window=window)

    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * G, Tk, D)
    vr = v.reshape(B * G, Tk, D)

    if G == H:
        def kv_head(b, i):
            return (b, 0, 0)
    else:
        def kv_head(b, i):
            return (b // H * G + b % H // (H // G), 0, 0)
    if window is None:
        kernel = functools.partial(_flash_kernel, block_k=block_k,
                                   causal=causal, scale=scale,
                                   q_offset_blocks=0)
    else:
        kernel = functools.partial(
            _flash_window_kernel, block_k=block_k, scale=scale,
            window=window,
            n_band=_band_tiles(Tq, block_q, block_k, window))
    out = pl.pallas_call(
        kernel,
        name="_flash_kernel" if window is None else "_flash_window_kernel",
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tk, D), kv_head),
            pl.BlockSpec((None, Tk, D), kv_head),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D)


def _column_spec(rows, width, blocks, part, whole):
    """``(rows, width)`` blocks of an ``[N, T, columns]`` operand: the
    program ``(b, i)`` takes batch row ``b // blocks`` and column block
    ``part · blocks + b % blocks`` (``part``: which of q, k, v, where the
    three are one array), all of T (``whole``) or its ``i``-th rows.
    Heads-first operands are the case ``blocks = 1``, ``part = 0``."""
    return pl.BlockSpec(
        (None, rows, width),
        lambda b, i: (b // blocks, 0 if whole else i,
                      part * blocks + b % blocks))


def _stat_spec(rows, heads, blocks, whole):
    """The same walk over an ``[N, heads a row, T, 1]`` row statistic."""
    return pl.BlockSpec(
        (None, heads, rows, 1),
        lambda b, i: (b // blocks, b % blocks, 0 if whole else i, 0))


def _call_geometry(q, d, heads, fused):
    """``(batch rows, columns of one of q, k, v, column blocks a row)``
    of a call whose operands are ``[N, T, columns]``."""
    columns = q.shape[-1] // (3 if fused else 1)
    return q.shape[0], columns, columns // (heads * d)


@functools.partial(jax.jit, static_argnames=(
    "d", "heads", "fused", "block_q", "block_k", "causal", "scale",
    "static_walk", "interpret"))
def _lse_forward_call(q, k, v, *, d, heads, fused, block_q, block_k,
                      causal, scale, static_walk, interpret):
    """The forward's one ``pallas_call``.  A jit of its own, everything
    but the operands static: the 24 layers of a step then share ONE
    trace of the kernel and one lowering, where each call site used to
    trace its own (an unrolled walk of ten tiles is ten times the
    equations; the step's set-up rose by 7 s until this: my chip runs,
    PR 28).

    Two forms, one kernel.  Heads-first: q, k, v are ``[B·H, T, D]``,
    ``heads = 1``.  Rows (``fused``): q, k and v are ONE array, the qkv
    GEMM's own ``[B, T, 3·H·D]`` given three times, a block is a
    ``heads · d``-lane column block of it, and the output is
    ``[B, T, H·D]``, what the next GEMM takes.  Returns ``(out, lse
    [N, heads a row, T, 1])``."""
    n, columns, blocks = _call_geometry(q, d, heads, fused)
    Tq, Tk = q.shape[1], k.shape[1]
    width = heads * d
    q_rows = Tq if static_walk else block_q   # of q, in one program
    parts = (0, 1, 2) if fused else (0, 0, 0)
    return pl.pallas_call(
        functools.partial(_flash_kernel_lse, heads=heads, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          static_walk=static_walk),
        name="_flash_kernel_lse",
        grid=(n * blocks, Tq // q_rows),
        in_specs=[
            _column_spec(q_rows, width, blocks, parts[0], False),
            _column_spec(Tk, width, blocks, parts[1], True),
            _column_spec(Tk, width, blocks, parts[2], True),
        ],
        out_specs=[
            _column_spec(q_rows, width, blocks, 0, False),
            _stat_spec(q_rows, heads, blocks, False),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, Tq, columns), q.dtype),
            jax.ShapeDtypeStruct((n, blocks * heads, Tq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v)


def _lse_tiles(leg, tq, tk, d, causal, block_q, block_k):
    """``(block_q, block_k)`` of a training kernel's call:
    :func:`_flash_tiles` clamped to the lengths."""
    block_q, block_k = _flash_tiles(leg, tq, tk, d, causal, block_q,
                                    block_k)
    return min(block_q, tq), min(block_k, tk)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None, interpret=False):
    """Forward kernel returning (out, lse [B, H, Tq]).  Tiles come from
    :func:`_flash_tiles`; the callers have validated that the shape
    tiles."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block_q, block_k = _lse_tiles("fwd", Tq, Tk, D, causal, block_q,
                                  block_k)
    out, lse = _lse_forward_call(
        q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D), d=D, heads=1, fused=False,
        block_q=block_q, block_k=block_k,
        causal=causal, scale=scale, interpret=interpret,
        static_walk=_static_walk(Tq, Tk, block_q, block_k, causal))
    return out.reshape(B, H, Tq, D), lse.reshape(B, H, Tq)


@functools.partial(jax.jit, static_argnames=(
    "d", "heads", "fused", "block_q", "block_k", "causal", "scale",
    "static_walk", "interpret"))
def _fused_backward_call(q, k, v, g, out, lse, g_lse, *, d, heads, fused,
                         block_q, block_k, causal, scale, static_walk,
                         interpret):
    """The fused backward's one ``pallas_call``, a jit of its own and in
    the two forms :func:`_lse_forward_call` has: ``g``, ``out`` and the
    three gradients are ``[N, T, columns of one of q, k, v]``, ``lse``
    and ``g_lse`` (or ``None``) ``[N, heads a row, T, 1]``."""
    n, columns, blocks = _call_geometry(q, d, heads, fused)
    Tq, Tk = q.shape[1], k.shape[1]
    width = heads * d
    k_rows = Tk if static_walk else block_k   # of k and v, in one program
    parts = (0, 1, 2) if fused else (0, 0, 0)
    with_g_lse = g_lse is not None
    whole = _column_spec(Tq, width, blocks, 0, True)
    stat = _stat_spec(Tq, heads, blocks, True)
    here = _column_spec(k_rows, width, blocks, 0, False)
    grad = jax.ShapeDtypeStruct((n, Tq, columns), q.dtype)
    return pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, heads=heads,
                          block_q=block_q, block_k=block_k, causal=causal,
                          scale=scale, static_walk=static_walk,
                          with_g_lse=with_g_lse),
        name="_flash_bwd_fused_kernel",
        grid=(n * blocks, Tk // k_rows),
        in_specs=[
            _column_spec(Tq, width, blocks, parts[0], True),
            _column_spec(k_rows, width, blocks, parts[1], False),
            _column_spec(k_rows, width, blocks, parts[2], False),
            whole, whole, stat] + [stat] * with_g_lse,
        out_specs=[whole, here, here],
        out_shape=[grad,
                   jax.ShapeDtypeStruct((n, Tk, columns), k.dtype),
                   jax.ShapeDtypeStruct((n, Tk, columns), v.dtype)],
        scratch_shapes=([] if static_walk
                        else [pltpu.VMEM((heads, Tq, width), jnp.float32)]),
        interpret=interpret,
        compiler_params=_BWD_FUSED_COMPILER_PARAMS,
    )(q, k, v, g, out, lse, *([g_lse] * with_g_lse))


def _bwd_tiles(tq, tk, d, causal, block_q, block_k, bwd_block_q,
               bwd_block_k):
    """The fused backward's tiles: ``bwd_block_q``/``bwd_block_k``, else
    what :func:`_flash_tiles` resolves for the ``"bwd"`` leg;
    ``block_q``/``block_k`` are the pair the shape was validated with
    (:func:`_flash_blocks`) and take over where the backward's own tiles
    do not divide this T (ragged lengths reached with explicit forward
    blocks)."""
    block_q, block_k = _flash_blocks(block_q, block_k, tq=tq, tk=tk)
    bq, bk = _lse_tiles("bwd", tq, tk, d, causal, bwd_block_q, bwd_block_k)
    if tq % bq or tk % bk:
        bq, bk = min(block_q, tq), min(block_k, tk)
    return bq, bk


def flash_attention_bwd(q, k, v, out, lse, g, causal=False, scale=None,
                        block_q=None, block_k=None, interpret=False,
                        g_lse=None, bwd_block_q=None, bwd_block_k=None):
    """Backward: (dq, dk, dv) with flash memory behavior, through the
    fused one-pass kernel (:func:`_flash_bwd_fused_kernel`): one
    recompute of each (qi, ki) attention tile feeds dq, dk and dv
    together.  Its tiles: :func:`_bwd_tiles`.

    ``g_lse``: optional cotangent of the lse output, which the kernel
    takes off ``delta``.  Ring attention depends on this: the
    cross-block merge weights are functions of each block's lse."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bq, bk = _bwd_tiles(Tq, Tk, D, causal, block_q, block_k, bwd_block_q,
                        bwd_block_k)

    def stat(x):  # trailing singleton: Mosaic-legal
        return x.reshape(B * H, 1, Tq, 1).astype(jnp.float32)

    dq, dk, dv = _fused_backward_call(
        q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D), g.reshape(B * H, Tq, D),
        out.reshape(B * H, Tq, D), stat(lse),
        None if g_lse is None else stat(g_lse), d=D, heads=1, fused=False,
        block_q=bq, block_k=bk, causal=causal, scale=scale,
        interpret=interpret,
        static_walk=_static_walk(Tq, Tk, bq, bk, causal))
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_diff(q, k, v, causal, scale, interpret):
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           interpret=interpret)


def _flash_diff_fwd(q, k, v, causal, scale, interpret):
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _flash_blocks(tq=Tq, tk=Tk)
    if Tq % min(bq, Tq) or Tk % min(bk, Tk):
        # irregular shapes: XLA fallback for both directions
        _warn_fallback(q, k, "XLA attention (forward and backward)")
        out = xla_attention(q, k, v, causal=causal, scale=scale)
        return out, (q, k, v, None, None)
    # the log-sum-exp forward resolves its own tiles (swept where the
    # call's shape was, else these); so does the backward, from the same
    # shapes
    out, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    if lse is None:
        _, vjp = jax.vjp(
            lambda q, k, v: xla_attention(q, k, v, causal=causal,
                                          scale=scale), q, k, v)
        return vjp(g)
    return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                               scale=scale, interpret=interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


@role("attn")
def attention(q, k, v, causal=False, scale=None):
    """Dispatch: Pallas kernels on TPU (flash forward AND fused backward
    via custom VJP), XLA reference elsewhere.
    CHAINERMN_TPU_FLASH_INTERPRET=1 forces the Pallas path in
    interpreter mode on any backend (CPU kernel tests)."""
    interpret = _interpret_forced()  # raises on a tpu backend
    if interpret or _on_tpu():
        return _flash_diff(q, k, v, causal, scale, interpret)
    return xla_attention(q, k, v, causal=causal, scale=scale)


@role("attn")
def grouped_attention(q, k, v, scale=None, window=None):
    """Causal attention of whole prompts with grouped K/V heads, and a
    ``window`` where the layer has one: ``q [B, H, T, D]`` over ``k``,
    ``v`` ``[B, G, T, D]``.  Forward only (the serving prefills; no
    backward is defined): the Pallas forward on TPU, under a window
    `_flash_window_kernel`, else :func:`xla_grouped_attention`."""
    interpret = _interpret_forced()
    if interpret or _on_tpu():
        return flash_attention(q, k, v, causal=True, scale=scale,
                               interpret=interpret, window=window)
    return xla_grouped_attention(q, k, v, scale=scale, window=window)


# ---------------------------------------------------------------------------
# self-attention over the qkv GEMM's own rows
# ---------------------------------------------------------------------------

def split_heads(qkv, n_heads):
    """``[B, T, 3·H·D]`` (columns ordered ``(3, H, D)``, what one qkv
    GEMM emits) -> q, k, v as ``[B, H, T, D]``.  On the chip each is a
    physical rewrite: a minor dimension of 64 is stored in 128-lane
    tiles."""
    B, T, C = qkv.shape
    qkv = qkv.reshape(B, T, 3, n_heads, C // (3 * n_heads))
    return [jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3)]


def merge_heads(out):
    """``[B, H, T, D]`` -> ``[B, T, H·D]``, what the output GEMM takes."""
    B, H, T, D = out.shape
    return jnp.moveaxis(out, 2, 1).reshape(B, T, H * D)


def _rows_heads(qkv, n_heads):
    """Heads a 128-lane column block where the training kernels can read
    ``qkv [B, T, 3·H·D]`` as it lies, else 0: a block is whole heads
    (``128 % D == 0``), the heads fill whole blocks
    (``H·D % 128 == 0``), and T tiles."""
    T, d = qkv.shape[1], qkv.shape[2] // (3 * n_heads)
    block = min(_flash_blocks(tq=T, tk=T)[0], T)
    if 128 % d or (n_heads * d) % 128 or T % block:
        return 0
    return 128 // d


def flash_self_attention_fwd(qkv, n_heads, causal=False, scale=None,
                             block_q=None, block_k=None, interpret=False):
    """The log-sum-exp forward in its rows form: ``qkv [B, T, 3·H·D]``
    -> ``(out [B, T, H·D], lse [B, H, T, 1])``.  The caller has asked
    :func:`_rows_heads`."""
    T, d = qkv.shape[1], qkv.shape[2] // (3 * n_heads)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_q, block_k = _lse_tiles("fwd", T, T, d, causal, block_q, block_k)
    return _lse_forward_call(
        qkv, qkv, qkv, d=d, heads=_rows_heads(qkv, n_heads), fused=True,
        block_q=block_q, block_k=block_k, causal=causal, scale=scale,
        interpret=interpret,
        static_walk=_static_walk(T, T, block_q, block_k, causal))


def flash_self_attention_bwd(qkv, out, lse, g, n_heads, causal=False,
                             scale=None, interpret=False, bwd_block_q=None,
                             bwd_block_k=None):
    """The fused backward in its rows form: ``g [B, T, H·D]``, as the
    output GEMM's backward leaves it -> the ``[B, T, 3·H·D]`` cotangent
    the qkv GEMM's backward contracts over.  dq, dk, dv leave the kernel
    as three ``[B, T, H·D]`` arrays and are joined on the last axis: no
    gradient is padded to the whole cotangent, nothing is transposed."""
    T, d = qkv.shape[1], qkv.shape[2] // (3 * n_heads)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq, bk = _bwd_tiles(T, T, d, causal, None, None, bwd_block_q,
                        bwd_block_k)
    dq, dk, dv = _fused_backward_call(
        qkv, qkv, qkv, g, out, lse, None, d=d,
        heads=_rows_heads(qkv, n_heads), fused=True, block_q=bq,
        block_k=bk, causal=causal, scale=scale, interpret=interpret,
        static_walk=_static_walk(T, T, bq, bk, causal))
    return jnp.concatenate([dq, dk, dv], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_rows_diff(qkv, n_heads, causal, scale, interpret):
    return flash_self_attention_fwd(qkv, n_heads, causal=causal,
                                    scale=scale, interpret=interpret)[0]


def _flash_rows_fwd(qkv, n_heads, causal, scale, interpret):
    out, lse = flash_self_attention_fwd(qkv, n_heads, causal=causal,
                                        scale=scale, interpret=interpret)
    # nothing in [B, H, T, D] is kept for the backward
    return out, (qkv, out, lse)


def _flash_rows_bwd(n_heads, causal, scale, interpret, res, g):
    qkv, out, lse = res
    return (flash_self_attention_bwd(qkv, out, lse, g, n_heads,
                                     causal=causal, scale=scale,
                                     interpret=interpret),)


_flash_rows_diff.defvjp(_flash_rows_fwd, _flash_rows_bwd)


@role("attn")
def self_attention(qkv, n_heads, causal=False, scale=None):
    """Self-attention between two GEMMs: ``qkv [B, T, 3·H·D]`` (columns
    ordered ``(3, H, D)``) -> ``[B, T, H·D]``.

    Where the kernels run (as for :func:`attention`) and the shape
    allows it (:func:`_rows_heads`), both training kernels read the qkv
    GEMM's rows as they lie and write the rows the output GEMM reads: no
    transpose, pad, slice or relayout on either side, forwards or
    backwards.  Any other call splits the heads and takes
    :func:`attention`; on the chip it says so once."""
    interpret = _interpret_forced()  # raises on a tpu backend
    if interpret or _on_tpu():
        if _rows_heads(qkv, n_heads):
            return _flash_rows_diff(qkv, n_heads, causal, scale, interpret)
        _warn_once(
            (tuple(qkv.shape), n_heads, "rows"),
            f"qkv{list(qkv.shape)} with {n_heads} heads cannot be read as "
            "rows (a 128-lane block must hold whole heads, the heads fill "
            "whole blocks, and T must tile); q, k, v and the result go "
            "through [B, H, T, D] instead")
    return merge_heads(attention(*split_heads(qkv, n_heads), causal=causal,
                                 scale=scale))


# ---------------------------------------------------------------------------
# (out, lse) attention — the composable block primitive for ring/Ulysses
# ---------------------------------------------------------------------------

def _blockwise_attention_lse_jnp(q, k, v, causal, scale, block_k=512):
    """Blockwise jnp (out, lse): scans KV blocks with the online-softmax
    recurrence — never materializes a [Tq, Tk] score matrix.  Fallback
    for non-TPU backends and irregular shapes; differentiable through
    the scan."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    block_k = min(block_k, Tk)
    if Tk % block_k:
        # pad KV to a block multiple; padded keys are masked out below —
        # NEVER fall back to one full-width block (that would materialize
        # the [Tq, Tk] scores this function exists to avoid)
        pad = block_k - Tk % block_k
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Tk_pad = k.shape[2]
    nb = Tk_pad // block_k
    ks = jnp.moveaxis(k.reshape(B, H, nb, block_k, D), 2, 0)
    vs = jnp.moveaxis(v.reshape(B, H, nb, block_k, D), 2, 0)
    q_pos = lax.broadcasted_iota(jnp.int32, (Tq, 1), 0)

    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, bi = blk
        # storage dtype into the matmul (bf16 MXU path), fp32 accumulator
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        k_pos = (bi * block_k
                 + lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
        valid = k_pos < Tk  # mask padded keys
        if causal:
            valid = valid & (q_pos >= k_pos)
        if causal or Tk != Tk_pad:
            s = jnp.where(valid[None, None], s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Tq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, acc0),
                              (ks, vs, jnp.arange(nb)))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe).astype(q.dtype)
    m_fin = jnp.where(jnp.isfinite(m), m, 0.0)
    lse = (m_fin + jnp.log(l_safe))[..., 0]
    lse = jnp.where(jnp.isfinite(m[..., 0]), lse, -jnp.inf)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse_diff(q, k, v, causal, scale, interpret):
    out, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret)
    return out, lse


def _flash_lse_fwd(q, k, v, causal, scale, interpret):
    out, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                   interpret=interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, interpret, res, cots):
    q, k, v, out, lse = res
    g, g_lse = cots
    return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                               scale=scale, interpret=interpret,
                               g_lse=g_lse)


_flash_lse_diff.defvjp(_flash_lse_fwd, _flash_lse_bwd)


@role("attn")
def attention_with_lse(q, k, v, causal=False, scale=None):
    """Differentiable blockwise attention returning ``(out, lse)``.

    ``lse`` (log-sum-exp softmax normalizer, [B, H, Tq], fp32) is what
    lets independently-computed attention blocks be merged exactly —
    ring attention's cross-chip recurrence (`parallel.ring_attention`)
    and any flash-style composition build on it.  Dispatch: Pallas
    kernels on TPU (128-aligned shapes), blockwise jnp otherwise —
    neither path materializes a [Tq, Tk] score matrix.
    """
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    Tq, Tk = q.shape[2], k.shape[2]
    bq, bk = _flash_blocks(tq=Tq, tk=Tk)
    interpret = _interpret_forced()  # raises on a tpu backend
    if interpret or _on_tpu():
        if Tq % min(bq, Tq) == 0 and Tk % min(bk, Tk) == 0:
            return _flash_lse_diff(q, k, v, causal, scale, interpret)
        _warn_fallback(q, k, "blockwise jnp attention")
    return _blockwise_attention_lse_jnp(q, k, v, causal, scale)


@role("attn")
def blockwise_attention(q, k, v, causal=False, scale=None):
    """Memory-bounded attention (no [Tq, Tk] materialization on any
    backend): flash kernel on TPU, blockwise jnp scan elsewhere."""
    return attention_with_lse(q, k, v, causal=causal, scale=scale)[0]
