"""The gated delta rule: a linear-attention layer whose cache is one
recurrent state a sequence.

A head keeps a state ``S`` (``dk x dv``, float32).  Token ``t`` brings a
query and a key ``q_t``, ``k_t`` (``dk``), a value ``v_t`` (``dv``), a
log decay ``g_t <= 0`` and a write strength ``beta_t``:

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Three forms of it:

* :func:`gated_delta_recurrence`: the equations as they stand, a
  ``lax.scan`` over tokens (the tests' yardstick);
* :func:`gated_delta_chunked`: what a prompt runs.  Inside a chunk of
  ``C`` tokens (``G`` the running sum of ``g`` there) the recurrence
  unrolls into products: ``A = strict_tril(diag(beta) (K K^T *
  exp(G_i - G_j)))``, ``T = (I + A)^-1 diag(beta)``, ``W = T (K *
  exp(G))``, ``U = T V``; then with the chunk's incoming state ``S``:
  ``V' = U - W S``, ``O = (Q * exp(G)) S + tril(Q K^T * exp(G_i -
  G_j)) V'``, ``S' = exp(G_C) S + (K * exp(G_C - G))^T V'``.  Exact:
  it equals the token recurrence.  What does not depend on ``S`` is
  made for every chunk at once in XLA (the decays as ``exp`` of masked
  DIFFERENCES, never ``exp(-G)``, and the unit-triangular solve, both
  float32); the pass over chunks, which carries ``S``, is one
  ``pallas_call`` a layer on a TPU (:func:`_gated_delta_chunk_kernel`:
  grid heads x chunks, the head's ``S`` in VMEM scratch) and a
  ``lax.scan`` elsewhere.  It also leaves the state as it stood at every
  ``stride`` tokens, which the serving engine keeps on its prefix trie;
* :func:`gated_delta_step`: one token a lane, for decode, over states
  laid out as the serving cache keeps them, ``[dk, H · dv]``.

Operands take the dtype of ``q`` (bfloat16 on the chip); the state, the
decays and the solve are float32.  Forward only: no backward is defined.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import role
from .flash_attention import _on_tpu

__all__ = ["gated_delta_recurrence", "gated_delta_chunked",
           "gated_delta_step", "CHUNK"]

#: tokens a chunk
CHUNK = 64


@role("state")
def gated_delta_recurrence(q, k, v, g, beta, state=None):
    """The token recurrence.  ``q``, ``k`` ``[T, H, dk]``, ``v`` ``[T, H,
    dv]``, ``g``, ``beta`` ``[T, H]``; ``state`` ``[H, dk, dv]`` (zeros
    where ``None``).  Returns ``(o [T, H, dv], states [T, H, dk, dv])``:
    the state after every token, all float32."""
    f32 = jnp.float32
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), f32)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        kS = jnp.einsum("hk,hkv->hv", k_t, S, precision="highest")
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - kS)[:, None, :]
        return S, (jnp.einsum("hk,hkv->hv", q_t, S, precision="highest"),
                   S)
    _, (o, states) = lax.scan(
        step, state.astype(f32),
        (q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
         beta.astype(f32)))
    return o, states


def _chunk_operands(q, k, v, g, beta):
    """What the pass over chunks reads, for every chunk at once: ``(W,
    Qg, Kd [H, N, C, dk], U [H, N, C, dv], Aqk [H, N, C, C], gc [H,
    N])`` from ``q``, ``k`` ``[T, H, dk]``, ``v`` ``[T, H, dv]``, ``g``,
    ``beta`` ``[T, H]`` with ``T = N · C``."""
    f32 = jnp.float32
    T, H, dk = q.shape
    C, N = CHUNK, T // CHUNK
    dtype = q.dtype

    def chunks(x):          # [T, H, ...] -> [H, N, C, ...]
        return jnp.moveaxis(x.reshape((N, C, H) + x.shape[2:]), 2, 0)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    G = jnp.cumsum(chunks(g.astype(f32)), axis=-1)          # [H, N, C]
    b = chunks(beta.astype(f32))
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # exp of the masked difference: G falls, so exp(G_i - G_j) <= 1 on
    # and below the diagonal where exp(-G_j) alone overflows
    decay = jnp.exp(jnp.where(i >= j, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("hnid,hnjd->hnij", kc, kc, preferred_element_type=f32)
    A = jnp.where(i > j, b[..., None] * kk * decay, 0.0)
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate([kc.astype(f32) * eG, vc.astype(f32)], -1) \
        * b[..., None]
    WU = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=f32), rhs, lower=True, unit_diagonal=True)
    qk = jnp.einsum("hnid,hnjd->hnij", qc, kc, preferred_element_type=f32)
    Aqk = qk * decay                       # zero above the diagonal
    Qg = qc.astype(f32) * eG
    Kd = kc.astype(f32) * jnp.exp(G[..., -1:] - G)[..., None]
    gc = jnp.exp(G[..., -1])
    return (WU[..., :dk].astype(dtype), Qg.astype(dtype), Kd.astype(dtype),
            WU[..., dk:].astype(dtype), Aqk.astype(dtype), gc)


def _chunk_pass(S, W, Qg, Kd, U, Aqk, gc):
    """One chunk of one head given its incoming state ``S [dk, dv]``
    (float32): ``(O [C, dv] float32, S')``.  The kernel's body and the
    scan's alike."""
    f32 = jnp.float32
    Sx = S.astype(W.dtype)
    Vn = U.astype(f32) - jnp.dot(W, Sx, preferred_element_type=f32)
    Vx = Vn.astype(W.dtype)
    O = jnp.dot(Qg, Sx, preferred_element_type=f32) \
        + jnp.dot(Aqk, Vx, preferred_element_type=f32)
    S = gc * S + lax.dot_general(Kd, Vx, (((0,), (0,)), ((), ())),
                                 preferred_element_type=f32)
    return O, S


def _gated_delta_chunk_kernel(s0_ref, w_ref, qg_ref, kd_ref, u_ref, aqk_ref,
                              gc_ref, o_ref, snap_ref, S, *, n_chunks,
                              period):
    """Grid (heads, chunks), the chunks in order: the head's state stays
    in the VMEM scratch ``S`` from chunk to chunk; it is copied out into
    ``snap_ref`` at the last chunk of every ``period`` and of the
    sequence (the block of ``snap_ref`` is the period's, so it is written
    back once)."""
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        S[...] = s0_ref[...]
    O, S_new = _chunk_pass(S[...], w_ref[...], qg_ref[...], kd_ref[...],
                           u_ref[...], aqk_ref[...], gc_ref[:, :1])
    o_ref[...] = O.astype(o_ref.dtype)
    S[...] = S_new

    @pl.when(((n + 1) % period == 0) | (n == n_chunks - 1))
    def _():
        snap_ref[...] = S_new


def _chunk_kernel_call(state, W, Qg, Kd, U, Aqk, gc, period, interpret):
    """The pass over chunks as one ``pallas_call``: ``(O [H, N, C, dv],
    snaps [n_snap, H, dk, dv])``."""
    H, N, C, dk = W.shape
    dv = U.shape[-1]
    n_snap = -(-N // period)
    # the chunk's closing decay, a row of lanes a chunk
    gc = jnp.broadcast_to(gc[..., None, None], (H, N, 1, 128))

    def per_chunk(width):
        return pl.BlockSpec((None, None, C, width),
                            lambda h, n: (h, n, 0, 0))
    kernel = functools.partial(_gated_delta_chunk_kernel, n_chunks=N,
                               period=period)
    return pl.pallas_call(
        kernel,
        name="_gated_delta_chunk_kernel",
        grid=(H, N),
        in_specs=[pl.BlockSpec((None, dk, dv), lambda h, n: (h, 0, 0)),
                  per_chunk(dk), per_chunk(dk), per_chunk(dk),
                  per_chunk(dv), per_chunk(C),
                  pl.BlockSpec((None, None, 1, 128),
                               lambda h, n: (h, n, 0, 0))],
        out_specs=[per_chunk(dv),
                   pl.BlockSpec((None, None, dk, dv),
                                lambda h, n: (n // period, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((H, N, C, dv), W.dtype),
                   jax.ShapeDtypeStruct((n_snap, H, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(state, W, Qg, Kd, U, Aqk, gc)


def _chunk_scan(state, W, Qg, Kd, U, Aqk, gc, period):
    """The same pass as a ``lax.scan`` over chunks, every head at once
    (any backend but the TPU)."""
    N = W.shape[1]
    n_snap = -(-N // period)

    def step(S, x):
        O, S = jax.vmap(_chunk_pass)(S, *x[:-1], x[-1][:, None, None])
        return S, (O.astype(W.dtype), S)
    _, (O, states) = lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0)
                           for a in (W, Qg, Kd, U, Aqk, gc)))
    ends = [min((i + 1) * period, N) - 1 for i in range(n_snap)]
    return jnp.moveaxis(O, 0, 1), states[jnp.asarray(ends)]


@role("state")
def gated_delta_chunked(q, k, v, g, beta, state=None, stride=None,
                        interpret=False):
    """The chunked form over one sequence.  ``q``, ``k`` ``[T, H, dk]``,
    ``v`` ``[T, H, dv]``, ``g``, ``beta`` ``[T, H]`` (a position that
    must leave the state as it is, such as padding, carries ``g = 0``
    and ``beta = 0``); ``state`` ``[H, dk, dv]`` float32, the state the
    sequence starts from (zeros where ``None``); ``stride``: a multiple
    of :data:`CHUNK`, or ``None`` for the end alone.

    Returns ``(o [T, H, dv]`` in ``q.dtype``, ``snaps [n, H, dk, dv]``
    float32``)``: ``snaps[i]`` is the state after ``min((i + 1) · stride,
    T)`` tokens, ``n = ceil(T / stride)``, so the last is the final
    state.  On a TPU (or with ``interpret``) the pass over chunks is the
    Pallas kernel."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = CHUNK
    if stride is None:
        stride = -(-T // C) * C
    if stride % C:
        raise ValueError(f"stride {stride} is not a multiple of the chunk "
                         f"of {C} tokens")
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)
    operands = _chunk_operands(q, k, v, g, beta)
    if interpret or _on_tpu():
        O, snaps = _chunk_kernel_call(state, *operands, stride // C,
                                      interpret)
    else:
        O, snaps = _chunk_scan(state, *operands, stride // C)
    o = jnp.moveaxis(O.reshape(H, T + pad, dv), 0, 1)[:T]
    return o, snaps


@role("state")
def gated_delta_step(S, q, k, v, g, beta):
    """One token a lane over states in the cache's layout.  ``S [B, dk,
    H · dv]`` float32 (head ``h`` in lanes ``h · dv`` on); ``q``, ``k``
    ``[B, H, dk]``, ``v`` ``[B, H, dv]``, ``g``, ``beta`` ``[B, H]``.
    Returns ``(o [B, H, dv] float32, S')``.  Everything stays in the
    states' own lanes: a head's ``k``, ``q`` and gates are spread over
    its ``dv`` lanes (a product with a matrix of ones: a ``repeat`` goes
    through ``[..., H, dv]``, whose lanes the TPU pads and relays) and
    the products over ``dk`` are sums down the rows, so the states are
    read once and written once and never relaid."""
    f32 = jnp.float32
    B, H, dk = q.shape
    dv = v.shape[-1]
    ones = (jnp.arange(H)[:, None]
            == jnp.arange(H * dv)[None, :] // dv).astype(f32)

    def spread(x):          # [..., H] -> [..., H · dv], exactly
        return jnp.einsum("...h,hl->...l", x.astype(f32), ones,
                          precision="highest")
    kx = spread(jnp.swapaxes(k, 1, 2))                     # [B, dk, H · dv]
    qx = spread(jnp.swapaxes(q, 1, 2))
    S = S * spread(jnp.exp(g.astype(f32)))[:, None, :]
    kS = jnp.sum(kx * S, axis=1, keepdims=True)            # [B, 1, H · dv]
    u = spread(beta)[:, None, :] \
        * (v.astype(f32).reshape(B, 1, H * dv) - kS)
    S = S + kx * u
    o = jnp.sum(qx * S, axis=1)
    return o.reshape(B, H, dv), S
