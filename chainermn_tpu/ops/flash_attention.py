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

The backward is a FUSED one-pass kernel by default
(:func:`_flash_bwd_fused_kernel`): each (qi, ki) attention tile is
recomputed once — s = q·kᵀ, mask, p = exp(s − lse) — and feeds all
three gradients (dk/dv accumulate in VMEM across the query loop, dq
leaves as per-key-block partial planes reduced by one XLA sum).  The
legacy two-kernel lowering (one dq pass + one dkv pass, each
recomputing the tile) stays available bit-for-bit behind
``CHAINERMN_TPU_FLASH_BWD=split``.  Backward tiles are tuned
independently of the forward's (``CHAINERMN_TPU_FLASH_BWD_BLOCK_Q/K``,
sweep-driven per-T table — `make sweep-flash`).

Ring-attention composition: ``parallel.ring_attention`` rotates KV blocks
between chips; within a chip this kernel computes each block's
contribution — ICI transfers at the outer level, VMEM tiling at the
inner.
"""

from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["attention", "flash_attention", "xla_attention"]

# Both grid dims are embarrassingly parallel (independent programs per
# (batch*head, block) pair).  vmem_limit_bytes raises Mosaic's scoped-VMEM
# cap from its 16 MB default: at long T, XLA can place whole kernel
# outputs in VMEM (observed OOM on v5e at T=8192 with the default).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
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


def _flash_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                      causal, scale):
    """Forward kernel variant that also writes the log-sum-exp row
    statistics (softmax normalizer) needed by the backward kernels."""
    bq, d = q_ref.shape
    tk = k_ref.shape[0]
    qi = pl.program_id(1)

    # dtype discipline: blocks go into the dots in their STORAGE dtype
    # (bf16 rides the MXU's native path; an f32 upcast would force the
    # 3-pass f32 matmul emulation) with fp32 accumulators via
    # preferred_element_type; the online-softmax state stays fp32.
    q = q_ref[:]
    m = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    n_kblocks = tk // block_k
    q_pos = (qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0))

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
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
        last = jnp.minimum((qi * bq + bq + block_k - 1) // block_k,
                           n_kblocks)
    else:
        last = n_kblocks
    m, l, acc = jax.lax.fori_loop(0, last, body, (m, l, acc))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    # lse is [bq, 1]: Mosaic requires the block's trailing dims to divide
    # (8, 128) or equal the array dims — a trailing singleton qualifies,
    # a squeezed 1-D block does not
    lse_ref[:] = m_safe + jnp.log(jnp.maximum(l, 1e-30))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k, causal, scale):
    """dq for one query block: recompute P from (q, k, lse); then
    dq = scale * sum_j (P_ij (g_i·v_j - delta_i)) k_j."""
    bq, d = q_ref.shape
    tk = k_ref.shape[0]
    qi = pl.program_id(1)
    q = q_ref[:]          # storage dtype into the dots (see fwd kernel)
    g = g_ref[:]
    lse = lse_ref[:].reshape(bq, 1)   # block arrives [bq, 1]
    delta = delta_ref[:].reshape(bq, 1)
    n_kblocks = tk // block_k
    q_pos = (qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
    dq = jnp.zeros((bq, d), jnp.float32)

    def body(ki, dq):
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = (ki * block_k
                     + lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse), 0.0)
        gv = jax.lax.dot_general(g, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (gv - delta)
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last = jnp.minimum((qi * bq + bq + block_k - 1) // block_k,
                           n_kblocks)
    else:
        last = n_kblocks
    dq = jax.lax.fori_loop(0, last, body, dq)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, causal, scale):
    """dk/dv for one key block: loop over query blocks;
    dv = P^T g ; dk = scale * sum_i (P_ij (g_i·v_j - delta_i)) q_i."""
    bk, d = k_ref.shape
    tq = q_ref.shape[0]
    ki = pl.program_id(1)
    k = k_ref[:]          # storage dtype into the dots (see fwd kernel)
    v = v_ref[:]
    n_qblocks = tq // block_q
    k_pos = (ki * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1))
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[pl.ds(qi * block_q, block_q), :]
        g_blk = g_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[pl.ds(qi * block_q, block_q), :] \
            .reshape(block_q, 1)
        delta = delta_ref[pl.ds(qi * block_q, block_q), :] \
            .reshape(block_q, 1)
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = (qi * block_q
                     + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(g_blk.dtype), g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        gv = jax.lax.dot_general(g_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (gv - delta)
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # query blocks at or after this key block participate
        first = (ki * bk) // block_q
    else:
        first = 0
    dk, dv = jax.lax.fori_loop(first, n_qblocks, body, (dk, dv))
    # ds was computed from UNSCALED q·k products with scale folded into s,
    # so dk = scale · Σ ds·q (the fwd scale that s carries)
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                            dq_part_ref, dk_ref, dv_ref, *, block_q,
                            causal, scale):
    """Fused backward: ONE pass over the (qi, ki) tiles per key block.

    The split lowering (`_flash_bwd_dq_kernel` + `_flash_bwd_dkv_kernel`)
    recomputes the attention block twice: each kernel re-runs the
    s = q·kᵀ dot, the mask, exp(s − lse) and the g·vᵀ dot for every tile
    it touches.  Here each (qi, ki) tile is recomputed ONCE and all three
    gradient contributions leave together:

        dv  += pᵀ g                      (accumulated in VMEM over qi)
        dk  += dsᵀ q                     (accumulated in VMEM over qi)
        dq_part[qi] = ds·k               (per-key-block partial plane)

    dq cannot be accumulated in-place across key blocks — the grid is
    parallel over ki and Mosaic offers no cross-program accumulation —
    so each program writes its [Tq, D] dq contribution to its own slot
    of a [n_kblocks, Tq, D] partial array; the caller reduces it with
    one XLA sum (the splash-attention fused-backward shape; the reduce
    is HBM-bound but a rounding error next to the recomputed dots it
    replaces).  Per tile pair the split lowering runs 8 MXU dots + 2
    exp's; this runs 5 dots + 1 exp — the recompute-once argument in
    docs/performance.md quantifies it.
    """
    bk, d = k_ref.shape
    tq = q_ref.shape[0]
    ki = pl.program_id(1)
    k = k_ref[:]          # storage dtype into the dots (see fwd kernel)
    v = v_ref[:]
    n_qblocks = tq // block_q
    k_pos = (ki * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1))
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    # causally-skipped query tiles must still leave a defined partial:
    # zero the whole plane once, the live tiles overwrite below
    dq_part_ref[:] = jnp.zeros((tq, d), jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[pl.ds(qi * block_q, block_q), :]
        g_blk = g_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[pl.ds(qi * block_q, block_q), :] \
            .reshape(block_q, 1)
        delta = delta_ref[pl.ds(qi * block_q, block_q), :] \
            .reshape(block_q, 1)
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = (qi * block_q
                     + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse), 0.0)  # ONCE
        dv = dv + jax.lax.dot_general(
            p.astype(g_blk.dtype), g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        gv = jax.lax.dot_general(g_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (gv - delta)
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dq contribution of this (qi, ki) tile; scale is applied after
        # the cross-block sum (mirrors the split dq kernel's `dq * scale`
        # after its fori accumulation)
        dq_part_ref[pl.ds(qi * block_q, block_q), :] = \
            jax.lax.dot_general(ds.astype(k.dtype), k,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # query blocks at or after this key block participate
        first = (ki * bk) // block_q
    else:
        first = 0
    dk, dv = jax.lax.fori_loop(first, n_qblocks, body, (dk, dv))
    # ds was computed from UNSCALED q·k products with scale folded into s,
    # so dk = scale · Σ ds·q (the fwd scale that s carries)
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


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


# Adaptive-default tile candidates, largest first.  The round-5 on-chip
# sweep (tools/flash_block_sweep.py, BENCH_NOTES r5): 128×128 tiles
# serialize the online-softmax loop into too-small MXU dots — 1024-wide
# tiles ran the same fwd+bwd 2.85× faster at T=8192 (11.2 → 31.8
# TFLOP/s) and lifted the end-to-end seq-1024 transformer step 1.40×
# (74.1k → 103.4k tokens/sec/chip, MFU 29.1% → 40.6%).  VMEM cost at
# 1024: the f32 score/probability tiles are 4 MB each — comfortably
# inside the kernel's 100 MB scoped-VMEM cap with whole-T K/V staging
# up to T≈64k.
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


def _flash_blocks(block_q=None, block_k=None, tq=None, tk=None):
    """Resolve kernel tile sizes: explicit arguments win, else the
    CHAINERMN_TPU_FLASH_BLOCK_Q/K env knobs (so an on-chip session can
    A/B block shapes without code edits), else the shape-adaptive
    default (:func:`_adaptive_block` over the given Tq/Tk).  Env changes
    only affect programs traced AFTERWARDS — jit caches are not keyed on
    them, so run each configuration in a fresh process (the probe does).
    Values must be positive multiples of 8 (Mosaic sublane tiling)."""
    out = []
    for name, given, t in (("CHAINERMN_TPU_FLASH_BLOCK_Q", block_q, tq),
                           ("CHAINERMN_TPU_FLASH_BLOCK_K", block_k, tk)):
        if given is None:
            raw = os.environ.get(name)
            if raw is None:
                given = _adaptive_block(t)
            else:
                try:
                    given = int(raw)
                except ValueError:
                    raise ValueError(f"{name}={raw!r} is not an integer")
                if given <= 0 or given % 8:
                    raise ValueError(
                        f"{name}={given} invalid: flash block sizes must "
                        "be positive multiples of 8")
        out.append(given)
    return tuple(out)


# -- backward lowering selection ---------------------------------------------

#: CHAINERMN_TPU_FLASH_BWD: "fused" (default) = the one-pass dq/dkv
#: kernel; "split" = the legacy two-kernel lowering (dq pass + dkv pass,
#: each recomputing the attention block) — the escape hatch, kept
#: exactly like nn.functions' CHAINERMN_TPU_MAXPOOL_VJP=xla: read once
#: at import, monkeypatchable in tests, and the legacy kernels are
#: untouched so `split` restores the old lowering bit-for-bit.
_FLASH_BWD = os.environ.get("CHAINERMN_TPU_FLASH_BWD", "fused")

#: Backward-specific tile table, keyed by sequence length — the bwd
#: kernels have a different VMEM/recompute balance than the forward
#: (whole-T q/g staging + an f32 [Tq, D] partial plane vs the forward's
#: K/V streaming), so their best tiles need not match.  Regenerate with
#: `make sweep-flash` (tools/flash_sweep.py sweeps fwd/bwd/fwd+bwd per
#: (block_q, block_k) and rewrites tools/flash_budgets.json; paste the
#: winners here).  Committed values are the best KNOWN config — the r5
#: on-chip sweep's 1024-tile winner for the split backward (BENCH_NOTES
#: r5: 128-tiles 11.2 → 1024-tiles 31.8 TFLOP/s at T=8192); the fused
#: kernel's own sweep refines them on the next chip session.
_BWD_BLOCK_TABLE = {
    1024: (1024, 1024),
    2048: (1024, 1024),
    8192: (1024, 1024),
    16384: (1024, 1024),
}


def _flash_bwd_mode():
    mode = _FLASH_BWD
    if mode not in ("fused", "split"):
        raise ValueError(
            f"CHAINERMN_TPU_FLASH_BWD={mode!r} invalid (fused|split)")
    return mode


def _flash_bwd_blocks(block_q=None, block_k=None, tq=None, tk=None):
    """Backward tile resolution: explicit arguments win, else the
    CHAINERMN_TPU_FLASH_BWD_BLOCK_Q/K env knobs, else the sweep-driven
    per-T table (:data:`_BWD_BLOCK_TABLE`), else the forward's
    shape-adaptive default.  Same env-retrace caveat and multiple-of-8
    validation as :func:`_flash_blocks`."""
    out = []
    for i, (name, given, t) in enumerate(
            (("CHAINERMN_TPU_FLASH_BWD_BLOCK_Q", block_q, tq),
             ("CHAINERMN_TPU_FLASH_BWD_BLOCK_K", block_k, tk))):
        if given is None:
            raw = os.environ.get(name)
            if raw is None:
                entry = _BWD_BLOCK_TABLE.get(t)
                given = entry[i] if entry else _adaptive_block(t)
            else:
                try:
                    given = int(raw)
                except ValueError:
                    raise ValueError(f"{name}={raw!r} is not an integer")
                if given <= 0 or given % 8:
                    raise ValueError(
                        f"{name}={given} invalid: flash block sizes must "
                        "be positive multiples of 8")
        out.append(given)
    return tuple(out)


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


def _warn_fallback(q, k, path):
    """On the tpu backend a shape the flash kernels cannot take is
    visible: one warning per (shape, path) at trace time.  Off the chip
    the non-kernel path is the normal one and stays silent."""
    key = (tuple(q.shape), tuple(k.shape), path)
    if not _on_tpu() or key in _WARNED_FALLBACK:
        return
    _WARNED_FALLBACK.add(key)
    warnings.warn(
        f"flash attention: q{list(q.shape)} k{list(k.shape)} does not "
        f"tile (T must be a multiple of its block); running {path} "
        "instead of the Pallas kernels", UserWarning, stacklevel=3)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False):
    """Fused attention via Pallas.  q/k/v: [B, H, T, D].  Default block
    sizes come from :func:`_flash_blocks` (env-tunable)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block_q, block_k = _flash_blocks(block_q, block_k, tq=Tq, tk=Tk)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k:
        _warn_fallback(q, k, "XLA attention")
        return xla_attention(q, k, v, causal=causal, scale=scale)

    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)

    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale,
                               q_offset_blocks=0)
    out = pl.pallas_call(
        kernel,
        name="_flash_kernel",
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D)


def flash_attention_fwd(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None, interpret=False):
    """Forward kernel returning (out, lse [B, H, Tq])."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block_q, block_k = _flash_blocks(block_q, block_k, tq=Tq, tk=Tk)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    kernel = functools.partial(_flash_kernel_lse, block_k=block_k,
                               causal=causal, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        name="_flash_kernel_lse",
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D), lse.reshape(B, H, Tq)


def flash_attention_bwd(q, k, v, out, lse, g, causal=False, scale=None,
                        block_q=None, block_k=None, interpret=False,
                        g_lse=None, bwd_block_q=None, bwd_block_k=None):
    """Backward: (dq, dk, dv) with flash memory behavior.

    Default lowering is the FUSED one-pass kernel
    (:func:`_flash_bwd_fused_kernel`): one recompute of each (qi, ki)
    attention tile feeds dq, dk and dv together, with its own
    sweep-tunable tiles (``bwd_block_q``/``bwd_block_k`` →
    :func:`_flash_bwd_blocks`).  ``CHAINERMN_TPU_FLASH_BWD=split``
    restores the legacy two-kernel lowering (a dq pass and a dkv pass,
    each recomputing exp(q·kᵀ − lse)) bit-for-bit — the escape hatch,
    same contract as PR 3's ``MAXPOOL_VJP=xla``.

    ``g_lse``: optional cotangent of the lse output.  Since
    ∂lse_i/∂s_ij = p_ij, its whole contribution is ``ds += g_lse_i * p``
    — algebraically identical to replacing ``delta`` with
    ``delta - g_lse`` in the kernels (``ds = p*(gv - delta)``), so no
    kernel changes are needed on either path.  Ring attention depends on
    this: the cross-block merge weights are functions of each block's
    lse."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block_q, block_k = _flash_blocks(block_q, block_k, tq=Tq, tk=Tk)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    gr = g.reshape(B * H, Tq, D)
    lser = lse.reshape(B * H, Tq, 1)  # trailing singleton: Mosaic-legal
    # delta_i = rowsum(g_i * out_i) — one fused elementwise reduce
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(B * H, Tq, D).astype(jnp.float32),
                    axis=-1, keepdims=True)
    if g_lse is not None:
        delta = delta - g_lse.reshape(B * H, Tq, 1).astype(jnp.float32)

    if _flash_bwd_mode() == "fused":
        # bwd-specific tiles; the (already shape-validated) forward
        # tiles are the fallback when the table/env tiles don't divide
        # this T — e.g. ragged lengths reached with explicit fwd blocks
        bq, bk = _flash_bwd_blocks(bwd_block_q, bwd_block_k,
                                   tq=Tq, tk=Tk)
        bq = min(bq, Tq)
        bk = min(bk, Tk)
        if Tq % bq or Tk % bk:
            bq, bk = block_q, block_k
        n_kblocks = Tk // bk
        dq_part, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_fused_kernel, block_q=bq,
                              causal=causal, scale=scale),
            name="_flash_bwd_fused_kernel",
            grid=(B * H, n_kblocks),
            in_specs=[
                pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((None, Tq, 1), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((None, Tq, 1), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, Tq, D),
                             lambda b, i: (b, i, 0, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, bk, D), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, n_kblocks, Tq, D),
                                     jnp.float32),
                jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
                jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype),
            ],
            interpret=interpret,
            compiler_params=_COMPILER_PARAMS,
        )(qr, kr, vr, gr, lser, delta)
        # the cross-key-block dq reduction the grid cannot express:
        # one XLA sum over the partial planes, then the fwd scale
        dq = (jnp.sum(dq_part, axis=1) * scale).astype(q.dtype)
        return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
                dv.reshape(B, H, Tk, D))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          causal=causal, scale=scale),
        name="_flash_bwd_dq_kernel",
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(qr, kr, vr, gr, lser, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          causal=causal, scale=scale),
        name="_flash_bwd_dkv_kernel",
        grid=(B * H, Tk // block_k),
        in_specs=[
            pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tq, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tq, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype),
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(qr, kr, vr, gr, lser, delta)
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
        return out, (q, k, v, None, None, None)
    out, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                   block_q=bq, block_k=bk,
                                   interpret=interpret)
    # carry the block config in the residuals: the backward's SHAPE
    # validation must use the exact tiles the forward was validated with
    # (they are the fused path's divisibility fallback and the split
    # path's tiles; re-reading the fwd env there would silently corrupt
    # gradients if it changed mid-process)
    return out, (q, k, v, out, lse, (bq, bk))


def _flash_diff_bwd(causal, scale, interpret, res, g):
    q, k, v, out, lse, blocks = res
    if lse is None:
        _, vjp = jax.vjp(
            lambda q, k, v: xla_attention(q, k, v, causal=causal,
                                          scale=scale), q, k, v)
        return vjp(g)
    bq, bk = blocks
    return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                               scale=scale, block_q=bq, block_k=bk,
                               interpret=interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def attention(q, k, v, causal=False, scale=None):
    """Dispatch: Pallas kernels on TPU (flash forward AND fused backward
    via custom VJP), XLA reference elsewhere.
    CHAINERMN_TPU_FLASH_INTERPRET=1 forces the Pallas path in
    interpreter mode on any backend (CPU kernel tests)."""
    interpret = _interpret_forced()  # raises on a tpu backend
    if interpret or _on_tpu():
        return _flash_diff(q, k, v, causal, scale, interpret)
    return xla_attention(q, k, v, causal=causal, scale=scale)


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
    bq, bk = _flash_blocks(tq=q.shape[2], tk=k.shape[2])
    out, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                   block_q=bq, block_k=bk,
                                   interpret=interpret)
    # same residual-carried block config as _flash_diff: the fwd tiles
    # are the backward's validated divisibility fallback
    return (out, lse), (q, k, v, out, lse, (bq, bk))


def _flash_lse_bwd(causal, scale, interpret, res, cots):
    q, k, v, out, lse, (bq, bk) = res
    g, g_lse = cots
    return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                               scale=scale, block_q=bq, block_k=bk,
                               interpret=interpret, g_lse=g_lse)


_flash_lse_diff.defvjp(_flash_lse_fwd, _flash_lse_bwd)


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


def blockwise_attention(q, k, v, causal=False, scale=None):
    """Memory-bounded attention (no [Tq, Tk] materialization on any
    backend): flash kernel on TPU, blockwise jnp scan elsewhere."""
    return attention_with_lse(q, k, v, causal=causal, scale=scale)[0]
