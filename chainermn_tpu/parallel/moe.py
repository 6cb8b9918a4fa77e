"""Expert parallelism — Switch-style mixture-of-experts over all_to_all.

Reference status: **absent** in ChainerMN (SURVEY.md §2.6 EP row: "not
required for parity; all_to_all primitive should still be first-class").
This module is the beyond-parity realization: experts are sharded one
per rank along the communicator axis; tokens are routed top-1 (Switch
Transformer) or top-k (GShard) with fixed per-expert capacity, exchanged
by ``all_to_all``, transformed by the local expert's fused GEMMs, and
returned by the reverse exchange.

Topology-aware dispatch (ISSUE 12): on a HIERARCHICAL communicator the
token exchange is TWO-STAGE — an ``all_to_all`` over the ICI axis first
(tokens regroup by destination slot within the host, so tokens whose
expert lives on-host never touch the slow fabric), then an
``all_to_all`` over DCN carrying only the off-host remainder, with the
combine path running the transposed reverse (DCN first, then ICI — the
slow wire starts the moment expert compute closes).  Emission follows
``_memory_utility.hop_schedule(mode="moe")`` literally.  The two stages
compose to EXACTLY the flat single-axis ``all_to_all`` (they permute
disjoint buffer dims), so the lossless two-stage dispatch is golden —
bit-for-bit — equal to the flat reference
(tests/core_tests/test_exchange_equivalence.py).

The DCN crossing compresses via the PR 7 per-hop machinery: with
``allreduce_grad_dtype={"dcn": "bfloat16"}`` the off-host blocks cross
as bf16; with an int8/fp8 dcn dtype they cross as codewords with
PER-SEGMENT symmetric scales (``quantize_symmetric_segments`` — one
scale per destination host block, shipped as a q+scale pair alongside
the codewords; the backward cotangents ride the same compressed
transposed crossing, straight-through).  ICI stays lossless BY DESIGN,
and the own-host block of a compressed crossing is restored from the
pre-quantization values — it never left the device, so it never pays
the codebook (the behavioral form of "on-host tokens never touch the
slow fabric", pinned by tests/parallel_tests/test_moe.py).  The
quantized path is NOT bit-exact and gates on convergence parity (the
5% final-loss band, like error feedback), while the lossless two-stage
path gates on bit-parity with the flat reference.

Escape hatches: ``two_stage=False`` is the EXPLICIT single-axis choice
on a multi-axis communicator (a hierarchical comm defaults to
two-stage — silent flat routing on a two-level mesh is the failure
mode this knob closes); ``CHAINERMN_TPU_HIERARCHY=flat`` drops
two-stage routing with a one-time warning (the PR 11 striping
pattern); ``CHAINERMN_TPU_COMPRESS=off`` already nulls the quantized
dcn dtype at communicator construction, so the dispatch crossing falls
back to lossless with no code change.

Static shapes throughout (capacity-bounded dispatch with drop/pad), so
XLA compiles one program regardless of routing decisions; gradients flow
through the combine weights (straight-through on the router probability).
Capacity honesty: the aux dict reports ``dropped_frac`` (the fraction of
routed token copies zeroed by the capacity cut) next to the ``frac`` /
``mean_prob`` load-balancing statistics, so benches and parity tests can
assert capacity is sized honestly instead of silently zeroing overflow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.link import Link, Parameter
from ..observability import role

__all__ = ["switch_moe", "moe_dispatch_combine", "moe_dispatch_combine_topk",
           "moe_capacity", "sigmoid_topk_route", "HeldExperts",
           "softmax_topk_route", "sorted_experts_ffn", "SortedExperts"]


def moe_capacity(n_tokens, n_experts, capacity_factor, k=1):
    """Per-expert slot count of the dispatch capacity buffer:
    ``max(1, int(capacity_factor · k · n_tokens / n_experts))`` over the
    RANK-LOCAL token count.  The ONE formula the dispatchers and the
    comm census share — a rounding tweak here re-prices every committed
    row together instead of letting the surfaces drift apart."""
    return max(1, int(capacity_factor * k * n_tokens / n_experts))


def _resolve_two_stage(comm, two_stage):
    """Resolve the ``two_stage`` knob against the communicator's
    topology (ISSUE 12 guard rail): ``None`` means topology-aware —
    two-stage on a hierarchical communicator, flat on a one-axis one —
    so single-axis use of a multi-axis comm is an EXPLICIT
    ``two_stage=False`` choice, never a silent default.  Requesting
    ``two_stage=True`` on a flat communicator is an error — except
    when the factory's ``CHAINERMN_TPU_HIERARCHY=flat`` hatch is what
    flattened a REQUESTED hierarchy (the communicator carries the
    ``_hierarchy_flattened_by_env`` mark), in which case two-stage
    routing is dropped with the one-time warning PR 11 established
    for striping.  A communicator that was never hierarchical never
    triggers the hatch warning, whatever the environment says."""
    hier = getattr(comm, "hierarchy", None) is not None
    hatch_degraded = getattr(comm, "_hierarchy_flattened_by_env", False)
    if two_stage is None:
        if hier:
            return True
        if hatch_degraded:
            from ..communicators import _warn_hierarchy_flat_two_stage_dropped
            _warn_hierarchy_flat_two_stage_dropped()
        return False
    two_stage = bool(two_stage)
    if two_stage and not hier:
        if hatch_degraded:
            from ..communicators import _warn_hierarchy_flat_two_stage_dropped
            _warn_hierarchy_flat_two_stage_dropped()
            return False
        raise ValueError(
            "two_stage=True needs a hierarchical communicator "
            "(name='hierarchical'/'two_dimensional' or an intra_size/"
            "inter_size split): a flat mesh has one fabric, there is "
            "no second hop to stage the dispatch across")
    return two_stage


def _dcn_crossing_fn(comm):
    """The slow-fabric ``all_to_all`` of the two-stage exchange, on a
    ``[inter, ...]`` buffer (leading axis = destination/source host
    block), honoring the communicator's per-hop dcn dtype:

    * lossless (``dcn_grad_dtype is None``): the native all_to_all
      (exact autodiff).
    * cast (bf16/fp16): cast → all_to_all → cast back; the transposed
      cotangent crossing rides the same cast wire for free.
    * quantized (int8/fp8): per-segment symmetric quantization — one
      scale per destination host block — q and the ``[inter]`` scale
      vector each cross on their own all_to_all, and each received
      block decodes with ITS sender's scale.  ``jax.custom_vjp``
      makes the backward the same compressed transposed crossing
      (straight-through: the codebook's round has no useful gradient,
      and a lossless f32 backward would silently give back the byte
      win the forward bought).

    In every compressed flavor the OWN-host block is restored from the
    pre-crossing values: an all_to_all keeps the own segment local, so
    on-host tokens never cross the slow fabric and must not pay its
    codebook.
    """
    from ..communicators._memory_utility import (
        dequantize_symmetric, is_quantized_dtype,
        quantize_symmetric_segments)
    dcn = comm.dcn_axis
    inter = comm.dcn_size
    wire = comm.dcn_grad_dtype

    if wire is None:
        return lambda v: lax.all_to_all(v, dcn, split_axis=0,
                                        concat_axis=0, tiled=False)

    def _own_restored(v, crossed):
        own = lax.axis_index(dcn)
        mask = lax.broadcasted_iota(
            jnp.int32, (inter,) + (1,) * (v.ndim - 1), 0) == own
        return jnp.where(mask, v, crossed)

    if not is_quantized_dtype(wire):
        def cast_crossing(v):
            out = lax.all_to_all(v.astype(wire), dcn, split_axis=0,
                                 concat_axis=0, tiled=False)
            return _own_restored(v, out.astype(v.dtype))
        return cast_crossing

    def quantized(v):
        q, scales = quantize_symmetric_segments(v, wire)
        qr = lax.all_to_all(q, dcn, split_axis=0, concat_axis=0,
                            tiled=False)
        sr = lax.all_to_all(scales, dcn, split_axis=0, concat_axis=0,
                            tiled=False)
        deq = dequantize_symmetric(
            qr, sr.reshape((inter,) + (1,) * (v.ndim - 1)))
        return _own_restored(v, deq.astype(v.dtype))

    @jax.custom_vjp
    def crossing(v):
        return quantized(v)

    def fwd(v):
        return quantized(v), None

    def bwd(_, ct):
        # the transposed crossing of the cotangents — same codebook,
        # own-block cotangent lossless (all_to_all with square blocks
        # is its own transpose on this indexing)
        return (quantized(ct),)

    crossing.defvjp(fwd, bwd)
    return crossing


def _exchange(comm, buf, two_stage, combine=False):
    """Move a ``[E, C, ...]`` capacity buffer between source ranks and
    expert ranks (``combine=False``: slot ``e`` of every rank converges
    on rank ``e``; ``combine=True``: the exact inverse).  Flat: ONE
    ``all_to_all`` over the communicator axis (the joint two-level axis
    on a hierarchical comm with ``two_stage=False`` — the explicit
    single-axis escape).  Two-stage: the buffer reshapes to
    ``[inter, intra, C, ...]`` and the ICI/DCN stages run in the order
    ``hop_schedule(mode="moe")`` pins — dispatch fast-hop-first (the
    slow crossing issued immediately after), combine transposed
    (slow-hop-first)."""
    if not two_stage:
        return lax.all_to_all(buf, comm.axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    from ..communicators._memory_utility import hop_schedule
    inter, intra = comm.dcn_size, comm.ici_size
    crossing = _dcn_crossing_fn(comm)
    s = buf.reshape((inter, intra) + buf.shape[1:])
    phase = "combine" if combine else "dispatch"
    for op, _ in hop_schedule(1, mode="moe"):
        if op == f"ici_{phase}":
            with jax.named_scope(f"moe_ici_{phase}"):
                s = lax.all_to_all(s, comm.ici_axis, split_axis=1,
                                   concat_axis=1, tiled=False)
        elif op == f"dcn_{phase}":
            with jax.named_scope(f"moe_dcn_{phase}"):
                s = crossing(s)
    return s.reshape(buf.shape)


def _one_hot_capacity(expert_idx, n_experts, capacity):
    """Position-in-expert assignment with capacity truncation.

    Returns (dispatch_mask [T, E, C] bool, position [T]) — token t goes to
    slot ``position[t]`` of its expert's buffer unless over capacity
    (dropped: contributes zero output, gradient flows only via the
    router's load-balancing loss).
    """
    T = expert_idx.shape[0]
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [T,E]
    # position of each token within its expert's queue
    position = jnp.cumsum(onehot, axis=0) * onehot  # [T, E]
    position = position.sum(axis=1) - 1             # [T]
    keep = position < capacity
    pos_cap = jnp.clip(position, 0, capacity - 1)
    dispatch = (jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.bool_)
                [:, :, None]
                & jax.nn.one_hot(pos_cap, capacity, dtype=jnp.bool_)
                [:, None, :]
                & keep[:, None, None])
    return dispatch, keep


@role("router")
def moe_dispatch_combine(comm, x, gate_logits, expert_fn,
                         capacity_factor=1.25, two_stage=None):
    """Route rank-local tokens through rank-sharded experts.

    ``x``: [T_local, D] tokens on this rank; ``gate_logits``: [T_local, E]
    with E == comm.size (one expert per rank); ``expert_fn(h)`` applies
    this rank's expert to [E*C', D]... returns same shape.
    ``two_stage``: ``None`` = topology-aware (two-stage on a
    hierarchical communicator), ``False`` = the explicit single-axis
    escape, ``True`` = require the two-stage exchange (error on a flat
    comm).  Returns ([T_local, D] combined output, aux dict with
    load-balancing stats: ``aux_loss``, ``frac`` [E], ``mean_prob``
    [E], ``dropped_frac`` (capacity-cut fraction of routed tokens),
    ``capacity``).
    """
    two_stage = _resolve_two_stage(comm, two_stage)
    E = comm.size
    T, D = x.shape
    capacity = moe_capacity(T, E, capacity_factor)

    probs = jax.nn.softmax(gate_logits, axis=-1)            # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)                  # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], 1)[:, 0]  # [T]

    dispatch, keep = _one_hot_capacity(expert_idx, E, capacity)

    # [E, C, D] buffer of tokens headed to each expert
    send = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    recv = _exchange(comm, send, two_stage)                 # [E, C, D]
    # local expert processes all ranks' contributions
    h = expert_fn(recv.reshape(E * capacity, D)).reshape(E, capacity, D)
    # return trip (two-stage: the transposed reverse, DCN first)
    back = _exchange(comm, h, two_stage, combine=True)      # [E, C, D]
    combined = jnp.einsum("tec,ecd->td", dispatch.astype(x.dtype), back)
    combined = combined * gate[:, None]

    # Switch load-balancing loss: E * sum_e fraction_e * mean_prob_e
    frac = jnp.mean(dispatch.any(axis=2).astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(frac * mean_prob)
    return combined, {"aux_loss": aux_loss,
                      "frac": frac,
                      "mean_prob": mean_prob,
                      "dropped_frac":
                          1.0 - jnp.mean(keep.astype(jnp.float32)),
                      "capacity": capacity}


def switch_moe(comm, x, router_w, w_in, b_in, w_out, b_out,
               capacity_factor=1.25, activation=jax.nn.gelu,
               two_stage=None):
    """Complete Switch-MoE layer: router + rank-local expert MLP.

    ``x``: [T_local, D].  ``router_w``: [D, E] (replicated).  ``w_in``:
    this rank's expert weights [D, H]; ``w_out``: [H, D] (shard the
    stacked [E, ...] expert bank with ``P(axis)``).  Returns
    ([T_local, D], aux).
    """
    with role("router"):
        gate_logits = x @ router_w

    @role("experts")
    def expert_fn(h):
        return activation(h @ w_in + b_in) @ w_out + b_out

    return moe_dispatch_combine(comm, x, gate_logits, expert_fn,
                                capacity_factor=capacity_factor,
                                two_stage=two_stage)


def _topk_dispatch(probs, k, capacity):
    """Joint top-k capacity assignment.

    Returns (dispatch [T, k, E, C] bool, gates [T, k], keep [T, k]).
    Queue positions are counted jointly across all (token, slot) pairs in
    (token-major, slot-minor) order so no two routed copies collide in an
    expert's buffer.
    """
    T, E = probs.shape
    gates, experts = jax.lax.top_k(probs, k)          # [T, k]
    flat_expert = experts.reshape(T * k)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # [T*k, E]
    position = jnp.cumsum(onehot, axis=0) * onehot
    position = position.sum(axis=1) - 1               # [T*k]
    keep = (position < capacity).reshape(T, k)
    pos_cap = jnp.clip(position, 0, capacity - 1)
    dispatch = (jax.nn.one_hot(flat_expert, E, dtype=jnp.bool_)[:, :, None]
                & jax.nn.one_hot(pos_cap, capacity, dtype=jnp.bool_)
                [:, None, :])
    dispatch = dispatch.reshape(T, k, E, capacity) & keep[:, :, None, None]
    return dispatch, gates, keep


@role("router")
def moe_dispatch_combine_topk(comm, x, gate_logits, expert_fn, k=2,
                              capacity_factor=1.25, normalize_gates=True,
                              two_stage=None):
    """Top-k routing variant of :func:`moe_dispatch_combine`.

    Each token is processed by its ``k`` highest-probability experts and
    the outputs are combined with (optionally renormalized) gate weights —
    the GShard-style generalization of Switch routing.  Shares the
    topology-aware two-stage exchange (and its compression) with the
    top-1 path; ``dropped_frac`` counts over the T·k routed copies.
    """
    two_stage = _resolve_two_stage(comm, two_stage)
    E = comm.size
    T, D = x.shape
    capacity = moe_capacity(T, E, capacity_factor, k=k)

    probs = jax.nn.softmax(gate_logits, axis=-1)
    dispatch, gates, keep = _topk_dispatch(probs, k, capacity)
    if normalize_gates:
        denom = jnp.maximum(gates.sum(axis=1, keepdims=True), 1e-9)
        gates = gates / denom
    gates = gates * keep.astype(gates.dtype)

    send = jnp.einsum("tkec,td->ecd", dispatch.astype(x.dtype), x)
    recv = _exchange(comm, send, two_stage)
    h = expert_fn(recv.reshape(E * capacity, D)).reshape(E, capacity, D)
    back = _exchange(comm, h, two_stage, combine=True)
    combined = jnp.einsum("tkec,tk,ecd->td", dispatch.astype(x.dtype),
                          gates, back)

    frac = jnp.mean(dispatch.any(axis=(1, 3)).astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(frac * mean_prob)
    return combined, {"aux_loss": aux_loss,
                      "frac": frac,
                      "mean_prob": mean_prob,
                      "dropped_frac":
                          1.0 - jnp.mean(keep.astype(jnp.float32)),
                      "capacity": capacity}


# -- a share of an expert layer: route over all, compute the held -------------
#
# What one chip of an expert-parallel group runs once the group is larger
# than one expert a rank: the router keeps every expert's output and its
# k a token, the chip holds experts ``[first, first + count)`` and adds
# only their terms of the sum.  Nothing is dropped: there is no capacity
# buffer, every copy routed to a held expert is computed.  No exchange is
# emitted here; what the other chips' experts add is not this layer's.
# The copies are grouped by SORTING (:func:`sorted_experts_ffn`), so an
# expert computes the copies routed to it and one that received none is
# not read.  (Until PR 47 the two share cells grouped them by masking:
# the held experts read as ONE SwiGLU of width ``H · F`` for every token,
# the routing weight zero where a token was not routed.  That read all
# 12 held experts of a Kimi layer at a decode step where 2.4 copies
# landed, 3.4 GB a step of matrices multiplied by zero, and computed 48
# times a prefill's routed products; PERF.md section 6, PR 47, has the
# two forms side by side at every decode bucket.)


@role("router")
def sigmoid_topk_route(x, router_w, bias, k, scale):
    """Sigmoid scoring with a selection bias (DeepSeek-V3's ``noaux_tc``
    with one group): ``s = sigmoid(x W_g)`` in float32 over ALL experts,
    the ``k`` chosen are the top ``k`` of ``s + bias``, and their weights
    are ``s`` (without the bias) over the chosen, divided by their sum,
    times ``scale``.  ``x``: ``[T, D]``; ``router_w``: ``[E, D]``;
    ``bias``: ``[E]``.  Returns ``(ids [T, k] int32, weights [T, k]
    float32)``."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32).T,
        precision=lax.Precision.HIGHEST))
    _, ids = lax.top_k(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return ids.astype(jnp.int32), w


@role("router")
def softmax_topk_route(logits, k):
    """Softmax over the chosen: the ``k`` largest of ``logits [T, E]``
    (float32), and the softmax of those ``k`` alone, which is the
    softmax over all ``E`` kept at the chosen and divided by their sum.
    Returns ``(ids [T, k] int32, weights [T, k] float32)``."""
    top, ids = lax.top_k(logits.astype(jnp.float32), k)
    return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _row_tile(rows):
    """The rows of one tile of a grouped product over ``rows`` sorted
    copies.  A group is visited once for each tile it reaches into and
    reads its whole matrix at each visit, so a long prefill wants tiles
    tall enough that the products, not the matrices' re-reads, bound it
    (256 rows against 768 a group at 8192 tokens), and a decode step
    tiles short enough that a visit's padding rows cost less than the
    matrix it reads (PERF.md section 6, PR 44: one sweep on the chip at
    49152, 192, 96 and 16 rows)."""
    if rows >= 4096:
        return 256
    return 32 if rows >= 32 else 16


# the matrix tile of a grouped product: a group's whole matrix where it
# is one expert's of 2560 x 768 (7.9 MB twice buffered), so that no
# output tile is visited twice; a larger matrix is cut into a tile of
# no more elements, whole lane tiles that divide it both ways: first
# one that spans the matrix one way (``tk = K``, or ``tn = N`` where the
# rows' tile leaves no room for that: a tile short both ways walks the
# rows' tiles once for every tile of the output AND every tile of K,
# 0.75 ms where 0.59 at Kimi's 7168 -> 2048 under 256 rows), then the
# largest, then the taller in K (PERF.md section 6, PR 47: 13 tilings a
# product at the two share cells' widths).  What a kernel may use
# (16 MB) also holds the rows' and the output's tiles twice buffered and
# the float32 accumulator: their sum stays inside what 256 rows of
# 768 -> 2560 take, the largest that is known to compile (256 rows of
# 7168 beside a 7168 x 256 tile, 14.5 MB by this count, do not).
_TILE_ELEMS = 2560 * 768


def _tile_bytes(tm, tk, tn):
    """The rows', the matrix's and the output's tiles in bfloat16, twice
    buffered, and the float32 accumulator."""
    return 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _whole_lane_tiles(n):
    """The tiles of whole 128 lanes that divide ``n``, and ``n``."""
    return [t for t in range(128, n, 128) if n % t == 0] + [n]


def _tiles(rows, K, N):
    tm = _row_tile(rows)
    *_, tk, tn = max(
        (tk == K or tn == N, tk * tn, tk, tn)
        for tk in _whole_lane_tiles(K) for tn in _whole_lane_tiles(N)
        if tk * tn <= _TILE_ELEMS
        and _tile_bytes(tm, tk, tn) <= _tile_bytes(256, 768, 2560))
    return tm, tk, tn


def _grouped_product(lhs, rhs, sizes, transpose_rhs, schedule=None,
                     interpret=False):
    """``lhs [M, K]``, its rows sorted by group, times the group's own
    matrix of ``rhs`` (``[H, K, N]``, or ``[H, N, K]`` with
    ``transpose_rhs``): rows ``sum(sizes[:g]) .. sum(sizes[:g + 1]) - 1``
    times ``rhs[g]``; ``[M, N]`` in ``lhs``'s dtype, the rows past
    ``sum(sizes)`` undefined.  ``M`` is whole tiles of
    :func:`_row_tile`.  On a TPU the repo's Pallas grouped matmul
    (:func:`chainermn_tpu.ops.grouped_matmul.gmm`, the kernel that ships
    with JAX as ``megablox.gmm``): a group of no rows is not visited, so
    its matrix is not read.  Its order of visits is ``schedule``
    (:func:`~chainermn_tpu.ops.grouped_matmul.group_metadata` of
    ``sizes``, made here where a caller has none): products over the
    same ``sizes`` and ``M`` share one.  Elsewhere
    ``lax.ragged_dot_general``, which
    says the same in one line and which the TPU's compiler expands to
    ONE product over every group's matrix for every row (12.4 TFLOP
    where 0.19 are asked for, at 49152 rows of 2560 into 64 groups of
    768: compiled for the described chip, PR 44), so it is the plain
    form and not the path."""
    from ..ops.flash_attention import _on_tpu
    M, K = lhs.shape
    if interpret or _on_tpu():
        from ..ops.grouped_matmul import gmm, group_metadata
        N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        if schedule is None:
            schedule = group_metadata(sizes, M, _row_tile(M))
        return gmm(lhs, rhs, schedule, _tiles(M, K, N),
                   transpose_rhs=transpose_rhs, interpret=interpret)
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2 if transpose_rhs else 1,)),
                               ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    return lax.ragged_dot_general(lhs, rhs, sizes, dims,
                                  preferred_element_type=lhs.dtype)


def _schedule(sizes, rows):
    """The order of visits that the grouped products over ``rows`` sorted
    copies in groups of ``sizes`` share on a TPU; ``None`` elsewhere."""
    from ..ops.flash_attention import _on_tpu
    if not _on_tpu():
        return None
    from ..ops.grouped_matmul import group_metadata
    return group_metadata(sizes, rows, _row_tile(rows))


@role("experts")
def sorted_experts_ffn(x, ids, weights, w_gate, w_up, w_down, first,
                       activation, valid=None):
    """The held experts' part of a routed gated layer, nothing dropped,
    its products grouped by SORTING.

    ``x``: ``[T, D]``.  ``ids``/``weights``: ``[T, k]`` from the router,
    over all experts.  ``w_gate``/``w_up``: ``[H, F, D]`` (out, in) and
    ``w_down``: ``[H, F, D]`` (in, out) for the ``H`` experts held,
    which are experts ``first .. first + H - 1``.  ``activation``: the
    gate's, a function (``jax.nn.silu`` makes the layer a SwiGLU).
    Returns ``(y [T, D], counts [H] int32)``: ``y = sum over held e of
    w_e · down_e(activation(gate_e x) * up_e x)`` over the experts the
    token was routed to, and the token-copies that landed on each held
    expert.  A token outside ``valid`` (a ``[T]`` mask, where given) is
    neither computed nor counted: its row of ``y`` is zeros.

    The ``T · k`` copies are ordered by held expert with a stable sort;
    a copy for an expert not held here, or of a token outside ``valid``,
    goes behind them into a group that computes nothing.  ``gate``,
    ``up`` and ``down`` are grouped products over the stacked leaves as
    they lie (no copy and no gather of weights; an expert without a copy
    is not read), the routing weight is applied once, in float32, where
    each token's ``k`` rows are brought back and added.  The cost
    follows the routing: an expert computes only the copies routed to
    it, and a skew onto one expert lengthens that expert's group and
    nothing else.  A share (``H`` of ``E`` experts) still orders and
    pads all ``T · k`` copies, since every one of them may land here;
    the rows behind the held groups are gathered and never read."""
    T, D = x.shape
    k, H = ids.shape[1], w_gate.shape[0]
    M = T * k
    rows = -(-M // _row_tile(M)) * _row_tile(M)
    with role("router"):        # the permutation and the group sizes
        local = ids - first
        held = (local >= 0) & (local < H)
        if valid is not None:
            held &= valid[:, None]
        group = jnp.where(held, local, H).reshape(M)
        order = jnp.argsort(group, stable=True)      # sorted row -> copy
        place = jnp.argsort(order).reshape(T, k)     # copy -> sorted row
        token = jnp.zeros(rows, order.dtype).at[:M].set(order // k)
        counts = jnp.sum(group[:, None] == jnp.arange(H, dtype=group.dtype),
                         axis=0, dtype=jnp.int32)
    # the three products' order of visits, once: the same ``counts``,
    # rows and row tile (off the TPU there is none to make)
    schedule = _schedule(counts, rows)
    xs = x[token]
    hidden = activation(_grouped_product(xs, w_gate, counts, True, schedule)) \
        * _grouped_product(xs, w_up, counts, True, schedule)
    out = _grouped_product(hidden, w_down, counts, False, schedule)
    # the way back, one gather of ``[T, D]`` for each of a token's k
    # copies, weighted and added as it arrives: at 8192 tokens 2.4 ms
    # where one gather of all ``T · k`` rows and a sum over them took
    # 4.0 (the rows written and read again) and 4.6 as ``[T, k, D]``
    # (relaid besides): PERF.md section 6, PR 44
    y = jnp.zeros((T, D), jnp.float32)
    for j in range(k):
        back = out[place[:, j]].astype(jnp.float32) * weights[:, j, None]
        y += jnp.where(held[:, j, None], back, 0.0)
    return y.astype(x.dtype), counts


def _draw_share(link, d_model, d_expert, n_experts, held):
    """``held = (first, count)`` checked against the layer's
    ``n_experts``, and ``link``'s ``router``, ``w_gate``, ``w_up`` and
    ``w_down`` drawn for that share (LeCun normal, one seeded stream);
    returns ``(first, count)`` as ints."""
    first, count = held
    if not (0 <= first and first + count <= n_experts and count > 0):
        raise ValueError(f"held={held} is not inside the layer's "
                         f"{n_experts} experts")
    rng = np.random.RandomState(0)
    shapes = {"router": ((n_experts, d_model), d_model),
              "w_gate": ((count, d_expert, d_model), d_model),
              "w_up": ((count, d_expert, d_model), d_model),
              "w_down": ((count, d_expert, d_model), d_expert)}
    for name, (shape, fan_in) in shapes.items():
        getattr(link, name).draw(
            shape, np.float32,
            lambda shape=shape, fan_in=fan_in: rng.normal(
                0.0, fan_in ** -0.5, shape).astype(np.float32))
    return int(first), int(count)


# one body a program: a model's expert layers are alike, and jitted here
# the second and every later one of a program is a call of the first's
# lowering.  Traced layer by layer the sorts, the gathers and the two
# kernels of 4 (Kimi) or 8 (Laguna) layers added 0.2-0.4 s to each of
# the 25-29 programs an engine warms up, 3.3 s of Kimi's ``setup_s``
# (PERF.md section 6, PR 47); what the one body still cost a program
# was the grouped products' order of visits, made inside each product
# (twice a body) until ``sorted_experts_ffn`` made it once (PR 48).
@functools.partial(jax.jit, static_argnames=("k", "scale", "first"))
def _held_share(x, router, bias, w_gate, w_up, w_down, valid, *, k, scale,
                first):
    ids, w = sigmoid_topk_route(x, router, bias, k, scale)
    return sorted_experts_ffn(x, ids, w, w_gate, w_up, w_down, first,
                              jax.nn.silu, valid=valid)


class HeldExperts(Link):
    """A chip's share of a routed expert layer: the router over all
    ``n_experts`` and the SwiGLU experts ``held = (first, count)``.

    ``forward(x, valid=None)`` → ``(y, counts)``: sigmoid scores with a
    selection bias choose ``k`` of all the experts
    (:func:`sigmoid_topk_route`), and the held ones' products are grouped
    by sorting (:func:`sorted_experts_ffn`).  Under an expert-parallel
    axis of ``n_experts // count`` chips this is each chip's layer (the
    sum over chips of ``y`` is the whole layer's routed part); on one
    chip it runs as it stands, with no exchange."""

    def __init__(self, d_model, d_expert, n_experts, held, k,
                 routed_scale=1.0):
        super().__init__()
        self.n_experts, self.k = int(n_experts), int(k)
        self.routed_scale = float(routed_scale)
        with self.init_scope():
            self.router = Parameter()
            self.router_bias = Parameter()
            self.w_gate = Parameter()
            self.w_up = Parameter()
            self.w_down = Parameter()
        self.first, self.count = _draw_share(self, d_model, d_expert,
                                             n_experts, held)
        self.router_bias.draw((n_experts,), np.float32,
                              lambda: np.zeros(n_experts, np.float32))

    @role("experts")        # the call's own name; the parts keep theirs
    def forward(self, x, valid=None):
        return _held_share(x, self.router.array, self.router_bias.array,
                           self.w_gate.array, self.w_up.array,
                           self.w_down.array, valid, k=self.k,
                           scale=self.routed_scale, first=self.first)


class SortedExperts(Link):
    """A chip's share of a routed expert layer whose products are
    grouped by sorting (:func:`sorted_experts_ffn`): the router's matrix
    over all ``n_experts`` (no bias), the gated experts ``held = (first,
    count)`` and the gate's ``activation``.  The router's logits are a
    call of their own, :meth:`logits`, so that a block can take them
    from another tensor than the experts read (ahead of its attention,
    say); ``forward(x, logits, valid=None)`` chooses ``k`` by
    :func:`softmax_topk_route` and returns ``(y, counts)``.  With every
    expert held it is the whole layer on one chip."""

    def __init__(self, d_model, d_expert, n_experts, held, k, activation):
        super().__init__()
        self.k = int(k)
        self.activation = activation
        with self.init_scope():
            self.router = Parameter()
            self.w_gate = Parameter()
            self.w_up = Parameter()
            self.w_down = Parameter()
        self.first, _ = _draw_share(self, d_model, d_expert, n_experts,
                                    held)

    @role("router")
    def logits(self, h):
        """``h [T, D]`` -> the router's ``[T, n_experts]`` in float32."""
        return jnp.dot(h.astype(jnp.float32),
                       self.router.array.astype(jnp.float32).T,
                       precision=lax.Precision.HIGHEST)

    def forward(self, x, logits, valid=None):
        ids, w = softmax_topk_route(logits, self.k)
        return sorted_experts_ffn(x, ids, w, self.w_gate.array,
                                  self.w_up.array, self.w_down.array,
                                  self.first, self.activation, valid=valid)
