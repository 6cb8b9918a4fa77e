"""Parameter packing utilities.

Reference: ``chainermn/communicators/_memory_utility.py · DeviceMemory,
pack_params, unpack_params`` (SURVEY.md §2.1, N2 in §2.5) — there, CUDA
arenas and batched-copy kernels gather scattered grads into one buffer.
On TPU, packing is a ``concatenate`` *inside* the compiled step (XLA fuses
the copies); no arena management exists because XLA owns HBM.  These
helpers provide the same pack/unpack contract for the ``flat``-flavor
communicator, the bucketed gradient exchange, and flat-buffer
checkpointing.

Bucket planning (reference: pure_nccl's size-bounded gradient buckets,
SURVEY §2.5 N2): :func:`plan_buckets` partitions a leaf list into
contiguous size-bounded groups in REVERSE leaf order — backward produces
the LAST-registered parameters' gradients first, so the first emitted
bucket closes (and its collective can start) while earlier layers'
gradients are still being computed.  The plan is a pure function of
(shapes, dtypes, bound): every process traces the identical partition,
which is what makes the per-bucket collectives line up across ranks.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["pack_params", "unpack_params", "tree_pack", "tree_unpack",
           "plan_buckets", "bucket_table", "hop_schedule", "stripe_plan",
           "derived_stripe_ratio", "derived_bucket_bytes",
           "plan_buckets_from_measurement", "stripe_plan_from_measurement",
           "exchanged_bytes", "hierarchical_exchanged_bytes",
           "striped_exchanged_bytes", "moe_dispatch_exchanged_bytes",
           "pad_to_multiple", "QUANTIZED_DTYPES", "resolve_grad_dtype",
           "is_quantized_dtype", "quantize_symmetric",
           "quantize_symmetric_segments",
           "dequantize_symmetric", "quantization_residual",
           "quantized_hop_bytes"]

#: default bucket bound (MB) for the bucketed exchange —
#: ``CHAINERMN_TPU_BUCKET_MB`` overrides (reference: pure_nccl's
#: allreduce chunking; ~4 MB keeps each collective large enough to hit
#: ring bandwidth while leaving several schedulable units per step)
DEFAULT_BUCKET_MB = 4.0

#: the DOCUMENTED FALLBACK stripe ratio (ISSUE 19), used only when no
#: fabric measurement exists — NOT a silent always-answer.  The right
#: value is the slow fabric's share of the mesh's aggregate bandwidth,
#: ``derived_stripe_ratio(b_ici, b_dcn)`` (docs/performance.md §10's
#: finish-together split r* = B_dcn / (B_ici + B_dcn)); ``autotune=``
#: measures the two hops at startup and derives it per topology.  When
#: a hop is unmeasurable (axis size 1, no measurement yet) the planner
#: falls back HERE and records why in the plan's derivation notes.
#: 0.25 is the 1:3 DCN:ICI seed ratio (DCN is the narrow fabric);
#: ``CHAINERMN_TPU_STRIPE_RATIO`` / ``create_communicator(stripe_ratio=)``
#: hand-pin it and win over any derived plan.
DEFAULT_STRIPE_RATIO = 0.25


def tree_pack(tree, dtype=None):
    """Flatten a pytree of arrays into (flat_vector, spec)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(-1).astype(dtype or l.dtype) for l in leaves]) \
        if leaves else jnp.zeros((0,), dtype or jnp.float32)
    return flat, (treedef, shapes, dtypes)


def tree_unpack(flat, spec):
    treedef, shapes, dtypes = spec
    leaves = []
    offset = 0
    for shape, dt in zip(shapes, dtypes):
        n = int(np.prod(shape))
        leaves.append(flat[offset:offset + n].reshape(shape).astype(dt))
        offset += n
    return jax.tree.unflatten(treedef, leaves)


def plan_buckets(shapes, dtypes, bucket_bytes):
    """Partition leaves into size-bounded buckets of leaf INDICES.

    Deterministic pure function of the arguments (identical on every
    rank — the cross-process contract the per-bucket collectives rely
    on).  Properties, pinned by tests/communicator_tests:

    * every leaf index appears in exactly one bucket;
    * buckets are emitted in REVERSE leaf order (last-registered
      parameter first — its gradient exists first in the backward);
    * a bucket never exceeds ``bucket_bytes`` unless a single leaf does
      (an oversize leaf gets a bucket of its own);
    * a bucket never mixes dtypes: the pack is a ``concatenate``, and a
      mixed bucket would silently promote (and mis-size) the transfer.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    buckets = []
    current = []
    current_bytes = 0
    current_dtype = None
    for i in reversed(range(len(shapes))):
        dt = jnp.dtype(dtypes[i])
        nbytes = int(np.prod(shapes[i])) * dt.itemsize
        if current and (current_bytes + nbytes > bucket_bytes
                        or dt != current_dtype):
            buckets.append(current)
            current, current_bytes = [], 0
        current.append(i)
        current_bytes += nbytes
        current_dtype = dt
    if current:
        buckets.append(current)
    return buckets


def hop_schedule(n_buckets, mode="hierarchical"):
    """Emission schedule of the two-level (ici × dcn) bucketed exchange:
    ordered ``(op, bucket)`` pairs the hierarchical/striped
    ``grad_transform`` follows literally, so the ordering properties are
    a tested pure function rather than an accident of loop structure.

    ``mode="hierarchical"`` (the strict two-level exchange, ISSUE 6) —
    ops per bucket: ``"ici_reduce_scatter"`` (fast hop, full bucket) →
    ``"dcn_exchange"`` (slow hop, the 1/intra chunk) →
    ``"ici_all_gather"`` (fast hop, rebuild).  Ordering contract
    (HiCCL / the multi-process-per-GPU allreduce paper's hop-overlap
    result — ROADMAP item 1):

    * within a bucket: reduce_scatter < dcn_exchange < all_gather
      (dataflow);
    * buckets enter the schedule in PLAN order (reverse registration —
      the first bucket to close in backward reaches the wire first);
    * EVERY slow-hop op precedes EVERY fast-hop all_gather: all DCN
      transfers are issued before any ICI rebuild, so the slow hop
      starts as early as dataflow allows and the ICI all-gathers
      overlap the remaining DCN traffic instead of serializing ahead
      of it.

    ``mode="striped"`` (ISSUE 11, the multi-path exchange) — each
    bucket's payload is split by :func:`stripe_plan` into an ICI-path
    slice (fast-hop-major exchange: rs over ICI → chunk crossing over
    DCN → ag over ICI) and a DCN-path slice (the TRANSPOSED, slow-hop-
    major exchange: rs over DCN → chunk crossing over ICI → ag over
    DCN), so both fabrics carry bulk traffic at the same time instead
    of hierarchically (FlexLink's use-every-link-at-once result).  Ops
    per bucket: ``dcn_path_scatter`` → ``ici_path_scatter`` →
    ``dcn_path_exchange`` → ``ici_path_exchange``, then per-bucket
    epilogue ``dcn_path_gather`` → ``ici_path_gather``.  Ordering
    contract, generalized from the hierarchical one:

    * within a bucket and phase, the SLOW path's op is issued first
      (its wire is the long pole);
    * per path, dataflow order holds (scatter < exchange < gather);
    * BOTH paths' scatter+exchange ops of every bucket precede ANY
      bucket's gather epilogue — the two paths are concurrently
      eligible end to end, and the rebuilds overlap whatever bulk
      traffic is still draining on either fabric.  This is the
      per-path ordering the generalized census ``hop_ordered`` gate
      validates.

    ``mode="moe"`` (ISSUE 12, the two-stage expert-parallel token
    exchange) — one "bucket" is one MoE layer's dispatch buffer.  Ops
    per bucket: ``ici_dispatch`` (fast hop: tokens regroup by
    destination SLOT within the host, so tokens whose expert lives
    on-host finish here) → ``dcn_dispatch`` (slow hop: only the
    off-host remainder crosses — issued immediately after its fast
    stage, as early as dataflow allows), then the combine epilogue
    ``dcn_combine`` → ``ici_combine`` — the TRANSPOSED reverse, slow
    hop first again so the combine's DCN crossing starts the moment
    the expert compute closes.  The two stages commute as index
    permutations (they act on disjoint buffer dims), so this order is
    a schedule CHOICE with the same result content — pinned here as a
    pure function the dispatch follows literally, like every other
    exchange.
    """
    if n_buckets < 0:
        raise ValueError(f"n_buckets must be >= 0, got {n_buckets}")
    if mode not in ("hierarchical", "striped", "moe"):
        raise ValueError(f"unknown hop_schedule mode {mode!r}")
    schedule = []
    if mode == "moe":
        for b in range(n_buckets):
            schedule.append(("ici_dispatch", b))
            schedule.append(("dcn_dispatch", b))
        for b in range(n_buckets):
            schedule.append(("dcn_combine", b))
            schedule.append(("ici_combine", b))
        return schedule
    if mode == "striped":
        for b in range(n_buckets):
            schedule.append(("dcn_path_scatter", b))
            schedule.append(("ici_path_scatter", b))
            schedule.append(("dcn_path_exchange", b))
            schedule.append(("ici_path_exchange", b))
        for b in range(n_buckets):
            schedule.append(("dcn_path_gather", b))
            schedule.append(("ici_path_gather", b))
        return schedule
    for b in range(n_buckets):
        schedule.append(("ici_reduce_scatter", b))
        schedule.append(("dcn_exchange", b))
    for b in range(n_buckets):
        schedule.append(("ici_all_gather", b))
    return schedule


def stripe_plan(n_elems, ratio):
    """Contiguous two-slice split of a bucket's flat payload for the
    striped exchange: ``(ici_elems, dcn_elems)`` with the ICI-path slice
    at ``flat[:ici_elems]`` and the DCN-path slice at
    ``flat[ici_elems:]``.

    Deterministic pure function of ``(n_elems, ratio)`` — every rank
    traces the identical split, the same cross-process contract
    :func:`plan_buckets` carries.  Properties, pinned by
    tests/communicator_tests:

    * every element lands in exactly one slice
      (``ici_elems + dcn_elems == n_elems``);
    * both slices are contiguous (one split point — the pack stays two
      cheap dynamic slices, never a gather);
    * the DCN share is the committed ratio rounded to whole elements
      (``dcn_elems == round(ratio * n_elems)``);
    * degenerate ratios collapse to a single path: ``ratio == 0`` is
      the strict hierarchical exchange (everything fast-hop-major),
      ``ratio == 1`` routes the whole payload over the slow-hop-major
      path (the flat-one-fabric shape with DCN as the bulk wire).

    The ratio itself is a per-topology constant (like ``bucket_mb``):
    the split that equalizes the two paths' finish times is the ratio of
    the fabrics' measured bandwidths (:func:`derived_stripe_ratio`).
    """
    if not 0.0 <= ratio <= 1.0:
        raise ValueError(f"stripe ratio must be in [0, 1], got {ratio}")
    if n_elems < 0:
        raise ValueError(f"n_elems must be >= 0, got {n_elems}")
    dcn_elems = int(round(ratio * n_elems))
    return n_elems - dcn_elems, dcn_elems


# -- measurement-driven planning (ISSUE 19) ----------------------------------
def derived_stripe_ratio(b_ici, b_dcn):
    """The finish-together DCN share from MEASURED per-hop bandwidths —
    docs/performance.md §10's ``r* = B_dcn / (B_ici + B_dcn)``: both
    paths of the striped exchange drain at the same instant when each
    fabric carries bytes in proportion to its bandwidth.

    Deterministic pure function of the two bandwidths (any consistent
    unit — only the ratio matters).  Properties, pinned by
    tests/communicator_tests/test_autotune.py:

    * monotone non-decreasing in ``b_dcn`` (a faster slow fabric earns
      a larger share) and non-increasing in ``b_ici``;
    * recovers :data:`DEFAULT_STRIPE_RATIO` (0.25) exactly at the 1:3
      DCN:ICI seed ratio;
    * clamped to the OPEN interval (0, 1): a derived plan never
      collapses the striped exchange to a degenerate single path —
      hand knobs may pin 0 or 1, the planner never does;
    * non-finite or non-positive bandwidths raise (an unmeasured hop is
      the caller's fallback branch, never a silent 0-bandwidth input).
    """
    b_ici, b_dcn = float(b_ici), float(b_dcn)
    if not (np.isfinite(b_ici) and np.isfinite(b_dcn)) \
            or b_ici <= 0 or b_dcn <= 0:
        raise ValueError(
            f"derived_stripe_ratio needs positive finite per-hop "
            f"bandwidths, got b_ici={b_ici!r} b_dcn={b_dcn!r}; an "
            f"unmeasured hop falls back to DEFAULT_STRIPE_RATIO "
            f"explicitly at the call site")
    ratio = b_dcn / (b_ici + b_dcn)
    eps = 1e-6
    return min(1.0 - eps, max(eps, ratio))


def derived_bucket_bytes(gbps, lat_us, overhead_frac=0.125,
                         floor_mb=1.0, cap_mb=32.0):
    """Bucket bound (BYTES) from a measured hop's (bandwidth, latency):
    the smallest bucket whose wire time keeps per-collective launch
    overhead under ``overhead_frac`` of the transfer —
    ``bytes = bandwidth × latency / overhead_frac`` — clamped to
    [``floor_mb``, ``cap_mb``] MB and rounded to 2 significant digits
    so the derived knob is a stable, human-readable census value
    rather than a noisy float.

    Deterministic pure function; small buckets stay schedulable (the
    overlap property §7 measures), huge buckets would serialize the
    exchange behind backward, hence the cap.
    """
    gbps, lat_us = float(gbps), float(lat_us)
    if not (np.isfinite(gbps) and np.isfinite(lat_us)) \
            or gbps <= 0 or lat_us < 0:
        raise ValueError(
            f"derived_bucket_bytes needs a positive finite bandwidth "
            f"and a non-negative latency, got gbps={gbps!r} "
            f"lat_us={lat_us!r}")
    raw = gbps * 1e9 * (lat_us * 1e-6) / float(overhead_frac)
    mb = min(float(cap_mb), max(float(floor_mb), raw / (1 << 20)))
    if mb > 0:
        from math import floor, log10
        digits = 1 - int(floor(log10(abs(mb))))
        mb = round(mb, digits)
    return int(round(min(float(cap_mb), max(float(floor_mb), mb))
                     * (1 << 20)))


def plan_buckets_from_measurement(shapes, dtypes, gbps, lat_us,
                                  overhead_frac=0.125):
    """:func:`plan_buckets` with the bound DERIVED from a measured hop
    (the measurement-driven entry point ``autotune=`` calls) — the
    partition properties are exactly :func:`plan_buckets`'s."""
    return plan_buckets(shapes, dtypes,
                        derived_bucket_bytes(gbps, lat_us,
                                             overhead_frac=overhead_frac))


def stripe_plan_from_measurement(n_elems, b_ici, b_dcn):
    """:func:`stripe_plan` with the ratio DERIVED from measured per-hop
    bandwidths (the measurement-driven entry point ``autotune=``
    calls) — the split properties are exactly :func:`stripe_plan`'s."""
    return stripe_plan(n_elems, derived_stripe_ratio(b_ici, b_dcn))


def pad_to_multiple(flat, multiple):
    """Zero-pad a 1-D vector up to the next multiple (a tiled
    ``psum_scatter``/``all_gather`` needs the scattered dim divisible by
    the axis size).  Returns ``(padded, true_length)``."""
    n = flat.shape[0]
    n_pad = -(-n // multiple) * multiple
    if n_pad == n:
        return flat, n
    return jnp.pad(flat, (0, n_pad - n)), n


# -- quantized wire dtypes (ISSUE 8) ----------------------------------------
#: wire dtypes the compressed gradient exchange quantizes to, mapped to
#: the largest magnitude each can represent (the symmetric-scale
#: target).  int8 uses the symmetric range ±127 (−128 is never emitted
#: — a symmetric codebook keeps Q(−v) == −Q(v), so the residual math
#: telescopes without a sign bias).  The fp8 names follow the ISSUE's
#: spelling; jax's dtype is the OCP ``e4m3fn`` variant (finite-only,
#: max 448) and ``e5m2`` (max 57344).
QUANTIZED_DTYPES = {
    "int8": 127.0,
    "float8_e4m3": 448.0,
    "float8_e5m2": 57344.0,
}

def resolve_grad_dtype(dtype):
    """``allreduce_grad_dtype`` entry → jnp dtype, accepting the
    quantized wire names (``"float8_e4m3"`` resolves to jax's
    ``float8_e4m3fn``).  ``None`` passes through (lossless)."""
    if dtype is None:
        return None
    name = str(dtype)
    if name in ("float8_e4m3", "float8_e4m3fn"):
        return jnp.dtype(jnp.float8_e4m3fn)
    if name == "float8_e5m2":
        return jnp.dtype(jnp.float8_e5m2)
    return jnp.dtype(dtype)


def _quant_key(dtype):
    """Canonical QUANTIZED_DTYPES key of a dtype, or ``None``."""
    if dtype is None:
        return None
    name = str(jnp.dtype(dtype) if not isinstance(dtype, str) else dtype)
    name = {"float8_e4m3fn": "float8_e4m3"}.get(name, name)
    return name if name in QUANTIZED_DTYPES else None


def is_quantized_dtype(dtype):
    """True for the int8/fp8 wire dtypes the quantized exchange owns
    (bf16/fp16 are plain casts — they ride the lossy-cast path, not the
    scale+residual machinery)."""
    return _quant_key(dtype) is not None


def quantize_symmetric(v, wire_dtype):
    """Per-bucket symmetric quantization: ``(q, scale)`` with
    ``q ≈ v / scale`` stored in ``wire_dtype`` and
    ``scale = absmax(v) / qmax``.

    Contract (pinned by tests/communicator_tests/test_quantization.py):

    * **deterministic** — a pure elementwise function of ``v``; every
      rank quantizing the same buffer computes the same ``(q, scale)``
      (the cross-rank agreement the dequantize-sum relies on);
    * **zero-safe** — an all-zero (or empty) bucket quantizes to zeros
      with ``scale = 1`` (never a 0/0);
    * **non-finite-safe** — ``±inf`` saturates to ``±qmax`` (the scale
      is computed over the FINITE values only, so one overflowed
      gradient cannot zero out the rest of the bucket); ``NaN`` encodes
      as 0.  The residual for non-finite inputs is defined as 0 by
      :func:`quantization_residual` — error feedback must not turn one
      bad step into a permanently poisoned buffer.

    Round-trip bound: for finite ``v``, ``|v − q·scale| ≤ scale/2``
    per element for int8 (round-to-nearest on a uniform codebook) and
    ``≤ absmax · 2^−m`` relative for fp8 with ``m`` mantissa bits.
    """
    wire = resolve_grad_dtype(wire_dtype)
    qmax = QUANTIZED_DTYPES[_quant_key(wire)]
    v = v.astype(jnp.float32)
    finite = jnp.isfinite(v)
    absmax = jnp.max(jnp.abs(jnp.where(finite, v, 0.0))) \
        if v.size else jnp.float32(0.0)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
    scaled = jnp.clip(jnp.where(jnp.isnan(v), 0.0, v) / scale,
                      -qmax, qmax)
    if jnp.issubdtype(wire, jnp.integer):
        q = jnp.round(scaled).astype(wire)
    else:
        q = scaled.astype(wire)
    return q, scale


def dequantize_symmetric(q, scale):
    """Inverse of :func:`quantize_symmetric`: ``q·scale`` in f32."""
    return q.astype(jnp.float32) * scale


def quantize_symmetric_segments(v, wire_dtype):
    """Per-SEGMENT symmetric quantization along the leading axis: one
    ``(q, scale)`` pair per segment, via :func:`quantize_symmetric`
    vmapped over ``v[0]`` — the MoE dispatch's slow-crossing codebook
    (ISSUE 12).  Each destination group's block quantizes with its OWN
    scale (one absmax per segment, so a hot expert's activations cannot
    flatten a quiet one's codewords), and the ``[segments]`` scale
    vector ships alongside the codewords on its own tiny collective.
    Inherits quantize_symmetric's determinism/zero/non-finite
    contracts per segment.  Returns ``(q [S, ...], scales [S])``."""
    return jax.vmap(lambda seg: quantize_symmetric(seg, wire_dtype))(v)


def quantization_residual(v, q, scale):
    """Error-feedback residual ``v − Q(v)``, sanitized: positions where
    ``v`` was non-finite carry 0 (their information is unrepresentable —
    carrying ±inf/NaN forward would poison every later step)."""
    v = v.astype(jnp.float32)
    r = v - dequantize_symmetric(q, scale)
    return jnp.where(jnp.isfinite(v) & jnp.isfinite(r), r, 0.0)


def quantize_with_feedback(v, residual, wire_dtype):
    """The one quantization prologue every compressed hop shares (flat
    transform, hierarchical DCN branch, sharded-update slow hop):
    ``v`` is accumulated in f32, the carried ``residual`` (or ``None``
    when error feedback is off) is added before quantizing, and the new
    residual ``v − Q(v)`` is returned (``None`` without feedback).
    Returns ``(q, scale, new_residual)``."""
    v = v.astype(jnp.float32)
    if residual is not None:
        v = v + residual
    q, scale = quantize_symmetric(v, wire_dtype)
    new_residual = quantization_residual(v, q, scale) \
        if residual is not None else None
    return q, scale, new_residual


def dequantize_sum(q_stacked, scales):
    """Sum of per-rank dequantized buffers: ``q_stacked`` is the
    gathered ``(size, n)`` codewords, ``scales`` the gathered ``(size,)``
    per-rank scales — each rank's codewords decode with ITS OWN scale
    before the f32 accumulation (summing codewords directly would be
    meaningless across scales)."""
    return jnp.sum(dequantize_symmetric(q_stacked, scales[:, None]),
                   axis=0)


def quantized_hop_bytes(chunk_elems, size, collective, wire_dtype):
    """Per-replica wire bytes of the QUANTIZED slow-hop exchange on a
    ``chunk_elems`` per-rank chunk over ``size`` ranks, priced at the
    wire dtype's itemsize (the packed buffer that actually crosses —
    never the gradient dtype's):

    * ``"psum"`` (the hierarchical allreduce's DCN hop): implemented as
      an ``all_gather`` of the quantized chunk + dequantize-sum —
      ``chunk_q · (size−1)`` per replica.  vs the f32 chunk allreduce's
      ``8 · chunk · (size−1)/size`` this is ``itemsize·size/8`` of the
      lossless crossing: exactly the quantized fraction at ``size=2``
      (1/4 for int8), break-even at ``size = 8/itemsize`` — the
      decision table in docs/performance.md §9.
    * ``"reduce_scatter"`` (the sharded-update DCN hop): an
      ``all_to_all`` of the quantized chunk's segments —
      ``chunk_q · (size−1)/size``: exactly the quantized fraction of
      the f32 reduce-scatter crossing at ANY ``size``.

    The per-bucket scale scalars also cross (one f32 ``all_gather`` per
    bucket) — O(buckets), excluded here as they are from the census's
    gradient rows (below ``GRAD_ELEMS_FLOOR``).
    """
    if size <= 1:
        return 0
    itemsize = resolve_grad_dtype(wire_dtype).itemsize
    n_bytes = chunk_elems * itemsize
    if collective == "psum":
        return int(n_bytes * (size - 1))
    if collective == "reduce_scatter":
        return int(n_bytes * (size - 1) / size)
    raise ValueError(f"unknown quantized collective {collective!r}")


def bucket_table(shapes, dtypes, bucket_bytes):
    """Human/probe-facing accounting of a bucket plan: one row per
    bucket with its leaf count, element count, bytes, and dtype."""
    rows = []
    for b, idx in enumerate(plan_buckets(shapes, dtypes, bucket_bytes)):
        dt = jnp.dtype(dtypes[idx[0]])
        elems = sum(int(np.prod(shapes[i])) for i in idx)
        rows.append({"bucket": b, "n_leaves": len(idx),
                     "elems": elems, "bytes": elems * dt.itemsize,
                     "dtype": str(dt)})
    return rows


def exchanged_bytes(n_bytes, size, collective):
    """Per-replica wire bytes of one collective on an ``n_bytes`` FULL
    buffer (for ``all_gather``, the gathered result — chunk × size)
    over ``size`` ranks, under the standard ring/bandwidth-optimal
    decomposition (the accounting tools/comm_budgets.json commits):

    * ``psum`` (allreduce)   → ``2 · n · (size-1)/size``
      (reduce-scatter phase + all-gather phase)
    * ``reduce_scatter``     → ``n · (size-1)/size``
    * ``all_gather``         → ``n · (size-1)/size``
    * ``all_to_all``         → ``n · (size-1)/size``
      (each rank keeps its own segment; the quantized reduce-scatter
      rides this — every segment crosses once, priced at the operand's
      own wire dtype)

    This is why the reduce-scatter update halves per-replica exchanged
    GRADIENT bytes vs allreduce: the gradient crosses the wire once
    (reduce-scatter) instead of twice; the step's other transfer — the
    params all-gather — is parameter bytes, accounted separately.
    """
    if size <= 1:
        return 0
    frac = (size - 1) / size
    if collective == "psum":
        return int(2 * n_bytes * frac)
    if collective in ("reduce_scatter", "all_gather", "all_to_all"):
        return int(n_bytes * frac)
    raise ValueError(f"unknown collective {collective!r}")


def hierarchical_exchanged_bytes(n_bytes, intra_size, inter_size,
                                 collective="psum", dcn_n_bytes=None):
    """Per-replica wire bytes of the two-level (ici × dcn) exchange on an
    ``n_bytes`` FULL buffer, split by hop: ``{"ici": ..., "dcn": ...}``.

    The slow hop only ever sees the 1/intra chunk the ICI reduce-scatter
    leaves on each device — the tentpole's byte contract (DCN payload =
    ``n_bytes / intra_size``).  ``dcn_n_bytes`` overrides that chunk's
    byte count for the per-hop-dtype variant (bf16 over DCN while ICI
    stays lossless: half the chunk bytes on the slow hop only).

    * ``"psum"`` (the hierarchical allreduce exchange):
      ICI carries the reduce-scatter AND the all-gather phase
      (``2·n·(intra-1)/intra``); DCN carries a chunk allreduce
      (``2·chunk·(inter-1)/inter``).
    * ``"reduce_scatter"`` / ``"all_gather"`` (the hierarchical DP
      update's gradient / params-rebuild halves): one crossing per hop
      (``n·(intra-1)/intra`` over ICI, ``chunk·(inter-1)/inter`` over
      DCN).

    Identity, pinned by tests: with matching dtypes the hop totals sum
    to the flat ring figure over ``intra·inter`` ranks —
    ``2n(intra-1)/intra + 2(n/intra)(inter-1)/inter =
    2n(intra·inter-1)/(intra·inter)`` — the hierarchy relocates bytes
    onto the fast wires, it does not add any.
    """
    if intra_size < 1 or inter_size < 1:
        raise ValueError(
            f"intra_size/inter_size must be >= 1, got "
            f"{intra_size}/{inter_size}")
    if n_bytes % intra_size:
        # callers pad buckets to a multiple of intra before the wire
        raise ValueError(
            f"n_bytes={n_bytes} not divisible by intra_size={intra_size} "
            f"(pad_to_multiple the bucket first — the accounting must "
            f"match the traced buffer)")
    chunk = n_bytes // intra_size if dcn_n_bytes is None else dcn_n_bytes
    ici = exchanged_bytes(n_bytes, intra_size, "reduce_scatter")
    dcn = exchanged_bytes(chunk, inter_size, "reduce_scatter")
    if collective == "psum":
        return {"ici": 2 * ici, "dcn": 2 * dcn}
    if collective in ("reduce_scatter", "all_gather"):
        return {"ici": ici, "dcn": dcn}
    raise ValueError(f"unknown collective {collective!r}")


def striped_exchanged_bytes(n_bytes, intra_size, inter_size, ratio,
                            itemsize=4, dcn_itemsize=None):
    """Per-replica wire bytes of the STRIPED exchange (ISSUE 11) on an
    ``n_bytes`` full buffer, split by PATH and by FABRIC::

        {"ici_path": {"ici": ..., "dcn": ..., "total": ...},
         "dcn_path": {"ici": ..., "dcn": ..., "total": ...}}

    The ICI-path slice (share ``1 - ratio``) runs the fast-hop-major
    exchange — its bulk (rs + ag) rides ICI, only its ``1/intra`` chunk
    allreduce crosses DCN.  The DCN-path slice (share ``ratio``) runs
    the TRANSPOSED slow-hop-major exchange — its bulk rides DCN, only
    its ``1/inter`` chunk allreduce crosses ICI.  Each path is priced by
    :func:`hierarchical_exchanged_bytes` with its own (fast, slow)
    orientation.

    Identities, pinned by tests (exact when the split divides cleanly;
    each slice otherwise pads to its ring multiple exactly like the
    wire does — ``pad_to_multiple`` before the bulk scatter — so the
    figures track the traced program, with the usual pad slack):

    * **conservation**: ``ici_path.total + dcn_path.total`` equals the
      flat allreduce's per-replica figure over ``intra × inter`` ranks
      (each path's hop totals already telescope to the flat ring figure
      for its slice — striping relocates bytes, it adds none);
    * **committed share**: ``dcn_path.total / grand total == ratio`` —
      per-path totals are proportional to slice sizes, so the DCN
      path's byte share IS the committed split ratio.

    ``dcn_itemsize`` prices only the DCN-fabric crossings at a
    different wire dtype (the per-hop-dtype variant: the ICI-path
    chunk's DCN allreduce AND the DCN-path slice's bulk rs/ag both ride
    the compressed wire, ICI stays lossless).  The DCN-path slice's ICI
    chunk crossing is always priced at f32 — the transform upcasts it
    before the fast-hop allreduce (lossless-over-ICI by design).

    This is the ONE per-path pricing of the striped exchange: the
    committed census identities (tools/comm_budgets.json) are held to
    it.
    """
    elems = n_bytes // itemsize
    if elems * itemsize != n_bytes:
        raise ValueError(
            f"n_bytes={n_bytes} is not a multiple of itemsize={itemsize}")
    ici_elems, dcn_elems = stripe_plan(elems, ratio)
    n_i = -(-ici_elems // intra_size) * intra_size * itemsize
    n_d = -(-dcn_elems // inter_size) * inter_size * itemsize
    dcn_scale = (dcn_itemsize / itemsize) if dcn_itemsize else 1.0
    # fast-hop-major path: hierarchical_exchanged_bytes as-is (the
    # per-hop-dtype override compresses only its DCN chunk crossing)
    a = hierarchical_exchanged_bytes(
        n_i, intra_size, inter_size, "psum",
        dcn_n_bytes=int(n_i // intra_size * dcn_scale)
        if dcn_itemsize else None) if n_i else {"ici": 0, "dcn": 0}
    # slow-hop-major path: the same formula with the hops TRANSPOSED —
    # its "intra" ring is the DCN axis (bulk rs+ag, compressed under the
    # per-hop dtype) and its chunk crossing rides ICI (lossless by
    # design: the chunk upcasts to f32 before the fast-hop allreduce);
    # relabel the returned hops back to fabrics
    b = hierarchical_exchanged_bytes(
        int(n_d * dcn_scale), inter_size, intra_size, "psum",
        dcn_n_bytes=n_d // itemsize // inter_size * 4) \
        if n_d else {"ici": 0, "dcn": 0}
    ici_path = {"ici": a["ici"], "dcn": a["dcn"]}
    dcn_path = {"dcn": b["ici"], "ici": b["dcn"]}
    for p in (ici_path, dcn_path):
        p["total"] = p["ici"] + p["dcn"]
    return {"ici_path": ici_path, "dcn_path": dcn_path}


def moe_dispatch_exchanged_bytes(n_bytes, intra_size, inter_size,
                                 two_stage=True, dcn_n_bytes=None):
    """Per-replica wire bytes of ONE MoE layer's token exchange — the
    dispatch + combine round trip on an ``n_bytes`` capacity buffer
    (``[E, C, D]`` at the compute wire dtype) — split by fabric
    (ISSUE 12):

    * ``two_stage=True``: an ``all_to_all`` over ICI each way
      (``n·(intra−1)/intra``) plus an ``all_to_all`` over DCN each way
      carrying only the off-host remainder (``n·(inter−1)/inter`` —
      the ring keeps the own-host segment local, so the slow-fabric
      bill IS the ``off_host_dispatch_ratio`` share of the buffer).
      ``dcn_n_bytes`` overrides the slow crossing's buffer bytes for
      the compressed variants (bf16 halves it, int8/fp8 quarter it;
      the per-segment scale vectors are O(inter) — excluded, like the
      gradient census's scale gathers).  Returns ``{"ici", "dcn"}``.
    * ``two_stage=False``: the flat single collective — one
      ``all_to_all`` each way over the JOINT ``intra·inter`` ring
      (``n·(E−1)/E``), one fabric label, unsplittable and
      uncompressible per hop.  Returns ``{"world": ...}``.

    This is the ONE pricing of the MoE dispatch: the committed MoE
    census identities (tools/comm_budgets.json) are held to it.
    """
    if two_stage:
        ici = exchanged_bytes(n_bytes, intra_size, "all_to_all")
        dcn = exchanged_bytes(
            n_bytes if dcn_n_bytes is None else dcn_n_bytes,
            inter_size, "all_to_all")
        return {"ici": 2 * ici, "dcn": 2 * dcn}
    world = exchanged_bytes(n_bytes, intra_size * inter_size,
                            "all_to_all")
    return {"world": 2 * world}


def pack_params(params, attr="grad", dtype=None):
    """Pack ``param.<attr>`` of a parameter list into one flat vector.

    Reference-shaped API (``pack_params(params, 'grad', buffer)``); returns
    (flat, spec) instead of filling a caller-owned arena.
    """
    arrays = [getattr(p, attr) for p in params]
    return tree_pack(arrays, dtype=dtype)


def unpack_params(params, flat, spec, attr="grad"):
    arrays = tree_unpack(flat, spec)
    for p, a in zip(params, arrays):
        setattr(p, attr, a)
