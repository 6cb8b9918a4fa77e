"""Collective census of the compiled DP train step (ISSUE 5).

The gradient-exchange structure — how many collectives the step emits,
over which buffers, in which pattern — is a property of what the
framework TRACES, identical on every backend.  This tool extracts it
from the step's jaxpr and commits it to ``tools/comm_budgets.json``,
where ``tests/test_comm_budget.py`` holds every future PR to it
(mirroring tools/flash_budgets.json / tools/hbm_budgets.json):

* ``per_leaf``      — one mean-``psum`` per parameter leaf
* ``flat``          — ONE monolithic flat-bucket ``psum``
* ``bucketed``      — K size-bounded bucket ``psum``s (default ~4 MB,
                      reverse registration order — the schedulable units
                      XLA's async scheduler overlaps with backward)
* ``bucketed_bf16`` — the same composed with dtype compression
* ``reduce_scatter`` — ``reduce_scatter(grads) → shard update →
                      all_gather(params)``: the full-gradient allreduce
                      is GONE from the census and per-replica exchanged
                      gradient bytes halve
* ``hierarchical*``  — the two-level (ici × dcn) exchange (ISSUE 6) on
                      a SIMULATED 2-host split of the 8-device mesh
                      (``inter_size=2`` → dcn 2 × ici 4): per-hop
                      collectives with axis-name-resolved counts, the
                      DCN gradient payload pinned at exactly
                      ``1/ici_size`` of the full gradient, the
                      slow-hop-first emission order
                      (``hop_schedule``), and per-hop dtype
                      (``hierarchical_dcn_bf16`` halves only the DCN
                      crossing)
* ``hierarchical_int8`` / ``hierarchical_fp8`` / ``hierarchical_rs_int8``
                    — the QUANTIZED slow hop (ISSUE 8): the DCN psum is
                      replaced by quantize → ``all_gather`` (allreduce
                      exchange) or ``all_to_all`` (sharded update) of
                      the int8/fp8 payload + dequantize-sum, with the
                      per-bucket scale scalars riding tiny all_gathers
                      (below the gradient floor).  Every row is priced
                      at its OWN operand dtype — the WIRE dtype of the
                      packed buffer, so the committed
                      ``dcn_payload_bytes_ratio`` pins the quantized
                      fraction from the trace (int8 crossings ≤ 1/4 of
                      f32), and unknown collective primitives are a
                      hard census error, never a silent skip.

The census runs on the CPU mesh (tests/conftest.py's simulated 8
devices) over a small-but-real transformer vertical whose gradients
exceed the default bucket bound, so ``bucketed`` provably emits K>1
collectives at the DEFAULT bucket size.  Every census row resolves the
collective's mesh AXES, so the hierarchical configs commit which hop
each transfer rides — the per-hop structure the tentpole promises is
machine-checked, not narrated.

ISSUE 12 adds a sibling ``moe`` section: the MoE token-dispatch census
(configs ``moe_flat`` / ``moe_two_stage`` / ``moe_two_stage_bf16`` /
``moe_two_stage_int8`` on the same simulated 2×4 split) — per-hop
``all_to_all`` counts and wire dtypes of the two-stage (ici → dcn)
exchange, the ``off_host_dispatch_ratio`` of the committed split, and
the trace-pinned ``dcn_dispatch_bytes_ratio`` showing the slow
crossing carries exactly the off-host remainder at the wire dtype
(lossless = the ratio, bf16 = half, int8 = a quarter).

Unlike the flash/HBM budgets' measured halves, the structure section
here may be (re)generated off-chip — it is a trace property —
``python tools/comm_census.py --write-budgets``.  The ``sweep`` section
(on-chip bucket-MB sweep + the ≥2-host exposed-comm A/B) is measured:
its rows come from a chip run and the numeric gate arms only when its
status says ``measured``.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "comm_budgets.json")

#: collective primitives the census recognizes (jaxpr names; ``pmean``
#: lowers to ``psum`` + divide, so the mean collectives appear as psum)
COLLECTIVE_PRIMS = ("psum", "reduce_scatter", "all_gather", "all_to_all",
                    "ppermute")

#: operand-element floor separating GRADIENT-exchange collectives from
#: bookkeeping ones (loss/observation pmeans are scalars; the smallest
#: parameter leaf of the vertical is a 256-wide bias) — well between 1
#: and 256, robust to both drifting
GRAD_ELEMS_FLOOR = 16

#: the committed vertical: small enough to trace in seconds on CPU,
#: large enough that f32 AND bf16 gradients exceed the default 4 MB
#: bucket bound (param count ~5.8M → ~23 MB f32 / ~11.6 MB bf16)
VERTICAL = dict(n_vocab=8192, d_model=256, n_heads=4, n_layers=2,
                max_len=64, bs=8, seq=32)

#: simulated 2-host split for the hierarchical configs (8 devices →
#: dcn 2 × ici 4); the DCN payload ratio below is pinned to 1/ici
HIER_INTER_SIZE = 2

#: committed DCN share of the striped configs (ISSUE 11).  0.25 splits
#: the vertical's 5,790,720-element gradient into slices that divide
#: BOTH rings cleanly (dcn slice 1,447,680 % 2 == 0, ici slice
#: 4,343,040 % 4 == 0), so the byte-conservation identity is pinned
#: EXACT — no pad slack muddies the gate
STRIPE_RATIO = 0.25

CONFIGS = {
    "per_leaf": dict(batch_collectives=False, grad_dtype=None,
                     exchange="allreduce"),
    "flat": dict(batch_collectives=True, grad_dtype=None,
                 exchange="allreduce"),
    "bucketed": dict(batch_collectives="bucketed", grad_dtype=None,
                     exchange="allreduce"),
    "bucketed_bf16": dict(batch_collectives="bucketed",
                          grad_dtype="bfloat16", exchange="allreduce"),
    "reduce_scatter": dict(batch_collectives=True, grad_dtype=None,
                           exchange="reduce_scatter"),
    "hierarchical": dict(batch_collectives=True, grad_dtype=None,
                         exchange="allreduce", comm="hierarchical",
                         inter_size=HIER_INTER_SIZE),
    "hierarchical_bucketed": dict(batch_collectives="bucketed",
                                  grad_dtype=None, exchange="allreduce",
                                  comm="hierarchical",
                                  inter_size=HIER_INTER_SIZE),
    "hierarchical_dcn_bf16": dict(batch_collectives=True,
                                  grad_dtype={"dcn": "bfloat16"},
                                  exchange="allreduce",
                                  comm="hierarchical",
                                  inter_size=HIER_INTER_SIZE),
    "hierarchical_rs": dict(batch_collectives=True, grad_dtype=None,
                            exchange="reduce_scatter",
                            comm="hierarchical",
                            inter_size=HIER_INTER_SIZE),
    "hierarchical_int8": dict(batch_collectives=True,
                              grad_dtype={"dcn": "int8"},
                              exchange="allreduce",
                              comm="hierarchical",
                              inter_size=HIER_INTER_SIZE),
    "hierarchical_fp8": dict(batch_collectives=True,
                             grad_dtype={"dcn": "float8_e4m3"},
                             exchange="allreduce",
                             comm="hierarchical",
                             inter_size=HIER_INTER_SIZE),
    "hierarchical_rs_int8": dict(batch_collectives=True,
                                 grad_dtype={"dcn": "int8"},
                                 exchange="reduce_scatter",
                                 comm="hierarchical",
                                 inter_size=HIER_INTER_SIZE),
    # ISSUE 11: the striped multi-path configs — each bucket's payload
    # splits by STRIPE_RATIO; the DCN-path slice runs the transposed
    # slow-hop-major exchange concurrently with the fast-hop-major
    # remainder, so both fabrics carry bulk traffic at once
    "striped": dict(batch_collectives=True, grad_dtype=None,
                    exchange="allreduce", comm="hierarchical",
                    inter_size=HIER_INTER_SIZE,
                    stripe_ratio=STRIPE_RATIO),
    "striped_bucketed": dict(batch_collectives="bucketed",
                             grad_dtype=None, exchange="allreduce",
                             comm="hierarchical",
                             inter_size=HIER_INTER_SIZE,
                             stripe_ratio=STRIPE_RATIO),
    "striped_dcn_bf16": dict(batch_collectives=True,
                             grad_dtype={"dcn": "bfloat16"},
                             exchange="allreduce", comm="hierarchical",
                             inter_size=HIER_INTER_SIZE,
                             stripe_ratio=STRIPE_RATIO),
    "striped_rs": dict(batch_collectives=True, grad_dtype=None,
                       exchange="reduce_scatter", comm="hierarchical",
                       inter_size=HIER_INTER_SIZE,
                       stripe_ratio=STRIPE_RATIO),
}

#: the MoE dispatch vertical (ISSUE 12): tokens-per-rank/d_model sized
#: so the [E, C, D] capacity buffer (8 experts × capacity 8 × 32 =
#: 2048 elems) clears GRAD_ELEMS_FLOOR while the per-segment scale
#: vectors ([inter] = 2 elems) stay below it, like the gradient
#: census's scale gathers
MOE_VERTICAL = dict(tokens_per_rank=64, d_model=32, capacity_factor=1.0)

#: committed MoE dispatch configs (ISSUE 12), all traced on the
#: simulated 2-host (dcn 2 × ici 4) split: the flat single-axis
#: reference (the explicit ``two_stage=False`` escape on the SAME
#: topology — its one all_to_all rides the joint axis pair), the
#: lossless two-stage exchange, and the compressed DCN crossings
#: (bf16 cast / int8 per-segment codewords)
MOE_CONFIGS = {
    "moe_flat": dict(two_stage=False, grad_dtype=None),
    "moe_two_stage": dict(two_stage=True, grad_dtype=None),
    "moe_two_stage_bf16": dict(two_stage=True,
                               grad_dtype={"dcn": "bfloat16"}),
    "moe_two_stage_int8": dict(two_stage=True,
                               grad_dtype={"dcn": "int8"}),
}


def _walk_jaxpr(jaxpr, visit):
    """Depth-first visit of every eqn of ``jaxpr`` and its sub-jaxprs
    (pjit/shard_map/scan/remat/custom-vjp bodies)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        visit(eqn)
        for value in eqn.params.values():
            stack = [value]
            while stack:
                v = stack.pop()
                if isinstance(v, (list, tuple)):
                    stack.extend(v)
                elif isinstance(v, ClosedJaxpr):
                    _walk_jaxpr(v.jaxpr, visit)
                elif isinstance(v, Jaxpr):
                    _walk_jaxpr(v, visit)


def _eqn_axes(eqn):
    """Mesh axis names a collective eqn runs over, as a sorted list —
    ``psum`` carries them as ``axes``, ``reduce_scatter``/``all_gather``
    as ``axis_name`` (possibly a bare string).  The hop resolution the
    per-hop census rides on."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return sorted(str(a) for a in axes)


def collective_census(jaxpr):
    """All collective eqns in the (closed) jaxpr, in PROGRAM ORDER
    (depth-first emission order — the hop-ordering gate relies on it):
    list of ``{"prim", "elems", "dtype", "axes"}``, one row per
    operand."""
    from jax.extend.core import ClosedJaxpr
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    rows = []

    def visit(eqn):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            return
        for var in eqn.invars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            rows.append({"prim": eqn.primitive.name,
                         "elems": int(np.prod(aval.shape, dtype=np.int64)),
                         "dtype": str(aval.dtype),
                         "axes": _eqn_axes(eqn)})

    _walk_jaxpr(jaxpr, visit)
    return rows


def row_hop(row, comm):
    """Hop label of a census row: ``dcn``/``ici`` on a hierarchical
    communicator (resolved from the eqn's own axis names), ``world``
    on a flat one.  Anything else (e.g. a residual full-axis
    collective) surfaces as a joined label the per-hop gates reject."""
    if comm.hierarchy is None:
        return "world"
    axes = set(row["axes"])
    if axes == {comm.dcn_axis}:
        return "dcn"
    if axes == {comm.ici_axis}:
        return "ici"
    return "+".join(row["axes"])


#: (prim, hop) → path table of the striped ALLREDUCE exchange: the
#: ICI path's ops are rs/ag over ici + its chunk psum over dcn; the
#: DCN path's are the transpose.  Unambiguous because the allreduce
#: exchange never emits the same primitive on the same axis for both
#: paths (the striped_rs exchange DOES — both paths chain psum_scatter
#: over both axes — so its census commits per-hop structure only).
_STRIPED_ALLREDUCE_PATHS = {
    ("reduce_scatter", "ici"): "ici", ("all_gather", "ici"): "ici",
    ("psum", "dcn"): "ici",
    ("reduce_scatter", "dcn"): "dcn", ("all_gather", "dcn"): "dcn",
    ("psum", "ici"): "dcn",
}


def row_path(row, comm):
    """PATH label of a census row (ISSUE 11): which slice's exchange
    the collective belongs to.  ``world`` on flat communicators,
    ``hier`` on the single-path hierarchical exchange; on the striped
    allreduce exchange ``ici``/``dcn`` resolved from the (primitive,
    hop) pair.  A pair the table cannot place (e.g. the striped_rs
    chains, where both paths scatter over both axes) surfaces as a
    joined ``prim@hop`` label the per-path gates reject."""
    if comm.hierarchy is None:
        return "world"
    if not getattr(comm, "striped", False):
        return "hier"
    hop = row_hop(row, comm)
    return _STRIPED_ALLREDUCE_PATHS.get(
        (row["prim"], hop), f"{row['prim']}@{hop}")


def row_phase(row):
    """Schedule phase of a census row: ``epilogue`` for rebuild
    all_gathers, ``exchange`` for every scatter/crossing op.  An
    all_gather whose operand rides a QUANTIZED wire dtype is a
    codeword CROSSING (the gather-based quantized hop), not a rebuild
    — the distinction the generalized ``hop_ordered`` gate needs."""
    from chainermn_tpu.communicators._memory_utility import \
        is_quantized_dtype
    if row["prim"] == "all_gather" and not is_quantized_dtype(row["dtype"]):
        return "epilogue"
    return "exchange"


def hop_ordered(grad_rows):
    """The generalized per-path ordering gate (ISSUE 11 satellite —
    the old check hard-assumed every DCN op precedes every ICI
    all_gather, which only holds for single-path schedules): every
    scatter/crossing op of EVERY path precedes every rebuild
    all_gather of ANY path in program order.  For the hierarchical
    exchange this degenerates to the old slow-hop-first property
    (rs + dcn crossing before the ici rebuild); for striped schedules
    it is exactly "both paths eligible before any bucket's epilogue"
    — the concurrency window the striped hop_schedule promises."""
    ex_idx = [i for i, r in enumerate(grad_rows)
              if row_phase(r) == "exchange"]
    ep_idx = [i for i, r in enumerate(grad_rows)
              if row_phase(r) == "epilogue"]
    return not ex_idx or not ep_idx or max(ex_idx) < min(ep_idx)


def row_ring(row, comm):
    """Ring size of a census row's collective: the product of its mesh
    axis sizes."""
    out = 1
    for a in row["axes"]:
        out *= int(comm.mesh.shape[a])
    return out


def row_wire_bytes(row, comm):
    """Per-replica wire bytes of one census row under the ring
    decomposition, in the row's own operand dtype — the WIRE dtype of
    the packed buffer (``all_gather`` operands are the per-rank chunk;
    the accounting is over the full gathered buffer) — the ONE pricing
    rule of config_row and of the per-hop table.

    A primitive this pricing does not understand is a HARD error (ISSUE
    8 satellite): a silently mispriced or skipped collective would make
    the committed byte budgets lie exactly when a new exchange shape
    lands."""
    import jax.numpy as jnp
    from chainermn_tpu.communicators._memory_utility import exchanged_bytes
    ring = row_ring(row, comm)
    n_bytes = row["elems"] * jnp.dtype(row["dtype"]).itemsize
    if row["prim"] == "all_gather":
        return exchanged_bytes(n_bytes * ring, ring, "all_gather")
    if row["prim"] == "psum":
        return exchanged_bytes(n_bytes, ring, "psum")
    if row["prim"] in ("reduce_scatter", "all_to_all"):
        return exchanged_bytes(n_bytes, ring, row["prim"])
    raise ValueError(
        f"census cannot price collective {row['prim']!r} "
        f"(elems={row['elems']}, axes={row['axes']}): teach "
        f"row_wire_bytes/_memory_utility.exchanged_bytes its ring "
        f"decomposition before committing a config that emits it")


class _Vertical:
    """The traced transformer DP vertical, built once per process."""

    _cached = None

    @classmethod
    def get(cls):
        if cls._cached is None:
            cls._cached = cls()
        return cls._cached

    def __init__(self):
        import jax.numpy as jnp
        from chainermn_tpu.models import TransformerLM
        from chainermn_tpu.core.link import extract_state
        v = VERTICAL
        self.model = TransformerLM(
            n_vocab=v["n_vocab"], d_model=v["d_model"],
            n_heads=v["n_heads"], n_layers=v["n_layers"],
            max_len=v["max_len"], seed=0)
        rng = np.random.RandomState(0)
        self.x = jnp.asarray(
            rng.randint(0, v["n_vocab"], (v["bs"], v["seq"]))
            .astype(np.int32))
        self.t = jnp.asarray(np.roll(np.asarray(self.x), -1, axis=1))
        params = extract_state(self.model)["params"]
        self.n_params = sum(int(np.prod(p.shape)) for p in params.values())
        self.param_bytes = sum(
            int(np.prod(p.shape)) * p.dtype.itemsize
            for p in params.values())


def trace_step(exchange="allreduce", batch_collectives=True,
               grad_dtype=None, bucket_mb=None, comm_name="jax_ici",
               inter_size=None, stripe_ratio=None):
    """Jaxpr of the REAL compiled multi-node train step for one config
    — the exact step makers ``update()`` dispatches, traced instead of
    executed (no XLA compile; CPU-safe)."""
    import jax
    import chainermn_tpu as ct
    from chainermn_tpu.core.link import extract_state

    vert = _Vertical.get()
    comm = ct.create_communicator(
        comm_name, batch_collectives=batch_collectives,
        allreduce_grad_dtype=grad_dtype, bucket_mb=bucket_mb,
        inter_size=inter_size, stripe_ratio=stripe_ratio)
    comm.bcast_data(vert.model)
    from chainermn_tpu.core.optimizer import MomentumSGD
    inner = MomentumSGD(lr=0.1, momentum=0.9)
    opt = ct.create_multi_node_optimizer(inner, comm,
                                         exchange=exchange)
    opt.setup(vert.model)
    state = extract_state(vert.model)
    params, pstate = state["params"], state["state"]
    args, kwargs = (vert.x, vert.t), {}
    if opt._sharded_update:
        opt_state = opt._ensure_zero_opt_state(params)
        step = opt._make_zero_step(vert.model, args, kwargs)
    else:
        opt_state = inner._ensure_opt_state(params)
        step = opt._make_step(vert.model, args, kwargs)
    operands = (params, pstate, opt_state, inner._hyper_values(),
                inner._next_rng_key(), (), opt._residual_operand(),
                args, kwargs)
    return jax.make_jaxpr(step)(*operands), comm


def config_row(name):
    """Computed census row for one committed config.

    Per-row accounting (the shared ``row_hop``/``row_ring``/
    ``row_wire_bytes`` helpers) resolves each collective's mesh AXES to
    a ring size and a hop label (``dcn`` / ``ici`` on hierarchical
    configs, ``world`` on flat ones), in the row's own operand dtype —
    so the per-hop dtype variant's halved DCN bytes fall out of the
    trace, not out of config metadata.  Classification: ``psum`` and
    ``reduce_scatter`` rows carry GRADIENT bytes; ``all_gather`` rows
    carry the gradient rebuild on the allreduce exchanges (the
    hierarchical fast-hop gather) and the PARAMS rebuild on the
    reduce-scatter exchanges."""
    cfg = CONFIGS[name]
    bucket_mb = cfg.get("bucket_mb")
    jaxpr, comm = trace_step(exchange=cfg["exchange"],
                             batch_collectives=cfg["batch_collectives"],
                             grad_dtype=cfg["grad_dtype"],
                             bucket_mb=bucket_mb,
                             comm_name=cfg.get("comm", "jax_ici"),
                             inter_size=cfg.get("inter_size"),
                             stripe_ratio=cfg.get("stripe_ratio"))
    census = collective_census(jaxpr)
    grad = [r for r in census if r["elems"] >= GRAD_ELEMS_FLOOR]
    counts = {}
    elems = {}
    for r in grad:
        counts[r["prim"]] = counts.get(r["prim"], 0) + 1
        elems.setdefault(r["prim"], []).append(r["elems"])
    for v in elems.values():
        v.sort(reverse=True)
    hier = comm.hierarchy
    rs_exchange = cfg["exchange"] == "reduce_scatter"
    per_hop = {}
    grad_bytes = 0
    param_bytes = 0
    for r in grad:
        wire = row_wire_bytes(r, comm)
        is_param = rs_exchange and r["prim"] == "all_gather"
        hop = per_hop.setdefault(row_hop(r, comm), {
            "collectives": {}, "exchanged_grad_bytes": 0,
            "exchanged_param_bytes": 0, "wire_dtypes": []})
        hop["collectives"][r["prim"]] = \
            hop["collectives"].get(r["prim"], 0) + 1
        if r["dtype"] not in hop["wire_dtypes"]:
            hop["wire_dtypes"] = sorted(hop["wire_dtypes"] + [r["dtype"]])
        if is_param:
            hop["exchanged_param_bytes"] += int(wire)
            param_bytes += wire
        else:
            hop["exchanged_grad_bytes"] += int(wire)
            grad_bytes += wire
    q_wire = comm.quantized_wire_dtype
    row = {
        "exchange": cfg["exchange"],
        "batch_collectives": cfg["batch_collectives"],
        "grad_dtype": cfg["grad_dtype"],
        "bucket_mb": bucket_mb,
        "topology": comm.topology,
        "intra_size": comm.ici_size,
        "inter_size": comm.dcn_size,
        "quantized_wire": None if q_wire is None else str(q_wire),
        "error_feedback": comm.error_feedback if q_wire is not None
        else None,
        "grad_collectives": counts,
        "grad_collective_elems": elems,
        "per_hop": per_hop,
        "n_buckets": counts.get("psum", 0),
        "exchanged_gradient_bytes_per_replica": int(grad_bytes),
        "exchanged_param_bytes_per_replica": int(param_bytes),
    }
    if hier is not None:
        import jax.numpy as jnp
        # the tentpole's byte contract: the largest gradient buffer that
        # crosses DCN is exactly 1/ici of the full gradient (per bucket:
        # the reduce-scattered chunk) — pin the ratio from the TRACE.
        # Payload rows are every DCN gradient crossing, whatever the
        # primitive (the quantized exchange crosses as all_gather /
        # all_to_all); the sharded update's params rebuild is excluded
        # (accounted as param bytes)
        vert = _Vertical.get()
        dcn_grad_rows = [r for r in grad if row_hop(r, comm) == "dcn"
                         and not (rs_exchange
                                  and r["prim"] == "all_gather")]
        dcn_payload = sum(r["elems"] for r in dcn_grad_rows)
        row["dcn_grad_payload_ratio"] = dcn_payload / vert.n_params
        # the ISSUE 8 acceptance ratio: DCN payload in WIRE bytes
        # (itemsize of the packed buffer) over the f32 gradient bytes —
        # the quantized fraction falls out of the trace, not metadata
        dcn_payload_bytes = sum(
            r["elems"] * jnp.dtype(r["dtype"]).itemsize
            for r in dcn_grad_rows)
        row["dcn_payload_bytes_ratio"] = \
            dcn_payload_bytes / (vert.n_params * 4)
        # per-path ordering (generalized, ISSUE 11 satellite): every
        # scatter/crossing op — psum, reduce_scatter, all_to_all, and
        # quantized-codeword all_gathers, on EITHER path — precedes
        # every rebuild all_gather in program order, so the striped
        # configs are budget-gated instead of exempted and the old
        # every-DCN-op-before-every-ICI-rebuild property falls out as
        # the single-path special case
        row["hop_ordered"] = hop_ordered(grad)
        if comm.striped:
            row["stripe_ratio"] = comm.stripe_ratio
            if cfg["exchange"] == "allreduce":
                # per-PATH byte accounting (the ISSUE 11 satellite):
                # each collective priced at its wire dtype and charged
                # to the slice whose exchange it implements — the
                # conservation identity (path totals sum to the flat
                # allreduce figure) and the committed-share identity
                # (dcn path total / grand total == stripe_ratio) are
                # gated from these, straight off the trace
                per_path = {}
                for r in grad:
                    p = row_path(r, comm)
                    per_path[p] = per_path.get(p, 0) \
                        + int(row_wire_bytes(r, comm))
                row["per_path_bytes"] = per_path
    return row


def trace_moe(name):
    """Jaxpr of one committed MoE dispatch+combine round trip (ISSUE
    12) — the real ``parallel.moe`` exchange shard_mapped over the
    simulated 2-host mesh, traced instead of executed (CPU-safe, no
    compile).  The expert is a shape-preserving affine stand-in: the
    census pins the EXCHANGE structure, and a real expert GEMM adds no
    collectives."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as ct
    from chainermn_tpu.parallel.moe import moe_dispatch_combine
    from jax import shard_map

    cfg = MOE_CONFIGS[name]
    v = MOE_VERTICAL
    comm = ct.create_communicator("hierarchical",
                                  inter_size=HIER_INTER_SIZE,
                                  allreduce_grad_dtype=cfg["grad_dtype"])
    E = comm.size
    T, D = v["tokens_per_rank"], v["d_model"]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (E * T, D)).astype(np.float32))
    router = jnp.asarray(rng.normal(0, 1, (D, E)).astype(np.float32))

    def body(x, router):
        out, _ = moe_dispatch_combine(
            comm, x, x @ router, lambda h: h * 2.0 + 1.0,
            capacity_factor=v["capacity_factor"],
            two_stage=cfg["two_stage"])
        return out

    axes = comm.axis_name
    mapped = shard_map(body, mesh=comm.mesh,
                       in_specs=(P(axes), P()), out_specs=P(axes),
                       check_vma=False)
    return jax.make_jaxpr(mapped)(x, router), comm


def moe_capacity(comm):
    from chainermn_tpu.parallel.moe import moe_capacity as _cap
    v = MOE_VERTICAL
    return _cap(v["tokens_per_rank"], comm.size, v["capacity_factor"])


def moe_config_row(name, traced=None):
    """Computed census row for one committed MoE dispatch config: the
    per-hop ``all_to_all`` structure (counts, wire dtypes, wire bytes —
    each crossing priced at its OWN operand dtype via the shared
    ``row_hop``/``row_wire_bytes`` helpers), the analytic
    ``off_host_dispatch_ratio`` of the 2-host split (the fraction of
    the capacity buffer whose expert lives off-host — what the slow
    fabric is allowed to carry), and for the two-stage configs the
    TRACE-pinned ``dcn_dispatch_bytes_ratio``: DCN dispatch wire bytes
    over the f32 round trip — equal to the off-host ratio when
    lossless, half of it under bf16, a quarter under int8 (the
    quantized fraction falls out of the trace, never out of
    metadata).  ``traced`` takes a prebuilt ``(jaxpr, comm)`` pair so
    callers that also want the raw census rows trace each config
    once, not twice."""
    import jax.numpy as jnp
    cfg = MOE_CONFIGS[name]
    jaxpr, comm = traced if traced is not None else trace_moe(name)
    census = collective_census(jaxpr)
    grad = [r for r in census if r["elems"] >= GRAD_ELEMS_FLOOR]
    a2a = [r for r in grad if r["prim"] == "all_to_all"]
    capacity = moe_capacity(comm)
    dispatch_elems = comm.size * capacity * MOE_VERTICAL["d_model"]
    per_hop = {}
    for r in a2a:
        hop = per_hop.setdefault(row_hop(r, comm), {
            "collectives": {}, "exchanged_dispatch_bytes": 0,
            "wire_dtypes": []})
        hop["collectives"][r["prim"]] = \
            hop["collectives"].get(r["prim"], 0) + 1
        if r["dtype"] not in hop["wire_dtypes"]:
            hop["wire_dtypes"] = sorted(hop["wire_dtypes"] + [r["dtype"]])
        hop["exchanged_dispatch_bytes"] += int(row_wire_bytes(r, comm))
    row = {
        "two_stage": cfg["two_stage"],
        "grad_dtype": cfg["grad_dtype"],
        "topology": comm.topology,
        "intra_size": comm.ici_size,
        "inter_size": comm.dcn_size,
        "dcn_wire_dtype": str(comm.dcn_grad_dtype)
        if comm.dcn_grad_dtype is not None else None,
        "capacity": capacity,
        "dispatch_elems": dispatch_elems,
        "per_hop": per_hop,
        # a non-all_to_all gradient-sized collective in the dispatch
        # program would be structure drift — pinned at zero
        "non_dispatch_collectives":
            sum(1 for r in grad if r["prim"] != "all_to_all"),
        # the routing fact of the committed split: (inter-1)/inter of
        # the capacity buffer's slots belong to off-host experts
        "off_host_dispatch_ratio":
            (comm.dcn_size - 1) / comm.dcn_size,
    }
    if cfg["two_stage"]:
        dcn_bytes = per_hop.get("dcn", {}) \
            .get("exchanged_dispatch_bytes", 0)
        row["dcn_dispatch_bytes_ratio"] = \
            dcn_bytes / (2 * dispatch_elems * 4)
    return row


def build_moe_structure():
    import chainermn_tpu as ct
    comm = ct.create_communicator("hierarchical",
                                  inter_size=HIER_INTER_SIZE)
    capacity = moe_capacity(comm)
    return {
        "vertical": dict(MOE_VERTICAL, n_devices=_n_devices(),
                         experts=comm.size, capacity=capacity,
                         dispatch_elems=comm.size * capacity
                         * MOE_VERTICAL["d_model"]),
        "structure": {name: moe_config_row(name)
                      for name in MOE_CONFIGS},
    }


def build_structure():
    vert = _Vertical.get()
    structure = {name: config_row(name) for name in CONFIGS}
    return {
        "vertical": dict(VERTICAL, n_devices=_n_devices(),
                         params=vert.n_params,
                         param_bytes=vert.param_bytes),
        "grad_elems_floor": GRAD_ELEMS_FLOOR,
        "structure": structure,
        "moe": build_moe_structure(),
    }


def _n_devices():
    import jax
    return len(jax.devices())


def load_budgets(path=None):
    with open(path or BUDGETS_PATH) as f:
        return json.load(f)


def main(argv):
    import jax
    jax.config.update("jax_platforms",
                      os.environ.get("PROBE_PLATFORM") or "cpu")
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    built = build_structure()
    for name, row in built["structure"].items():
        print(json.dumps(dict(row, config=name)), flush=True)
    for name, row in built["moe"]["structure"].items():
        print(json.dumps(dict(row, config=name)), flush=True)
    if "--write-budgets" not in argv:
        return 0
    try:
        budgets = load_budgets()
    except Exception:
        budgets = {}
    budgets.update(built)
    budgets.setdefault("sweep", {
        "status": "pending_on_chip",
        "note": "bucket-MB sweep + >=2-host exposed-comm A/B: not "
                "measured; rows land here from a chip run",
    })
    with open(BUDGETS_PATH, "w") as f:
        json.dump(budgets, f, indent=1)
        f.write("\n")
    print(f"wrote {BUDGETS_PATH}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
