"""Multi-node optimizer wrappers.

Reference: ``chainermn/optimizers.py · _MultiNodeOptimizer,
_DoubleBufferingOptimizer, create_multi_node_optimizer`` (SURVEY.md §2.4,
call stack §3.2).

The reference interposes ``communicator.allreduce_grad(target)`` between
``loss.backward()`` and ``optimizer.update()`` as a separate host-driven
step (pack kernel → NCCL → unpack kernel).  Here the *entire* data-parallel
step — per-rank forward/backward on the local batch shard, gradient mean
over the communicator axis (optionally dtype-compressed / flat- or
size-bounded-bucketed, per the communicator's ``batch_collectives``),
and the optax update — is one ``shard_map``ped, jit-compiled program:
SURVEY §3.2's "this whole stack becomes ONE train_step".  XLA's
async-collective scheduler overlaps the gradient collectives with
remaining backward compute; the ``"bucketed"`` exchange hands it K
independently schedulable units instead of one monolithic transfer
(docs/performance.md §7, tools/comm_budgets.json).

``exchange="reduce_scatter"`` replaces the allreduce-then-replicated-
update structure with ``reduce_scatter(grads) → shard-local update →
all_gather(params)``: per-replica exchanged gradient bytes are halved
(the gradient crosses the wire once), the optimizer state lives
shard-local, and — unlike ``zero_sharding`` — it composes with double
buffering (the stale buffer is the 1/n mean-gradient chunk).

On a HIERARCHICAL communicator (ISSUE 6: a real (dcn, ici) two-level
mesh) every exchange composes with the topology: the allreduce path's
``grad_transform`` runs intra-host reduce-scatter → DCN chunk
allreduce → intra-host all-gather per bucket, and the sharded-update
path chains ``psum_scatter`` fast-hop-first (``comm.chunk_axes()``) so
the slow DCN wire only ever carries ``1/ici_size`` of the bytes in
either direction (docs/performance.md §8).

Batch convention (single-controller translation of "each rank feeds its
local batch"): ``update(lossfun, *args)`` receives the *global* batch
(leading dim divisible by ``comm.size``); the shard_map in_spec splits it
across ranks.  A per-rank batchsize of ``b`` in reference scripts becomes
an iterator batchsize of ``b * comm.size`` here (see
``examples/train_mnist_dp.py``).

``double_buffering=True`` reproduces the reference's one-step-stale
gradient semantics (SURVEY §7 hard-parts note: defined by *observable
semantics*, not stream mechanics): step ``t`` applies the mean gradient
computed at step ``t-1`` while step ``t``'s gradients are produced in the
same compiled program.  Since XLA already overlaps the collective with
compute, the staleness is the semantic contract kept for parity, and it
additionally lets the runtime pipeline consecutive steps (the update no
longer serializes on the current step's collective).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import observability
from .core import reporter as reporter_module
from .core.link import bind_state, extract_state

__all__ = ["create_multi_node_optimizer", "_MultiNodeOptimizer",
           "_DoubleBufferingOptimizer"]


def _rehome_replicated(tree, communicator):
    """Re-place a REPLICATED pytree onto ``communicator``'s mesh by
    value (elastic resize, ISSUE 10): a jax.Array committed to the OLD
    mesh — possibly spanning processes that are gone — cannot be fed to
    the new mesh's compiled step, but a replicated array's every local
    shard holds the full value, so the move is a host round-trip that
    needs no collective and no dead peer.  The commit goes through
    ``make_array_from_callback`` (like ``_commit_opt_state_to_mesh``),
    NOT ``device_put``: multi-process device_put runs a cross-process
    value-equality collective, and mid-resize the values are allowed to
    differ (a joiner's stale state is about to be replaced by the
    consensus load — it only has to be SHAPED right here)."""
    from jax.sharding import NamedSharding
    sharding = NamedSharding(communicator.mesh, P())

    def move(leaf):
        if not isinstance(leaf, jax.Array):
            return leaf
        if leaf.is_fully_addressable:
            host = np.asarray(leaf)
        else:
            host = np.asarray(leaf.addressable_shards[0].data)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    return jax.tree.map(move, tree)


def _pmean_state(pstate, axis):
    """Mean the floating persistent state (BN running statistics) over
    the ranks.  Integer leaves (BN's batch counter) are equal on every
    rank and pass through: a ``pmean`` would return them as floats, and
    that dtype change compiles the step a second time."""
    return jax.tree.map(
        lambda s: lax.pmean(s, axis)
        if jnp.issubdtype(jnp.result_type(s), jnp.inexact) else s, pstate)


def create_multi_node_optimizer(actual_optimizer, communicator,
                                double_buffering=False, zero_fill=True,
                                zero_sharding=False, exchange=None,
                                autotune=None):
    """Wrap an optimizer so updates average gradients over the communicator.

    Reference signature and delegation semantics preserved: the returned
    object forwards attribute access to ``actual_optimizer``.

    ``exchange`` selects the gradient-exchange structure of the compiled
    DP step (docs/performance.md §7):

    * ``"allreduce"`` (default) — mean-``psum`` of the full gradient via
      the communicator's ``grad_transform`` (per-leaf / flat / bucketed
      per its ``batch_collectives``), then the replicated update.
    * ``"reduce_scatter"`` — the comm-optimal DP update:
      ``reduce_scatter(grads) → shard-local optimizer update →
      all_gather(params)``.  The gradient crosses the wire ONCE instead
      of twice — per-replica exchanged gradient bytes are halved vs any
      allreduce flavor (tools/comm_budgets.json commits the accounting)
      — and the optimizer state is maintained shard-local as a
      consequence (each rank only ever sees its 1/n gradient chunk), so
      it shares ZeRO-1's observable contract: ``Parameter.grad`` is not
      populated and the serialized optimizer state is the flat sharded
      vector.  Unlike ``zero_sharding`` it composes with
      ``double_buffering`` (the one-step-stale buffer is the sharded
      mean-gradient CHUNK — 1/n of a full stale buffer).  Trajectories
      are golden-equal to the allreduce flavors
      (tests/core_tests/test_exchange_equivalence.py).

    ``zero_sharding=True`` (beyond the reference — ZeRO-1 over the DP
    axis, TPU-idiomatic): the gradient mean becomes a ``psum_scatter``
    (reduce-scatter riding ICI), each rank updates only its 1/n shard of
    the flat parameter/optimizer-state vector, and an ``all_gather``
    rebuilds the replicated parameters — optimizer state and the reduced
    gradient buffer shrink by the communicator size (Adam: 2×params →
    2×params/n).  Observable differences, documented: ``Parameter.grad``
    is not populated (the full mean gradient never materializes) and the
    serialized optimizer state is the flat sharded vector, not the
    per-parameter tree.  ``zero_sharding`` already implies the
    reduce-scatter exchange; passing both is a redundancy error.

    ``autotune`` (ISSUE 19, docs/performance.md §12): self-tune the
    communicator's exchange knobs.  ``True``/``"startup"`` runs the
    startup micro-bench NOW (unless the communicator already carries an
    agreed plan) and wraps the retuned communicator; ``"online"`` (or an
    int N, default 3) re-tunes after the first N updates from the span
    tracer's payload-tagged ``train/grad_exchange`` spans — online mode
    needs tracing on (``CHAINERMN_TPU_TRACE=events``); with tracing off
    it falls back to the startup micro-bench WITH a warning, never a
    silent no-op.  The re-tune swap rides :meth:`change_communicator`,
    which also re-tunes automatically on every elastic resize when the
    outgoing communicator was autotuned.
    """
    online_after = 0
    if autotune not in (None, False, True, "startup", "online") \
            and not (isinstance(autotune, int)
                     and not isinstance(autotune, bool)
                     and autotune > 0):
        raise ValueError(
            f"autotune must be True/'startup', 'online', or a positive "
            f"int (online re-tune after N updates); got {autotune!r}")
    if autotune:
        from .communicators._autotune import retune_communicator
        if autotune in (True, "startup"):
            if getattr(communicator, "autotune_plan", None) is None:
                communicator = retune_communicator(communicator,
                                                   mode="startup")
        else:
            if observability.ring_enabled():
                online_after = autotune if isinstance(autotune, int) \
                    and not isinstance(autotune, bool) else 3
                communicator._autotune_mode = "online"
            else:
                import warnings
                warnings.warn(
                    "autotune='online' reads the span tracer's "
                    "train/grad_exchange spans but tracing is off "
                    "(CHAINERMN_TPU_TRACE): running the startup "
                    "micro-bench instead", UserWarning, stacklevel=2)
                if getattr(communicator, "autotune_plan", None) is None:
                    communicator = retune_communicator(communicator,
                                                       mode="startup")
    if exchange is None:
        exchange = "allreduce"
    if exchange not in ("allreduce", "reduce_scatter"):
        raise ValueError(
            f"exchange must be 'allreduce' or 'reduce_scatter', got "
            f"{exchange!r} (per_leaf/flat/bucketed are communicator "
            f"batch_collectives flavors of the allreduce exchange)")
    if (exchange == "reduce_scatter" or zero_sharding) \
            and getattr(communicator, "striped", False) \
            and getattr(communicator, "quantized_wire_dtype", None) \
            is not None:
        # covers BOTH sharded-update routes (zero_sharding and the
        # plain-DP reduce-scatter exchange share _make_zero_update): a
        # quantized dtype reaching the striped chains would raw-cast
        # gradients to int8 with no scale or residual — silent
        # corruption, never acceptable
        raise ValueError(
            "a quantized (int8/fp8) wire does not compose with the "
            "STRIPED sharded update (zero_sharding or "
            "exchange='reduce_scatter') yet: the slow-hop-major "
            "chain has no quantized psum_scatter shape.  Use the "
            "allreduce striped exchange (which quantizes both slices' "
            "DCN crossings) or the non-striped hierarchical_rs path")
    if zero_sharding and exchange == "reduce_scatter":
        raise ValueError(
            "zero_sharding already exchanges gradients via reduce-scatter; "
            "exchange='reduce_scatter' on top of it is a redundancy error "
            "(pick one: zero_sharding=True for the ZeRO-1 contract, "
            "exchange='reduce_scatter' for the comm-optimal plain-DP step)")
    if double_buffering not in (False, True, "dcn"):
        raise ValueError(
            f"double_buffering must be False, True (full one-step-stale "
            f"semantics) or 'dcn' (the striped exchange's DCN-slice-only "
            f"stale variant, ISSUE 11); got {double_buffering!r}")
    if double_buffering:
        if zero_sharding:
            raise ValueError(
                "zero_sharding is incompatible with double buffering "
                "(a one-step-stale FULL gradient buffer would defeat "
                "the sharded-state memory contract)")
        if double_buffering == "dcn":
            if not getattr(communicator, "striped", False):
                raise ValueError(
                    "double_buffering='dcn' is the striped exchange's "
                    "DCN-slice-only stale variant: it needs a "
                    "communicator with stripe_ratio > 0 "
                    "(create_communicator('hierarchical', "
                    "stripe_ratio=...))")
            if exchange == "reduce_scatter":
                raise ValueError(
                    "double_buffering='dcn' rides the allreduce striped "
                    "exchange (the DCN-path slice of grad_transform); "
                    "with exchange='reduce_scatter' use "
                    "double_buffering=True — the stale chunk is already "
                    "1/n-sized")
        if communicator.name not in ("pure_nccl", "jax_ici", "hierarchical",
                                     "two_dimensional", "single_node", "flat",
                                     "dummy"):
            # reference restricts double buffering to PureNcclCommunicator
            raise ValueError(
                "double buffering requires a fused-bucket communicator "
                f"(reference: pure_nccl); got {communicator.name!r}")
        opt = _DoubleBufferingOptimizer(actual_optimizer, communicator,
                                        zero_fill, exchange=exchange,
                                        db_mode=double_buffering)
        opt._autotune_online_after = online_after
        return opt
    opt = _MultiNodeOptimizer(actual_optimizer, communicator, zero_fill,
                              zero_sharding=zero_sharding,
                              exchange=exchange)
    opt._autotune_online_after = online_after
    return opt


class _MultiNodeOptimizer:
    def __init__(self, actual_optimizer, communicator, zero_fill=True,
                 zero_sharding=False, exchange="allreduce"):
        super().__setattr__("communicator", communicator)
        super().__setattr__("actual_optimizer", actual_optimizer)
        super().__setattr__("zero_fill", zero_fill)
        super().__setattr__("zero_sharding", zero_sharding)
        super().__setattr__("exchange", exchange)
        super().__setattr__("_zero_layout", None)  # (spec, n, n_pad)
        from .core.optimizer import _LRUCache
        super().__setattr__("_mn_step_cache", _LRUCache())
        super().__setattr__("_stale_grads", None)  # double-buffer slot
        super().__setattr__("_residual", None)  # error-feedback slot

    _double_buffering = False
    #: "dcn" on the striped DCN-slice-only stale variant (ISSUE 11) —
    #: the update applies FRESH ICI-path gradients and one-step-stale
    #: DCN-path gradients, so the slow path's latency hides entirely
    #: behind compute while the fast path stays exact
    _db_mode = False
    #: online autotune (ISSUE 19): re-tune from the span tracer's
    #: payload-tagged exchange spans after this many updates (0 = off;
    #: armed by ``create_multi_node_optimizer(autotune='online')``)
    _autotune_online_after = 0
    _autotune_steps_done = 0

    @property
    def _db_dcn(self):
        return self._db_mode == "dcn"

    @property
    def _needs_residual(self):
        """True when the compiled step threads the error-feedback
        residual (ISSUE 8): the communicator quantizes a hop AND error
        feedback is on.  The residual rides the stale-grad machinery —
        a persistent flat f32 buffer, donated into the step, sharded by
        ``flat_chunk_spec`` (each device owns its slice), serialized
        next to the stale buffer so resume keeps the telescoping sum
        intact."""
        comm = self.communicator
        return bool(getattr(comm, "quantized", False)
                    and getattr(comm, "error_feedback", False))

    def _residual_global_len(self):
        """Length of the GLOBAL residual vector: per-device residual ×
        size.  Sharded-update steps quantize the post-fast-hop chunk
        (``n_pad / ici`` per device); allreduce steps quantize per
        bucket (the communicator owns that accounting)."""
        comm = self.communicator
        if self._sharded_update:
            _, _, n_pad = self._zero_layout
            slow = comm.dcn_size if comm.hierarchy is not None \
                else comm.size
            return n_pad * slow
        return comm.grad_residual_len_for(self.actual_optimizer.target) \
            * comm.size

    def _residual_operand(self):
        """The residual tuple operand the compiled step expects — ``()``
        when error feedback is off, ``(buffer,)`` (zero-seeded on first
        use: no error has been made yet) when on.  Shared by
        ``update()``/``update_scan()`` and the census tracer."""
        if not self._needs_residual:
            return ()
        if self._residual is None:
            super().__setattr__("_residual", jnp.zeros(
                (self._residual_global_len(),), jnp.float32))
        return (self._residual,)

    @property
    def _sharded_update(self):
        """True when the compiled step updates flat parameter CHUNKS
        after a reduce-scatter (ZeRO-1, or the comm-optimal plain-DP
        ``exchange="reduce_scatter"``) — the paths that share the flat
        sharded optimizer state, its serialization, and the
        grad-not-populated contract."""
        return self.zero_sharding or self.exchange == "reduce_scatter"

    def _emit_exchange_telemetry(self):
        """Per-bucket gradient-exchange attribution (ISSUE 14).

        The exchange runs INSIDE the compiled step, so host code cannot
        time individual buckets: the host trace instead carries one
        instant event per bucket stamped with the PLANNED wire payload
        (the same ``grad_buckets_for`` plan the census gates check),
        and the registry accumulates the per-bucket byte counters."""
        plan = self._exchange_plan_rows()
        if not plan:
            return
        comm = self.communicator
        exchange = getattr(comm, "exchange", None) or self.exchange
        counter = observability.registry().counter(
            "chainermn_tpu_grad_exchange_payload_bytes_total",
            help="planned per-bucket gradient wire payload (gradient "
                 "dtype; the census prices the per-hop wire dtypes)")
        for row in plan:
            observability.instant(
                f"train/grad_exchange/bucket{row['bucket']}",
                tags=dict(row, exchange=str(exchange)))
            counter.inc(row["payload_bytes"], bucket=str(row["bucket"]),
                        exchange=str(exchange))

    def _exchange_plan_rows(self):
        """The cached per-bucket ``{bucket, leaves, elems,
        payload_bytes}`` rows of the current exchange plan — shared by
        the telemetry instants, the timed eager span's payload tags
        (the ISSUE 19 small fix: bandwidth readable off a trace), and
        nothing else; invalidated wherever ``_obs_exchange_plan``
        resets (setup, change_communicator).  Empty where the
        communicator's ``grad_transform`` exchanges nothing (a
        one-device axis): no instant, no counter, no payload tag."""
        plan = self.__dict__.get("_obs_exchange_plan")
        if plan is None:
            comm = self.communicator
            target = self.actual_optimizer.target
            buckets = []
            # over a one-device axis grad_transform exchanges nothing:
            # no bucket is announced on a wire
            if self._sharded_update \
                    or getattr(comm, "grad_exchange_on_wire", True):
                try:
                    shapes, dtypes = comm.grad_leaf_specs(target)
                    buckets = comm.grad_buckets_for(target)
                except Exception:
                    buckets = []
            plan = []
            for i, idx in enumerate(buckets):
                elems = sum(int(np.prod(shapes[j])) for j in idx)
                nbytes = sum(int(np.prod(shapes[j]))
                             * np.dtype(dtypes[j]).itemsize for j in idx)
                plan.append({"bucket": i, "leaves": len(idx),
                             "elems": elems, "payload_bytes": nbytes})
            super().__setattr__("_obs_exchange_plan", plan)
        return plan

    def _maybe_online_retune(self):
        """Online autotune (ISSUE 19): after the armed number of
        updates, derive a plan from the tracer's payload-tagged
        ``train/grad_exchange*`` spans, agree it across ranks, and swap
        in the retuned communicator through
        :meth:`change_communicator`.  One-shot — the counter disarms
        whether or not the plan changed anything.  A plan the sharded
        striped layout cannot absorb in memory (ratio change without a
        checkpointer) is WARNED about and skipped, never a crash in the
        middle of training."""
        n = self._autotune_online_after
        if not n:
            return
        done = self._autotune_steps_done + 1
        self._autotune_steps_done = done
        if done < n:
            return
        self._autotune_online_after = 0
        from .communicators._autotune import (agree_exchange_plan,
                                              measurements_from_trace)
        comm = self.communicator
        measurement = measurements_from_trace(
            observability.tracer().events())
        plan = agree_exchange_plan(comm, measurement)
        new_comm = comm.retuned(plan)
        if new_comm is comm:
            return
        try:
            self.change_communicator(new_comm)
        except RuntimeError as e:
            import warnings
            warnings.warn(
                f"online autotune plan {plan.get('fingerprint')} not "
                f"applied: {e}", RuntimeWarning, stacklevel=2)

    # -- reference-style delegation ---------------------------------------
    def __getattr__(self, name):
        return getattr(self.actual_optimizer, name)

    def __setattr__(self, name, value):
        if name in self.__dict__ or hasattr(type(self), name):
            super().__setattr__(name, value)
        else:
            setattr(self.actual_optimizer, name, value)

    def setup(self, link):
        self.actual_optimizer.setup(link)
        # setup() resets the wrapped optimizer's _opt_state; every piece
        # of wrapper state whose lifetime tracks _opt_state (the ZeRO
        # flat-layout, compiled-step cache, double-buffer slot) must
        # reset with it — otherwise a later deserialize sees a stale
        # _zero_layout, skips the flat-template pre-seed, and restores
        # the saved flat chunks onto mismatched per-param slots.
        super().__setattr__("_zero_layout", None)
        super().__setattr__("_stale_grads", None)
        super().__setattr__("_residual", None)
        super().__setattr__("_obs_exchange_plan", None)
        self._mn_step_cache.clear()
        return self

    # -- elastic resize (ISSUE 10) -----------------------------------------
    def change_communicator(self, communicator, via_checkpoint=False):
        """Swap the transport after an elastic resize, re-planning every
        piece of state whose layout depends on the world size.

        What is PRESERVED vs RE-SEEDED (the contract
        ``docs/resilience.md`` §7 documents):

        * model params and (replicated) optimizer state — preserved:
          re-homed onto the new mesh by value;
        * compiled steps, bucket plans, the ZeRO flat layout —
          re-derived lazily (cache cleared; the padding multiple and
          chunk specs follow the new size);
        * the double-buffer stale-grad buffer and the error-feedback
          ``_residual`` — RE-SEEDED ZEROS: both are per-device content
          with no cross-partition meaning (the same rule size-changed
          snapshot resume already applies), costing one step of
          staleness/correction, never correctness;
        * SHARDED (``zero_sharding`` / ``exchange="reduce_scatter"``)
          optimizer state: fully-addressable flat leaves are sliced to
          the true length and re-committed to the new mesh's padded
          chunk layout (the PR 5 size-changed-resume brick, applied
          in-memory).  REAL multi-controller sharded leaves cannot be
          reassembled here — the old mesh's collectives may span dead
          processes — so they require ``via_checkpoint=True``: the
          state is dropped and the caller's consensus ``maybe_load``
          (which the elastic supervisor always runs next) restores it
          onto the new layout.
        """
        old = self.communicator
        if communicator is old:
            return self
        if getattr(old, "_autotune_mode", None) \
                and getattr(communicator, "autotune_plan", None) is None \
                and getattr(communicator, "axis_name", None) is not None:
            # the OLD communicator was autotuned and the incoming one
            # carries no agreed plan (an elastic rebuild): re-tune it —
            # the plan tracks the world it actually runs on, one fresh
            # plan artifact per epoch-suffixed mesh (ISSUE 19).  Knob
            # PROVENANCE carries over from the old communicator first:
            # the elastic factory passes the old knob VALUES as explicit
            # constructor arguments, which must not read as hand-set.
            hand = getattr(old, "_hand_knobs", None)
            if hand is not None:
                communicator._hand_knobs = dict(hand)
            communicator._autotune_mode = old._autotune_mode
            from .communicators._autotune import retune_communicator
            # a resize always re-MEASURES (startup micro-bench): the
            # old trace's spans timed the old world's fabric
            communicator = retune_communicator(communicator,
                                               mode="startup")
        actual = self.actual_optimizer
        if self._sharded_update and actual._opt_state is not None:
            leaves = jax.tree.leaves(actual._opt_state)
            nonaddr = any(isinstance(l, jax.Array)
                          and not l.is_fully_addressable for l in leaves)
            if nonaddr:
                if not via_checkpoint:
                    raise RuntimeError(
                        "change_communicator on a multi-controller "
                        "sharded optimizer needs via_checkpoint=True: "
                        "the old mesh's chunks cannot be reassembled "
                        "without the departed processes — resume the "
                        "state through the checkpointer's consensus "
                        "maybe_load instead")
                actual._opt_state = None
                old_state = None
            else:
                old_state = actual._opt_state
        else:
            old_state = None
        if old_state is not None and (
                (getattr(old, "striped", False),
                 getattr(old, "stripe_ratio", 0.0))
                != (getattr(communicator, "striped", False),
                    getattr(communicator, "stripe_ratio", 0.0))):
            # the striped pair layout's split point moves with the
            # ratio and its leaves are keyed per path — a cross-
            # topology in-memory re-commit would silently mis-slice;
            # resume through the checkpointer's consensus load instead
            if not via_checkpoint:
                raise RuntimeError(
                    "change_communicator across a striped-layout change "
                    "(striped<->flat chunking or a different "
                    "stripe_ratio) needs via_checkpoint=True: the "
                    "sharded flat state cannot be re-sliced in memory "
                    "across split layouts")
            actual._opt_state = None
            old_state = None
        super().__setattr__("communicator", communicator)
        super().__setattr__("_zero_layout", None)
        super().__setattr__("_stale_grads", None)  # re-seed zeros
        super().__setattr__("_residual", None)     # re-seed zeros
        super().__setattr__("_obs_exchange_plan", None)  # new plan
        self._mn_step_cache.clear()
        if old_state is not None:
            # recompute the flat layout at the NEW size, then slice/
            # re-pad + re-commit each flat leaf (what
            # _commit_opt_state_to_mesh does for a size-changed load)
            params = extract_state(actual.target)["params"]
            if params and all(v is not None for v in params.values()):
                from .communicators._memory_utility import tree_pack
                flat, spec = tree_pack(params)
                n = flat.shape[0]
                size = communicator.size
                if communicator.striped:
                    _, n_pa, n_pb = self._striped_split(n)
                    n_pad = n_pa + n_pb
                else:
                    n_pad = -(-n // size) * size
                super().__setattr__("_zero_layout", (spec, n, n_pad))
                actual._opt_state = \
                    self._commit_opt_state_to_mesh(old_state)
        elif not self._sharded_update and actual._opt_state is not None:
            # replicated per-param state: re-home by value onto the new
            # mesh (multi-controller arrays on the old mesh cannot be
            # fed to the new mesh's program directly)
            actual._opt_state = _rehome_replicated(
                actual._opt_state, communicator)
        return self

    # -- update -------------------------------------------------------------
    def update(self, lossfun=None, *args, **kwargs):
        actual = self.actual_optimizer
        if actual.target is None:
            raise RuntimeError("setup(link) was not called")
        if lossfun is None:
            # eager path: grads already on Parameter.grad (reference flow:
            # backward → allreduce_grad → update) — the one exchange the
            # host dispatches itself, so its span times the real thing.
            # The span carries the PLANNED wire payload (ISSUE 19 small
            # fix): bandwidth = payload_bytes / duration is readable
            # directly off the trace, which is what the online autotune
            # mode (and humans in Perfetto) consume
            tags = None
            if observability.enabled():
                rows = self._exchange_plan_rows()
                if rows:
                    tags = {"payload_bytes":
                            sum(r["payload_bytes"] for r in rows),
                            "buckets": len(rows)}
            with observability.span("train/grad_exchange", tags=tags):
                self.communicator.multi_node_mean_grad(
                    actual.target, zero_fill=self.zero_fill)
            out = actual.update()
            self._maybe_online_retune()
            return out
        if self.communicator.axis_name is None:
            # dummy communicator: plain local update
            return actual.update(lossfun, *args, **kwargs)

        if any(p.array is None for p in actual.target.params()):
            with bind_state(actual.target, extract_state(actual.target)):
                lossfun(*jax.tree.map(lambda a: a, args), **kwargs)
        if hasattr(self.communicator, "verify_step_signature"):
            # debug communicator: agree on shapes/dtypes across hosts
            # before launching (fail fast instead of collective deadlock)
            self.communicator.verify_step_signature((args, kwargs))
        state = extract_state(actual.target)
        params, pstate = state["params"], state["state"]
        if self._sharded_update:
            opt_state = self._ensure_zero_opt_state(params)
        else:
            opt_state = actual._ensure_opt_state(params)
        key = actual._cache_key(lossfun, args, kwargs) \
            + (self._double_buffering, self._db_mode,
               self._sharded_update, self._needs_residual)
        step = self._mn_step_cache.get(key)
        if step is None:
            step = (self._make_zero_step(lossfun, args, kwargs)
                    if self._sharded_update
                    else self._make_step(lossfun, args, kwargs))
            self._mn_step_cache[key] = step
            # first dispatch of this program: hand it its state placed
            # as it will return it, or the second dispatch recompiles
            pstate = self._replicate_uncommitted(pstate)
            if not self._sharded_update:
                opt_state = actual._opt_state = \
                    self._replicate_uncommitted(opt_state)

        if self._double_buffering and self._stale_grads is None:
            if self._db_dcn:
                # DCN-slice-only staleness (ISSUE 11): the buffer is the
                # concatenated DCN-path slices of every bucket — a
                # stripe_ratio fraction of a full stale tree; first
                # update applies zeros on the DCN slices only
                zeros = jnp.zeros(
                    (self.communicator.grad_dcn_stale_len_for(
                        actual.target),), jnp.float32)
            elif self._sharded_update:
                # the stale buffer is the reduce-scattered mean-gradient
                # CHUNK (flat, padded, f32 — 1/n of a full stale tree on
                # each rank); first update applies zeros, same contract
                _, _, n_pad = self._zero_layout
                zeros = self._striped_chunk_template() \
                    if self.communicator.striped \
                    else jnp.zeros((n_pad,), jnp.float32)
            else:
                zeros = jax.tree.map(jnp.zeros_like, params)
            super().__setattr__("_stale_grads", zeros)
        stale = (self._stale_grads,) if self._double_buffering else ()
        residual = self._residual_operand()
        operands = (params, pstate, opt_state, actual._hyper_values(),
                    actual._next_rng_key(), stale, residual, args, kwargs)
        actual._stash_step_spec(step, operands)
        if observability.ring_enabled():
            self._emit_exchange_telemetry()
        try:
            with observability.span("train/step_dispatch"):
                new_params, new_pstate, new_opt_state, loss, grads, \
                    res_out, obs = step(*operands)
        except Exception as e:
            from .core.optimizer import raise_if_donated_state_lost
            raise_if_donated_state_lost(e, actual)
            raise
        if self._double_buffering:
            # the donated stale buffer is rebound to this step's fresh
            # mean gradient — through the wrapper, never a raw alias.
            # Under the DCN-slice variant the step returns (applied
            # gradient tree, fresh DCN-slice vector): only the latter
            # becomes the next stale buffer
            if self._db_dcn:
                grads, fresh_dcn = grads
                super().__setattr__("_stale_grads", fresh_dcn)
            else:
                super().__setattr__("_stale_grads", grads)
        if self._needs_residual:
            # same contract for the donated error-feedback buffer: this
            # step's quantization error becomes next step's correction
            super().__setattr__("_residual", res_out[0])
        # sharded updates never materialize the full mean gradient, so
        # Parameter.grad stays unpopulated (documented ZeRO contract;
        # under double buffering ``grads`` is the flat fresh CHUNK and
        # must not be scattered onto per-param slots)
        actual._write_back(new_params, new_pstate,
                           None if self._sharded_update else grads)
        actual._opt_state = new_opt_state
        actual.t += 1
        reporter_module.report(obs)
        self._maybe_online_retune()
        return loss

    def _replicate_uncommitted(self, tree):
        """Place what is not yet committed to a device (optax's scalar
        step count, BN's Python-int batch counter) replicated over the
        mesh, which is how the step returns it.  Otherwise the first
        dispatch sees an uncommitted single-device scalar and the second
        a committed mesh array: two jit cache keys, and the whole step
        compiles twice."""
        from jax.sharding import NamedSharding
        replicated = NamedSharding(self.communicator.mesh, P())
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.Array) and a.committed
            else jax.device_put(a, replicated), tree)

    # -- ZeRO-1 sharded optimizer state (beyond reference) -----------------
    def _zero_transform(self):
        """Hook chain for the ZeRO step: each rank's transform sees only
        its 1/n chunk of the flat gradient, so hooks whose semantics need
        GLOBAL gradient statistics (e.g. ``GradientClipping``'s global L2
        norm) psum across the axis — see ``Optimizer._transform``."""
        return self.actual_optimizer._transform(
            sharded_axis=self.communicator.axis_name)

    # -- striped sharded update (ISSUE 11) ---------------------------------
    def _striped_split(self, n):
        """``(n_i, n_pad_ici, n_pad_dcn)`` of the striped flat layout:
        the parameter vector splits at ``stripe_plan(n, ratio)`` and
        each slice pads to its own multiple of ``size`` (both chains
        scatter over all ``ici × dcn`` devices — only the chunk ORDER
        differs between the fast- and slow-hop-major layouts)."""
        from .communicators._memory_utility import stripe_plan
        size = self.communicator.size
        n_i, n_d = stripe_plan(n, self.communicator.stripe_ratio)
        return n_i, -(-n_i // size) * size, -(-n_d // size) * size

    def _flat_param_len(self):
        if self._zero_layout is not None:
            return self._zero_layout[1]
        from .communicators._memory_utility import tree_pack
        params = extract_state(self.actual_optimizer.target)["params"]
        return tree_pack(params)[0].shape[0]

    def _striped_chunk_template(self):
        """Zero-seeded pair of flat global vectors in the striped ZeRO
        layout — the stale-chunk template (and the restore template the
        serializer builds)."""
        n_i, n_pa, n_pb = self._striped_split(self._flat_param_len())
        return {"ici": jnp.zeros((n_pa,), jnp.float32),
                "dcn": jnp.zeros((n_pb,), jnp.float32)}

    def _stale_chunk_spec(self):
        """Sharding spec of the reduce-scatter stale buffer: the flat
        chunk layout, or the per-path pair on striped communicators."""
        comm = self.communicator
        if comm.striped:
            fast, slow = comm.striped_chunk_specs()
            return {"ici": fast, "dcn": slow}
        return comm.flat_chunk_spec()

    def _ensure_zero_opt_state(self, params):
        """Optimizer state over the PADDED FLAT parameter vector.

        Initialized on the full flat view so the compiled step can split
        it with an in_spec of ``P(axis)`` — each rank then holds (and
        updates) exactly its 1/n chunk; the returned state stays sharded
        across steps.

        On a STRIPED communicator (ISSUE 11) the flat vector splits
        into the ICI-path / DCN-path pair ``{"ici": ..., "dcn": ...}``
        — each slice padded to its own multiple of ``size`` and sharded
        by its own chunk layout (fast- vs slow-hop-major,
        ``striped_chunk_specs``); the optax transform inits over the
        pair tree, so state leaves mirror the two-slice structure.
        """
        actual = self.actual_optimizer
        if actual._opt_state is None:
            from .communicators._memory_utility import tree_pack
            flat, spec = tree_pack(params)
            n = flat.shape[0]
            size = self.communicator.size
            if self.communicator.striped:
                n_i, n_pa, n_pb = self._striped_split(n)
                super().__setattr__("_zero_layout",
                                    (spec, n, n_pa + n_pb))
                pair = {"ici": jnp.pad(flat[:n_i], (0, n_pa - n_i)),
                        "dcn": jnp.pad(flat[n_i:],
                                       (0, n_pb - (n - n_i)))}
                actual._opt_state = self._zero_transform().init(pair)
                return actual._opt_state
            n_pad = -(-n // size) * size
            flat = jnp.pad(flat, (0, n_pad - n))
            super().__setattr__("_zero_layout", (spec, n, n_pad))
            actual._opt_state = self._zero_transform().init(flat)
        return actual._opt_state

    def _zero_state_spec(self, opt_state):
        """Chunk spec for flat param-length leaves, replicated otherwise
        (e.g. Adam's step count).  The chunk layout is the
        communicator's (``flat_chunk_spec``): one axis on flat
        communicators, fast-hop-major over (ici, dcn) on hierarchical
        ones — the layout the chained reduce-scatter produces.  On
        striped communicators each slice of the pair layout gets its
        own spec, resolved by the leaf's position under the
        ``"ici"``/``"dcn"`` dict keys (the leaf LENGTHS can coincide,
        so the tree path — not the shape — is the disambiguator)."""
        _, n, n_pad = self._zero_layout
        if self.communicator.striped:
            from jax.tree_util import DictKey, tree_map_with_path
            n_i, n_pa, n_pb = self._striped_split(n)
            fast, slow = self.communicator.striped_chunk_specs()

            def spec_for(path, leaf):
                if getattr(leaf, "ndim", 0) != 1:
                    return P()
                keys = [k.key for k in path if isinstance(k, DictKey)
                        and k.key in ("ici", "dcn")]
                if keys and keys[-1] == "ici" and leaf.shape[0] == n_pa:
                    return fast
                if keys and keys[-1] == "dcn" and leaf.shape[0] == n_pb:
                    return slow
                return P()

            return tree_map_with_path(spec_for, opt_state)
        chunk_spec = self.communicator.flat_chunk_spec()
        return jax.tree.map(
            lambda leaf: chunk_spec if getattr(leaf, "ndim", 0) == 1
            and leaf.shape[0] == n_pad else P(), opt_state)

    def _make_zero_update(self):
        """Shared reduce-scatter core (ZeRO-1 AND the plain-DP
        ``exchange="reduce_scatter"`` step, per-step AND scan makers):
        flat-pack grads → reduce-scatter (each rank receives the SUM of
        its own 1/n segment — the reference's allreduce splits into
        reduce_scatter + all_gather; this path stops halfway and updates
        in the scattered domain) → chunk update → all-gather(params) →
        unpack.

        ``stale_chunk`` (double buffering × reduce-scatter): the update
        applies the PREVIOUS step's reduce-scattered mean-gradient chunk
        while this step's fresh chunk is returned to become the next
        stale buffer — the reference's one-step-stale semantics at 1/n
        of the stale-buffer footprint.

        On a HIERARCHICAL communicator the single reduce-scatter /
        all-gather becomes the hop chain ``comm.chunk_axes()`` traces
        fast-hop-first (ISSUE 6): ``psum_scatter`` over ICI on the full
        gradient, ``psum_scatter`` over DCN on the 1/ici chunk (the slow
        wire never sees more than 1/ici of the bytes; ``dcn_grad_dtype``
        can compress just that crossing), the chunk update, then
        ``all_gather`` over DCN first and ICI last — the params rebuild
        likewise puts only 1/ici of the parameter bytes on DCN.  The
        chunk layout is fast-hop-major (``comm.flat_chunk_spec()``);
        the chained index below addresses the same layout the gathers
        reassemble.

        QUANTIZED slow hop (ISSUE 8): an int8/fp8 ``dcn_grad_dtype``
        (or a quantized scalar dtype on a flat communicator — the
        escape-hatch collapse) replaces the slow hop's ``psum_scatter``
        with a quantized reduce-scatter: quantize the chunk with ONE
        per-bucket symmetric scale, ``all_to_all`` the quantized
        SEGMENTS (each crosses the slow wire exactly once — the wire
        carries the quantized fraction of the f32 reduce-scatter's
        bytes at any ring size), ``all_gather`` the scale scalars, and
        dequantize-sum on the owner.  ``residual`` (error feedback) is
        added before quantizing and the new residual ``v − Q(v)`` is
        returned to become next step's correction.
        """
        from .communicators._memory_utility import (
            dequantize_sum, is_quantized_dtype, quantize_with_feedback,
            tree_pack, tree_unpack)
        from .core.optimizer import apply_transform_update
        comm = self.communicator
        if comm.striped:
            return self._make_striped_zero_update()
        tx = self._zero_transform()
        size = comm.size
        spec, n, n_pad = self._zero_layout
        chunk = n_pad // size
        grad_dtype = comm.allreduce_grad_dtype
        dcn_dtype = getattr(comm, "dcn_grad_dtype", None)
        rs_axes = comm.chunk_axes()
        axis_sizes = [int(comm.mesh.shape[a]) for a in rs_axes]
        slow_axis = rs_axes[-1] if len(rs_axes) > 1 else None
        # the quantized hop: the slow (last) axis of the chain —
        # on a flat communicator the single world axis IS the wire the
        # quantized dtype compresses
        q_dtype = getattr(comm, "quantized_wire_dtype", None)
        q_axis = rs_axes[-1] if q_dtype is not None else None
        if is_quantized_dtype(grad_dtype):
            grad_dtype = None  # quantize at the wire, never pre-cast

        def zero_update(params, grads, opt_state, hyper, stale_chunk=None,
                        residual=None):
            new_residual = None
            with jax.named_scope("zero_reduce_scatter_grad"):
                gflat, _ = tree_pack(grads)
                gflat = jnp.pad(gflat, (0, n_pad - n))
                if grad_dtype is not None:
                    gflat = gflat.astype(grad_dtype)
                gchunk = gflat
                for a, a_size in zip(rs_axes, axis_sizes):
                    if a == q_axis:
                        with jax.named_scope("zero_quantized_rs"):
                            q, scale, new_residual = quantize_with_feedback(
                                gchunk, residual, q_dtype)
                            seg = lax.all_to_all(
                                q.reshape(a_size, -1), a,
                                split_axis=0, concat_axis=0)
                            sg = lax.all_gather(scale, a)
                            gchunk = dequantize_sum(seg, sg)
                        continue
                    if a == slow_axis and dcn_dtype is not None:
                        gchunk = gchunk.astype(dcn_dtype)
                    gchunk = lax.psum_scatter(
                        gchunk, a, scatter_dimension=0, tiled=True)
                gchunk = gchunk.astype(jnp.float32) / size
            with jax.named_scope("zero_shard_update"):
                pflat, _ = tree_pack(params)
                pflat = jnp.pad(pflat, (0, n_pad - n))
                idx = jnp.int32(0)
                for a, a_size in zip(rs_axes, axis_sizes):
                    idx = idx * a_size + lax.axis_index(a)
                pchunk = lax.dynamic_slice_in_dim(
                    pflat, idx * chunk, chunk)
                new_pchunk, new_opt_state = apply_transform_update(
                    tx, gchunk if stale_chunk is None else stale_chunk,
                    opt_state, pchunk, hyper["lr"],
                    hyper.get("decoupled_wd", 0.0))
            with jax.named_scope("zero_all_gather_params"):
                new_flat = new_pchunk
                for a in reversed(rs_axes):
                    new_flat = lax.all_gather(new_flat, a, tiled=True)
                new_params = tree_unpack(new_flat, spec)
            return new_params, new_opt_state, gchunk, new_residual

        return zero_update

    def _make_striped_zero_update(self):
        """The STRIPED two-slice sharded update (ISSUE 11): the flat
        gradient/parameter vector splits at ``stripe_plan(n, ratio)``;
        the ICI-path slice runs the fast-hop-major chained
        reduce-scatter (``psum_scatter`` over ICI on the full slice,
        then over DCN on the 1/ici chunk — the PR 6 chain), the
        DCN-path slice runs the TRANSPOSED chain (``psum_scatter`` over
        DCN on the full slice — the bulk rides the slow wire — then
        over ICI), both paths' scatters emitted before either path's
        chunk update so the two fabrics drain concurrently.  The chunk
        update runs on the ``{"ici", "dcn"}`` pair tree (optax is
        tree-generic), and the params rebuild all-gathers each slice
        along its chain in reverse — DCN carries the full DCN-path
        slice plus 1/ici of the ICI-path slice, in both directions.

        Per-hop dtype: ``dcn_grad_dtype`` compresses exactly the DCN
        crossings (the ICI-path chunk's DCN scatter AND the DCN-path
        slice's bulk scatter); the fast hop accumulates in f32
        (lossless by design — the DCN-path chunk upcasts before its ICI
        scatter).  Quantized wires are rejected at construction.
        ``stale_chunk`` (double buffering) is the one-step-stale pair
        of mean-gradient chunks — the PR 5 contract on both paths at
        the striped layout."""
        from .communicators._memory_utility import tree_pack, tree_unpack
        from .core.optimizer import apply_transform_update
        comm = self.communicator
        tx = self._zero_transform()
        size = comm.size
        spec, n, _ = self._zero_layout
        n_i, n_pa, n_pb = self._striped_split(n)
        n_d = n - n_i
        chunk_a = n_pa // size
        chunk_b = n_pb // size
        ici, dcn = comm.ici_axis, comm.dcn_axis
        intra, inter = comm.ici_size, comm.dcn_size
        grad_dtype = comm.allreduce_grad_dtype
        dcn_dtype = getattr(comm, "dcn_grad_dtype", None)

        def zero_update(params, grads, opt_state, hyper, stale_chunk=None,
                        residual=None):
            with jax.named_scope("striped_zero_rs_grad"):
                gflat, _ = tree_pack(grads)
                if grad_dtype is not None:
                    gflat = gflat.astype(grad_dtype)
                ga = jnp.pad(gflat[:n_i], (0, n_pa - n_i))
                gb = jnp.pad(gflat[n_i:n], (0, n_pb - n_d))
                # slow-path-first emission (hop_schedule's striped
                # contract): the DCN-path bulk scatter is issued first,
                # then the ICI-path bulk, then the two chunk scatters
                if dcn_dtype is not None:
                    gb = gb.astype(dcn_dtype)
                if n_d:
                    gb = lax.psum_scatter(gb, dcn, scatter_dimension=0,
                                          tiled=True)
                if n_i:
                    ga = lax.psum_scatter(ga, ici, scatter_dimension=0,
                                          tiled=True)
                if n_d:
                    # lossless fast hop: upcast before accumulating
                    gb = lax.psum_scatter(gb.astype(jnp.float32), ici,
                                          scatter_dimension=0, tiled=True)
                if n_i:
                    if dcn_dtype is not None:
                        ga = ga.astype(dcn_dtype)
                    ga = lax.psum_scatter(ga, dcn, scatter_dimension=0,
                                          tiled=True)
                gchunk = {"ici": ga.astype(jnp.float32) / size,
                          "dcn": gb.astype(jnp.float32) / size}
            with jax.named_scope("striped_zero_shard_update"):
                pflat, _ = tree_pack(params)
                pa = jnp.pad(pflat[:n_i], (0, n_pa - n_i))
                pb = jnp.pad(pflat[n_i:n], (0, n_pb - n_d))
                idx_a = lax.axis_index(ici) * inter + lax.axis_index(dcn)
                idx_b = lax.axis_index(dcn) * intra + lax.axis_index(ici)
                # a degenerate ratio (0/1) leaves one slice EMPTY: its
                # chunk is the (0,) vector itself — zero-length
                # dynamic_slices and all_gathers do not lower
                pchunk = {"ici": lax.dynamic_slice_in_dim(
                              pa, idx_a * chunk_a, chunk_a)
                          if n_i else pa,
                          "dcn": lax.dynamic_slice_in_dim(
                              pb, idx_b * chunk_b, chunk_b)
                          if n_d else pb}
                new_pchunk, new_opt_state = apply_transform_update(
                    tx, gchunk if stale_chunk is None else stale_chunk,
                    opt_state, pchunk, hyper["lr"],
                    hyper.get("decoupled_wd", 0.0))
            with jax.named_scope("striped_zero_all_gather_params"):
                fa = new_pchunk["ici"]
                if n_i:
                    for a in (dcn, ici):  # reverse of the (ici, dcn) chain
                        fa = lax.all_gather(fa, a, tiled=True)
                fb = new_pchunk["dcn"]
                if n_d:
                    for a in (ici, dcn):  # reverse of the (dcn, ici) chain
                        fb = lax.all_gather(fb, a, tiled=True)
                new_params = tree_unpack(
                    jnp.concatenate([fa[:n_i], fb[:n_d]]), spec)
            return new_params, new_opt_state, gchunk, None

        return zero_update

    def _make_zero_step(self, lossfun, ex_args, ex_kwargs):
        from jax import shard_map
        from .core.optimizer import make_loss_and_grad
        comm = self.communicator
        actual = self.actual_optimizer
        axis = comm.axis_name
        size = comm.size
        double_buffering = self._double_buffering
        needs_residual = self._needs_residual
        zero_update = self._make_zero_update()
        loss_and_grad = make_loss_and_grad(actual.target, lossfun)

        def rank_step(params, pstate, opt_state, hyper, rng_key, stale,
                      residual, args, kwargs):
            rng_local = jax.random.fold_in(rng_key, lax.axis_index(axis))
            with jax.named_scope("zero_forward_backward"):
                loss, new_pstate, obs, grads = loss_and_grad(
                    params, pstate, rng_local, args, kwargs)
            new_params, new_opt_state, fresh_chunk, new_residual = \
                zero_update(params, grads, opt_state, hyper,
                            stale[0] if double_buffering else None,
                            residual[0] if needs_residual else None)
            loss = lax.pmean(loss, axis)
            obs = jax.tree.map(lambda o: lax.pmean(o, axis), obs)
            new_pstate = _pmean_state(new_pstate, axis)
            # grads out: the fresh mean-gradient CHUNK under double
            # buffering (it becomes the next stale buffer); otherwise
            # None — the full mean gradient never exists on this path
            out_grads = fresh_chunk if double_buffering else None
            res_out = (new_residual,) if needs_residual else ()
            return new_params, new_pstate, new_opt_state, loss, \
                out_grads, res_out, obs

        args_specs = jax.tree.map(
            lambda leaf: self._batch_spec(leaf, axis, size), ex_args)
        kwargs_specs = jax.tree.map(
            lambda leaf: self._batch_spec(leaf, axis, size), ex_kwargs)
        opt_specs = self._zero_state_spec(actual._opt_state)
        # the stale chunk is sharded like the opt state's flat leaves
        # (the per-path pair on striped communicators); the
        # error-feedback residual shares the flat layout (per-device
        # slice of a flat vector)
        stale_spec = self._stale_chunk_spec() if double_buffering else P()
        residual_spec = comm.flat_chunk_spec() if needs_residual else P()
        # the stale operand is tuple-wrapped; a dict-shaped striped
        # spec cannot prefix a tuple, so wrap the IN spec to match the
        # operand structure (the OUT slot is the bare fresh chunk)
        stale_in_spec = (stale_spec,) if double_buffering else P()
        mapped = shard_map(
            rank_step, mesh=comm.mesh,
            in_specs=(P(), P(), opt_specs, P(), P(), stale_in_spec,
                      residual_spec, args_specs, kwargs_specs),
            out_specs=(P(), P(), opt_specs, P(), stale_spec,
                       residual_spec, P()),
            check_vma=False)
        if getattr(actual, "donate_params", True):
            # under double buffering the stale chunk (argnum 5) is
            # replaced by this step's fresh chunk — donate it too; same
            # for the error-feedback residual (argnum 6)
            donate = (0, 2)
            donate += (5,) if double_buffering else ()
            donate += (6,) if needs_residual else ()
        else:
            donate = (2,)
        return jax.jit(mapped, donate_argnums=donate)

    # -- compiled DP step ------------------------------------------------------
    @staticmethod
    def _scan_batch_spec(leaf, axis, size):
        """update_scan leaves: leading axis = step axis (replicated),
        axis 1 = global batch (split across ranks)."""
        if leaf.shape[1] % size == 0 and leaf.shape[1] > 0:
            return P(None, axis)
        raise ValueError(
            f"update_scan leaf with batch dim {leaf.shape[1]} is not "
            f"divisible by communicator size {size}")

    def _batch_spec(self, leaf, axis, size):
        """Batch-sharding heuristic: leaves with a leading dim divisible by
        ``size`` are split across ranks; scalars are replicated; anything
        else is a shape error (scatter_dataset guarantees divisibility —
        silent replication would quietly discard data parallelism)."""
        if not hasattr(leaf, "shape") or leaf.ndim == 0:
            return P()
        if leaf.shape[0] % size == 0 and leaf.shape[0] > 0:
            return P(axis)
        raise ValueError(
            f"batch leaf with leading dim {leaf.shape[0]} is not divisible "
            f"by communicator size {size}; scatter_dataset keeps shards "
            f"equal — use batchsize = per_rank_bs * comm.size (pass "
            f"per-example weights with a batch-sized leading axis, scalars "
            f"as 0-d arrays)")

    def _make_step(self, lossfun, ex_args, ex_kwargs):
        from jax import shard_map
        from .core.optimizer import (apply_transform_update,
                                     make_loss_and_grad)
        comm = self.communicator
        actual = self.actual_optimizer
        tx = actual._transform()
        grad_transform = comm.grad_transform()
        axis = comm.axis_name
        size = comm.size
        double_buffering = self._double_buffering
        db_dcn = self._db_dcn
        needs_residual = self._needs_residual
        loss_and_grad = make_loss_and_grad(actual.target, lossfun)

        def rank_step(params, pstate, opt_state, hyper, rng_key, stale,
                      residual, args, kwargs):
            # decorrelate stochastic masks across ranks (each rank holds a
            # different batch shard)
            rng_local = jax.random.fold_in(rng_key, lax.axis_index(axis))
            with jax.named_scope("mn_forward_backward"):
                loss, new_pstate, obs, grads = loss_and_grad(
                    params, pstate, rng_local, args, kwargs)
            # the reference's allreduce_grad: mean over ranks, optional
            # dtype compression, optional flat bucket — all in-program;
            # quantized wires additionally thread the error-feedback
            # residual through the transform (ISSUE 8); the striped
            # DCN-slice stale variant (ISSUE 11) threads the previous
            # step's DCN-path results and receives the fresh ones back
            with jax.named_scope("mn_allreduce_grad"):
                if db_dcn:
                    out = grad_transform(
                        grads, residual[0] if needs_residual else None,
                        stale_dcn=stale[0])
                    if needs_residual:
                        grads, new_residual, fresh_dcn = out
                        res_out = (new_residual,)
                    else:
                        grads, fresh_dcn = out
                        res_out = ()
                elif needs_residual:
                    grads, new_residual = grad_transform(grads, residual[0])
                    res_out = (new_residual,)
                else:
                    grads = grad_transform(grads)
                    res_out = ()
            # db_dcn applies the transform's output directly — the stale
            # DCN slices are already assembled INSIDE it, per path
            apply_grads = stale[0] \
                if double_buffering and not db_dcn else grads
            with jax.named_scope("mn_optimizer_update"):
                new_params, new_opt_state = apply_transform_update(
                    tx, apply_grads, opt_state, params, hyper["lr"],
                    hyper.get("decoupled_wd", 0.0))
            # per-rank scalars → global means for reporting / BN state
            loss = lax.pmean(loss, axis)
            obs = jax.tree.map(lambda o: lax.pmean(o, axis), obs)
            new_pstate = _pmean_state(new_pstate, axis)
            out_grads = (grads, fresh_dcn) if db_dcn else grads
            return new_params, new_pstate, new_opt_state, loss, out_grads, \
                res_out, obs

        args_specs = jax.tree.map(
            lambda leaf: self._batch_spec(leaf, axis, size), ex_args)
        kwargs_specs = jax.tree.map(
            lambda leaf: self._batch_spec(leaf, axis, size), ex_kwargs)
        # the residual is a per-device slice of a flat vector — the
        # same chunked layout (and resume plumbing) as the
        # reduce-scatter stale chunk
        residual_spec = comm.flat_chunk_spec() if needs_residual else P()
        mapped = shard_map(
            rank_step, mesh=comm.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), residual_spec,
                      args_specs, kwargs_specs),
            out_specs=(P(), P(), P(), P(), P(), residual_spec, P()),
            check_vma=False)
        # donate params + opt_state (and, under double buffering, the
        # params-sized stale-grad buffer at argnum 5: it is replaced by
        # this step's returned gradient, so XLA may update it in place;
        # same for the error-feedback residual at argnum 6).
        # Safe by default through the Link bridge — see core/optimizer.py
        # ``donate_params``; set it False on the wrapped optimizer to
        # keep pre-update buffers alive.
        if getattr(actual, "donate_params", True):
            donate = (0, 2)
            donate += (5,) if double_buffering else ()
            donate += (6,) if needs_residual else ()
        else:
            donate = (2,)
        return jax.jit(mapped, donate_argnums=donate)

    # -- multi-step fused dispatch ----------------------------------------------
    def update_scan(self, lossfun, *args, **kwargs):
        """Run K training steps in ONE compiled dispatch.

        Every array leaf in ``args``/``kwargs`` carries a leading *step*
        axis of length K stacked on top of the usual global-batch axis:
        shape ``(K, global_bs, ...)``.  The compiled program lax.scans
        over the step axis inside the shard_mapped body — K full
        forward/backward/allreduce/update iterations per host dispatch,
        so per-step host and dispatch latency is amortized K-fold (the
        TPU-idiomatic equivalent of the reference's tight C-level update
        loop).

        Returns the per-step loss array of shape ``(K,)``.  Reported
        observations are the MEAN over the K steps (what a LogReport
        consumer would average from K plain updates).  Hyperparams
        (lr, ...) are read once per dispatch — a schedule that must
        change *within* the K steps needs plain ``update`` calls.
        Double buffering is not supported here (one-step staleness
        inside a fused scan would reorder its observable semantics).
        ``zero_sharding`` composes: the scan carries one gathered
        params buffer plus the sharded flat optimizer state, each
        iteration running the full reduce-scatter → chunk update →
        all-gather step (``_make_zero_scan_step``).
        RNG streams differ from the per-step ``update()`` path (one
        dispatch key with the step index folded in, vs a fresh host key
        per step), so stochastic layers (dropout) are numerically equal
        only for deterministic models.
        """
        if self._double_buffering:
            raise RuntimeError("update_scan does not support double "
                               "buffering; use update()")
        actual = self.actual_optimizer
        if actual.target is None:
            raise RuntimeError("setup(link) was not called")
        if self.communicator.axis_name is None:
            raise RuntimeError("update_scan requires a mesh communicator")
        leaves = jax.tree.leaves((args, kwargs))
        if not leaves or any(not hasattr(l, "shape") or l.ndim < 2
                             for l in leaves):
            raise ValueError("update_scan arguments must be arrays with a "
                             "leading (n_steps, global_batch, ...) axis")
        n_steps = leaves[0].shape[0]
        if any(l.shape[0] != n_steps for l in leaves):
            raise ValueError("all update_scan leaves must share the same "
                             "leading step-axis length")

        if any(p.array is None for p in actual.target.params()):
            with bind_state(actual.target, extract_state(actual.target)):
                first = jax.tree.map(lambda a: a[0], (args, kwargs))
                lossfun(*first[0], **first[1])
        if hasattr(self.communicator, "verify_step_signature"):
            # debug communicator: agree on shapes/dtypes across hosts
            # before launching (fail fast instead of collective deadlock)
            self.communicator.verify_step_signature((args, kwargs))
        state = extract_state(actual.target)
        params, pstate = state["params"], state["state"]
        if self._sharded_update:
            opt_state = self._ensure_zero_opt_state(params)
        else:
            opt_state = actual._ensure_opt_state(params)
        key = ("scan", n_steps, self._sharded_update,
               self._needs_residual) \
            + actual._cache_key(lossfun, args, kwargs)
        step = self._mn_step_cache.get(key)
        if step is None:
            step = (self._make_zero_scan_step(lossfun, args, kwargs, n_steps)
                    if self._sharded_update
                    else self._make_scan_step(lossfun, args, kwargs, n_steps))
            self._mn_step_cache[key] = step
        residual = self._residual_operand()
        operands = (params, pstate, opt_state, actual._hyper_values(),
                    actual._next_rng_key(), residual, args, kwargs)
        actual._stash_step_spec(step, operands)
        if observability.ring_enabled():
            self._emit_exchange_telemetry()
        try:
            new_params, new_pstate, new_opt_state, losses, grads, \
                res_out, obs = step(*operands)
        except Exception as e:
            from .core.optimizer import raise_if_donated_state_lost
            raise_if_donated_state_lost(e, actual)
            raise
        if self._needs_residual:
            # the residual rides the scan carry: the K-th step's error
            # comes back to seed dispatch K+1
            super().__setattr__("_residual", res_out[0])
        actual._write_back(new_params, new_pstate, grads)
        actual._opt_state = new_opt_state
        actual.t += n_steps
        reporter_module.report(obs)
        return losses

    def _make_scan_step(self, lossfun, ex_args, ex_kwargs, n_steps):
        from jax import shard_map
        from .core.optimizer import (apply_transform_update,
                                     make_loss_and_grad)
        comm = self.communicator
        actual = self.actual_optimizer
        tx = actual._transform()
        grad_transform = comm.grad_transform()
        axis = comm.axis_name
        size = comm.size
        needs_residual = self._needs_residual
        loss_and_grad = make_loss_and_grad(actual.target, lossfun)

        def rank_scan(params, pstate, opt_state, hyper, rng_key, residual,
                      args, kwargs):
            rng_rank = jax.random.fold_in(rng_key, lax.axis_index(axis))

            def one_step(carry, xs):
                params, pstate, opt_state, _, res, i = carry
                s_args, s_kwargs = xs
                rng_i = jax.random.fold_in(rng_rank, i)
                loss, new_pstate, obs, grads = loss_and_grad(
                    params, pstate, rng_i, s_args, s_kwargs)
                if needs_residual:
                    grads, res = grad_transform(grads, res)
                else:
                    grads = grad_transform(grads)
                new_params, new_opt_state = apply_transform_update(
                    tx, grads, opt_state, params, hyper["lr"],
                    hyper.get("decoupled_wd", 0.0))
                # grads ride the CARRY (one params-sized buffer, the last
                # step's value survives) — stacking them as scan ys would
                # materialize a (K, model-size) buffer in HBM, defeating
                # donate_params for exactly the large models K-step fusion
                # targets.  Only the small per-step scalars stack.  The
                # error-feedback residual rides the carry for the same
                # reason — each step corrects the previous one's error.
                return ((new_params, new_pstate, new_opt_state, grads,
                         res, i + 1), (loss, obs))

            init_grads = jax.tree.map(jnp.zeros_like, params)
            init_res = residual[0] if needs_residual else jnp.zeros((0,))
            (params, pstate, opt_state, last_grads, last_res, _), \
                (losses, all_obs) = \
                lax.scan(one_step, (params, pstate, opt_state, init_grads,
                                    init_res, jnp.int32(0)),
                         (args, kwargs))
            losses = lax.pmean(losses, axis)
            pstate = _pmean_state(pstate, axis)
            # observations: mean over the K fused steps (matches what a
            # LogReport consumer would average from K plain updates), then
            # over ranks
            obs = jax.tree.map(
                lambda o: lax.pmean(jnp.mean(o, axis=0), axis), all_obs)
            res_out = (last_res,) if needs_residual else ()
            return params, pstate, opt_state, losses, last_grads, \
                res_out, obs

        args_specs = jax.tree.map(
            lambda leaf: self._scan_batch_spec(leaf, axis, size), ex_args)
        kwargs_specs = jax.tree.map(
            lambda leaf: self._scan_batch_spec(leaf, axis, size), ex_kwargs)
        residual_spec = comm.flat_chunk_spec() if needs_residual else P()
        mapped = shard_map(
            rank_scan, mesh=comm.mesh,
            in_specs=(P(), P(), P(), P(), P(), residual_spec, args_specs,
                      kwargs_specs),
            out_specs=(P(), P(), P(), P(), P(), residual_spec, P()),
            check_vma=False)
        donate = (0, 2) if getattr(actual, "donate_params", True) else (2,)
        if needs_residual and getattr(actual, "donate_params", True):
            donate += (5,)
        return jax.jit(mapped, donate_argnums=donate)

    def _make_zero_scan_step(self, lossfun, ex_args, ex_kwargs, n_steps):
        """ZeRO-1 × fused K-step dispatch: the scan carries the gathered
        params (ONE buffer, exactly as per-step ZeRO keeps one gathered
        copy live) plus the sharded flat opt state; each scan iteration
        is the full reduce-scatter → chunk update → all-gather step."""
        from jax import shard_map
        from .core.optimizer import make_loss_and_grad
        comm = self.communicator
        actual = self.actual_optimizer
        axis = comm.axis_name
        size = comm.size
        needs_residual = self._needs_residual
        zero_update = self._make_zero_update()
        loss_and_grad = make_loss_and_grad(actual.target, lossfun)

        def rank_scan(params, pstate, opt_state, hyper, rng_key, residual,
                      args, kwargs):
            rng_rank = jax.random.fold_in(rng_key, lax.axis_index(axis))

            def one_step(carry, xs):
                params, pstate, opt_state, res, i = carry
                s_args, s_kwargs = xs
                rng_i = jax.random.fold_in(rng_rank, i)
                loss, new_pstate, obs, grads = loss_and_grad(
                    params, pstate, rng_i, s_args, s_kwargs)
                new_params, new_opt_state, _, new_res = zero_update(
                    params, grads, opt_state, hyper, None,
                    res if needs_residual else None)
                if not needs_residual:
                    new_res = res
                return ((new_params, new_pstate, new_opt_state, new_res,
                         i + 1), (loss, obs))

            init_res = residual[0] if needs_residual else jnp.zeros((0,))
            (params, pstate, opt_state, last_res, _), (losses, all_obs) = \
                lax.scan(one_step,
                         (params, pstate, opt_state, init_res,
                          jnp.int32(0)), (args, kwargs))
            losses = lax.pmean(losses, axis)
            pstate = _pmean_state(pstate, axis)
            obs = jax.tree.map(
                lambda o: lax.pmean(jnp.mean(o, axis=0), axis), all_obs)
            res_out = (last_res,) if needs_residual else ()
            # None grads: the full mean gradient never exists under ZeRO
            return params, pstate, opt_state, losses, None, res_out, obs

        args_specs = jax.tree.map(
            lambda leaf: self._scan_batch_spec(leaf, axis, size), ex_args)
        kwargs_specs = jax.tree.map(
            lambda leaf: self._scan_batch_spec(leaf, axis, size), ex_kwargs)
        opt_specs = self._zero_state_spec(actual._opt_state)
        residual_spec = comm.flat_chunk_spec() if needs_residual else P()
        mapped = shard_map(
            rank_scan, mesh=comm.mesh,
            in_specs=(P(), P(), opt_specs, P(), P(), residual_spec,
                      args_specs, kwargs_specs),
            out_specs=(P(), P(), opt_specs, P(), P(), residual_spec, P()),
            check_vma=False)
        donate = (0, 2) if getattr(actual, "donate_params", True) else (2,)
        if needs_residual and getattr(actual, "donate_params", True):
            donate += (5,)
        return jax.jit(mapped, donate_argnums=donate)

    # -- misc reference API -----------------------------------------------------
    def new_epoch(self):
        self.actual_optimizer.new_epoch()

    def add_hook(self, hook, name=None, timing="pre"):
        self.actual_optimizer.add_hook(hook, name, timing)
        # add_hook resets _opt_state; every piece of wrapper state whose
        # lifetime tracks it resets too (same invariant as setup()): a
        # stale _zero_layout would make the serialize pre-seed guard
        # skip rebuilding the flat template, and a kept _stale_grads
        # would apply a pre-hook gradient against fresh optimizer state
        # instead of the double-buffer fresh-start semantics
        super().__setattr__("_zero_layout", None)
        super().__setattr__("_stale_grads", None)
        super().__setattr__("_residual", None)
        self._mn_step_cache.clear()

    def remove_hook(self, name):
        self.actual_optimizer.remove_hook(name)
        super().__setattr__("_zero_layout", None)
        super().__setattr__("_stale_grads", None)
        super().__setattr__("_residual", None)
        self._mn_step_cache.clear()

    def _gather_opt_state_to_host(self, opt_state):
        """Assemble non-fully-addressable (real multi-controller sharded)
        leaves as full host ndarrays on EVERY process, via the object
        channel.  ``np.asarray`` on such leaves raises — each process only
        holds its own 1/n chunk — so the npz writer cannot see them
        directly.  Gathering to host makes every per-host snapshot carry
        the complete flat vector; ``_commit_opt_state_to_mesh`` re-pads it
        on load, so resume tolerates a changed communicator size.

        COLLECTIVE on a real multi-process mesh: every process must enter
        ``serialize`` (the per-host multi-node checkpointer does; a
        rank-0-only ``extensions.snapshot()`` pattern would deadlock in
        the allgather — use ``create_multi_node_checkpointer`` for ZeRO
        runs, as the reference does for distributed state)."""
        def materialize(leaf):
            if not isinstance(leaf, jax.Array) or leaf.is_fully_addressable:
                return leaf
            local = [(s.index, np.asarray(s.data))
                     for s in leaf.addressable_shards]
            gathered = self.communicator._process_allgather_pickled(local)
            out = np.empty(leaf.shape, leaf.dtype)
            for shards in gathered:
                for index, data in shards:
                    out[index] = data
            return out

        return jax.tree.map(materialize, opt_state)

    def _commit_opt_state_to_mesh(self, opt_state):
        """Re-commit restored flat (n_pad,) leaves to the ZeRO sharded
        layout.  ``deserialize_flat_tree`` leaves full host-replicated
        arrays; on a real multi-process mesh the compiled step's
        ``shard_map`` needs globally-sharded ``jax.Array`` inputs, and on
        any mesh committing up front avoids a device_put inside the first
        post-resume step.  A flat vector saved under a DIFFERENT
        communicator size (padding to a different multiple) is sliced to
        the true parameter length ``n`` and re-padded to this mesh's
        ``n_pad`` first — the host-gathered snapshots are full vectors,
        so size-changed resume is well-defined."""
        if self.communicator.striped:
            return self._commit_striped_state_to_mesh(opt_state)
        chunk_spec = self.communicator.flat_chunk_spec()
        mesh = self.communicator.mesh
        _, n, n_pad = self._zero_layout

        def commit(leaf):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                # already mesh-sharded (e.g. the pre-seeded template kept
                # by a partial/pre-feature snapshot): nothing to commit,
                # and np.asarray on it would raise
                return leaf
            if getattr(leaf, "ndim", 0) != 1:
                return leaf
            if leaf.shape[0] != n_pad:
                if leaf.shape[0] < n:
                    return leaf  # not a flat param vector
                leaf = jnp.pad(jnp.asarray(leaf)[:n], (0, n_pad - n))
            host = np.asarray(leaf)
            sharding = jax.sharding.NamedSharding(mesh, chunk_spec)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])

        return jax.tree.map(commit, opt_state)

    def _commit_striped_state_to_mesh(self, tree):
        """Striped variant of :meth:`_commit_opt_state_to_mesh`: each
        flat leaf of the ``{"ici", "dcn"}`` pair layout commits to ITS
        path's chunk spec (fast- vs slow-hop-major), resolved by the
        leaf's dict-key path — the two padded lengths may coincide, so
        the tree position, not the shape, is the disambiguator.  A leaf
        saved under a different communicator SIZE re-pads from its
        path's true (size-independent) slice length; a different
        STRIPE RATIO moves the split point itself, which the ef-
        residual-style re-seed contract does not cover — resume striped
        state with the ratio it was saved under."""
        from jax.tree_util import DictKey, tree_map_with_path
        comm = self.communicator
        mesh = comm.mesh
        _, n, _ = self._zero_layout
        n_i, n_pa, n_pb = self._striped_split(n)
        fast, slow = comm.striped_chunk_specs()
        target = {"ici": (n_i, n_pa, fast), "dcn": (n - n_i, n_pb, slow)}

        def commit(path, leaf):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                return leaf
            if getattr(leaf, "ndim", 0) != 1:
                return leaf
            keys = [k.key for k in path if isinstance(k, DictKey)
                    and k.key in target]
            if not keys:
                return leaf
            true_n, n_pad, cspec = target[keys[-1]]
            if leaf.shape[0] != n_pad:
                if leaf.shape[0] < true_n:
                    return leaf  # not a flat slice vector
                leaf = jnp.pad(jnp.asarray(leaf)[:true_n],
                               (0, n_pad - true_n))
            host = np.asarray(leaf)
            sharding = jax.sharding.NamedSharding(mesh, cspec)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])

        return tree_map_with_path(commit, tree)

    def serialize(self, serializer):
        actual = self.actual_optimizer
        if self._sharded_update and not serializer.is_writer \
                and actual.target is not None and self._zero_layout is None:
            # The saved opt_state leaves are flat (n_pad,) vectors.  The
            # base reader builds its template from the CURRENT _opt_state
            # — or, when None, from the default per-param tree, whose leaf
            # count/shapes mismatch the flat save.  Pre-seed the flat
            # sharded template + _zero_layout before delegating.  Guarded
            # on _zero_layout is None: a warm ZeRO process already holds a
            # valid flat template (and must NOT be reset — a snapshot
            # without opt_state keys would otherwise silently zero trained
            # state); a layout-less process either has no state or a
            # per-param tree from pre-wrapper use, both safely rebuilt.
            params = extract_state(actual.target)["params"]
            if not params or any(v is None for v in params.values()):
                # lazily-initialized model: take shapes from the snapshot
                # (idempotent — the delegated serialize re-reads this
                # section)
                actual.target.serialize(serializer["target"])
                params = extract_state(actual.target)["params"]
            if params and all(v is not None for v in params.values()):
                actual._opt_state = None
                self._ensure_zero_opt_state(params)
        device_state = None
        if serializer.is_writer and self._sharded_update \
                and actual._opt_state is not None \
                and any(isinstance(l, jax.Array)
                        and not l.is_fully_addressable
                        for l in jax.tree.leaves(actual._opt_state)):
            # real multi-controller mesh: swap in host-assembled full
            # vectors for the write, then restore the sharded originals
            device_state = actual._opt_state
            actual._opt_state = self._gather_opt_state_to_host(device_state)
        try:
            actual.serialize(serializer)
        finally:
            if device_state is not None:
                actual._opt_state = device_state
        if self._sharded_update and not serializer.is_writer \
                and actual._opt_state is not None \
                and self._zero_layout is not None:
            actual._opt_state = self._commit_opt_state_to_mesh(
                actual._opt_state)
        if self._needs_residual:
            # the error-feedback residual is OBSERVABLE state (ISSUE 8):
            # the telescoping sum — applied updates so far + residual ==
            # true gradient sum — must survive a checkpoint/restore, or
            # the resumed run silently drops the carried error.  Same
            # flat-vector plumbing as the stale chunk.  Size-changed
            # resume re-seeds ZEROS: the residual is per-DEVICE
            # quantization error with no global content invariant (a new
            # partition quantizes different chunks), and dropping it
            # costs exactly one step of correction, never correctness.
            self._serialize_residual(serializer)
        if self._double_buffering:
            # the one-step-stale gradient buffer is OBSERVABLE state:
            # without it a resumed run applies zeros on its first update
            # (fresh-start semantics) instead of the saved step's grads,
            # breaking bit-exact resume
            from .core.optimizer import (deserialize_flat_tree,
                                         serialize_flat_tree)
            sub = serializer["stale_grads"]
            if serializer.is_writer:
                if self._stale_grads is not None:
                    # reduce-scatter double buffering on a real
                    # multi-controller mesh: the stale buffer is
                    # P(axis)-sharded (each process holds its 1/n
                    # chunk) and np.asarray on it raises — same
                    # host-gather the opt_state write gets above
                    serialize_flat_tree(
                        sub,
                        self._gather_opt_state_to_host(self._stale_grads),
                        "n", "g")
                return
            if actual.target is None:
                return  # target-less load: base serialize skipped too
            params = extract_state(actual.target)["params"]
            if not params or any(v is None for v in params.values()):
                super().__setattr__("_stale_grads", None)
                return
            if self._db_dcn:
                # DCN-slice-only stale variant (ISSUE 11): a flat
                # replicated vector of the buckets' DCN-path slices —
                # length derivable from params + the committed ratio
                template = jnp.zeros(
                    (self.communicator.grad_dcn_stale_len_for(
                        actual.target),), jnp.float32)
            elif self._sharded_update:
                # reduce-scatter double buffering: the stale buffer is
                # the flat padded mean-gradient vector, not a per-param
                # tree.  Its length is derivable from params alone, so
                # compute it directly rather than depending on the
                # opt-state pre-seed having run.
                if self._zero_layout is not None:
                    _, n, n_pad = self._zero_layout
                else:
                    from .communicators._memory_utility import tree_pack
                    n = tree_pack(params)[0].shape[0]
                    size = self.communicator.size
                    n_pad = -(-n // size) * size
                template = jnp.zeros((n_pad,), jnp.float32) \
                    if not self.communicator.striped \
                    else self._striped_chunk_template()
            else:
                template = jax.tree.map(jnp.zeros_like, params)
            restored = deserialize_flat_tree(sub, template, "n", "g")
            if self._sharded_update and self.communicator.striped \
                    and restored is not None:
                # striped pair layout: commit each path's slice to its
                # own chunk spec (size-changed re-pad included)
                super().__setattr__(
                    "_stale_grads",
                    self._commit_striped_state_to_mesh(restored))
                return
            if self._sharded_update and restored is not None and not (
                    isinstance(restored, jax.Array)
                    and not restored.is_fully_addressable):
                if restored.shape != template.shape \
                        and restored.shape[0] >= n:
                    # saved under a DIFFERENT communicator size: the
                    # vector is padded to the old size's multiple, but
                    # content length n is invariant — slice and re-pad,
                    # the same size-changed resume contract
                    # _commit_opt_state_to_mesh gives the flat opt-state
                    # leaves
                    restored = jnp.pad(jnp.asarray(restored)[:n],
                                       (0, n_pad - n))
                # commit to the P(axis) layout the compiled step's
                # shard_map expects — on a real multi-controller mesh the
                # host-replicated restore cannot be auto-sharded at
                # dispatch (same reason the opt-state restore goes
                # through _commit_opt_state_to_mesh)
                host = np.asarray(restored)
                sharding = jax.sharding.NamedSharding(
                    self.communicator.mesh,
                    self.communicator.flat_chunk_spec())
                restored = jax.make_array_from_callback(
                    host.shape, sharding, lambda idx: host[idx])
            # None restored = snapshot predates stale-grad saving (or was
            # taken before the first update): fresh zero-seed semantics
            super().__setattr__("_stale_grads", restored)

    def _serialize_residual(self, serializer):
        from .core.optimizer import (deserialize_flat_tree,
                                     serialize_flat_tree)
        actual = self.actual_optimizer
        sub = serializer["ef_residual"]
        if serializer.is_writer:
            if self._residual is not None:
                # sharded on a real multi-controller mesh — same
                # host-gather the opt_state/stale writes get
                serialize_flat_tree(
                    sub, self._gather_opt_state_to_host(self._residual),
                    "n", "r")
                # the residual is per-DEVICE content: record the world
                # size it was partitioned for, so a size-changed resume
                # re-seeds even when the GLOBAL lengths coincide (e.g.
                # ceil(n/4)·8 == ceil(n/2)·4 — ISSUE 10 satellite)
                sub("world_size", self.communicator.size)
            return
        if actual.target is None:
            return
        params = extract_state(actual.target)["params"]
        if not params or any(v is None for v in params.values()):
            super().__setattr__("_residual", None)
            return
        if self._sharded_update and self._zero_layout is None:
            # no flat layout yet (e.g. pre-feature snapshot without
            # opt_state): the residual length is underivable — zero-seed
            # on first update instead
            super().__setattr__("_residual", None)
            return
        length = self._residual_global_len()
        template = jnp.zeros((length,), jnp.float32)
        restored = deserialize_flat_tree(sub, template, "n", "r")
        if restored is None:
            # pre-feature snapshot: fresh zero-seed on first update
            super().__setattr__("_residual", None)
            return
        try:
            saved_size = int(sub("world_size", -1))
        except KeyError:
            saved_size = -1  # strict reader, pre-field snapshot
        if saved_size not in (-1, self.communicator.size):
            # partitioned for a DIFFERENT world: zero-seed even when the
            # global length happens to coincide (the shape check below
            # cannot see a re-partition at equal length)
            super().__setattr__("_residual", None)
            return
        if not (isinstance(restored, jax.Array)
                and not restored.is_fully_addressable):
            if restored.shape != template.shape:
                # saved under a DIFFERENT communicator size/plan:
                # per-device error has no cross-partition meaning —
                # zero-seed (documented contract, one step of error)
                super().__setattr__("_residual", None)
                return
            host = np.asarray(restored)
            sharding = jax.sharding.NamedSharding(
                self.communicator.mesh,
                self.communicator.flat_chunk_spec())
            restored = jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])
        super().__setattr__("_residual", restored)


class _DoubleBufferingOptimizer(_MultiNodeOptimizer):
    """One-step-stale gradient application (reference semantics).

    Reference: ``optimizers.py · _DoubleBufferingOptimizer`` — allreduce of
    step *t*'s grads overlaps step *t+1*'s compute; the applied gradient is
    one step old.  Here both live in the same compiled program and XLA's
    async dispatch provides the overlap; the observable contract (first
    update applies zeros, update ``t`` applies grads of ``t-1``) matches.

    ``db_mode="dcn"`` (ISSUE 11, striped communicators only): staleness
    applies PER PATH — the ICI-path slice of every bucket is applied
    fresh, only the DCN-path slice is one step old (first update applies
    zeros on the DCN slices).  The stale buffer shrinks to the
    ``stripe_ratio`` fraction of a full stale tree, and the slow
    fabric's latency is hidden without giving up freshness on the fast
    path.
    """

    _double_buffering = True

    def __init__(self, actual_optimizer, communicator, zero_fill=True,
                 exchange="allreduce", db_mode=True):
        super().__init__(actual_optimizer, communicator, zero_fill,
                         exchange=exchange)
        super().__setattr__("_db_mode", db_mode)
