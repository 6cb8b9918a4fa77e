"""Mesh-backed communicator — the ``jax_ici`` backend.

Reference: the whole of ``chainermn/communicators/`` (SURVEY.md §2.1).
The reference's eight communicator classes solve GPU-cluster problems
(CUDA-aware MPI, host staging, node hierarchy, NCCL rings).  On TPU the
transport is one thing — XLA collectives over ICI/DCN — so the classification
collapses into *mesh-axis choice + gradient dtype choice* (SURVEY §2.7),
and the named variants (``naive``/``flat``/``hierarchical``/
``two_dimensional``/``single_node``/``non_cuda_aware``/``pure_nccl``)
are aliases of this class with their distinguishing knobs preserved:

* ``pure_nccl(allreduce_grad_dtype=float16)`` → ``grad_dtype=bfloat16``
  compressed gradient ``psum`` (N3 in SURVEY §2.5; bf16 is the TPU-native
  half type — fp16 is honored if explicitly requested).
* ``flat``'s single fused buffer → ``batch_collectives=True``: gradients
  are flattened into one contiguous bucket before the collective (N2;
  XLA usually fuses this anyway — measured, not assumed; see bench/).
* pure_nccl's size-bounded allreduce pipeline →
  ``batch_collectives="bucketed"``: gradients are packed into K
  size-bounded buckets (``CHAINERMN_TPU_BUCKET_MB`` / ``bucket_mb``,
  default ~4 MB) in reverse parameter-registration order, one ``pmean``
  per bucket — schedulable units XLA's async-collective scheduler can
  overlap with the remaining backward compute (the reference hid its
  NCCL allreduces behind backward the same way; see
  docs/performance.md §7 and tools/comm_budgets.json).
* ``hierarchical``/``two_dimensional`` → a REAL two-level ``(dcn, ici)``
  mesh axis split (ISSUE 6; no longer aliases of the flat path): the
  gradient exchange composes with the machine topology as intra-host
  ``reduce_scatter`` over ICI → inter-host exchange over DCN on the
  1/intra chunk → intra-host ``all_gather`` over ICI, so the slow DCN
  hop only ever carries ``1/ici_size`` of the gradient bytes.  The
  split is inferred from the controller topology (``process_count`` ×
  local devices), forced with ``intra_size=``/``inter_size=`` (the
  simulated-2-host tier-1 grid), or taken from two named axes of an
  existing mesh (:meth:`from_mesh_axis` with a 2-tuple).  Per-hop
  compression: ``allreduce_grad_dtype={"dcn": "bfloat16"}`` lowers DCN
  traffic while ICI stays lossless.  ``CHAINERMN_TPU_HIERARCHY=flat``
  is the escape hatch back to the one-axis alias behavior.

Two operating modes (see ``communicator_base`` docstring): eager host-mode
collectives on stacked arrays, and in-step ``lax`` collectives inside
``shard_map`` programs launched by :meth:`run_spmd`.
"""

from __future__ import annotations

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .communicator_base import CommunicatorBase

__all__ = ["MeshCommunicator", "ElasticMeshCommunicator"]


def _is_traced(*xs):
    return any(isinstance(leaf, jax.core.Tracer)
               for x in xs for leaf in jax.tree.leaves(x))


_warned_inert_ef = False


def _warn_inert_error_feedback():
    """A quantized transform was invoked through the legacy 1-arg form
    while the communicator asked for error feedback: quantization still
    happens, but the residual is DISCARDED — the exact EF-off mode the
    parity ablation shows drifting from the lossless trajectory.  Warn
    once per process (trace-time, so the hot path never pays): callers
    that cannot thread the residual should construct the communicator
    with error_feedback=False to make the ablation explicit."""
    global _warned_inert_ef
    if _warned_inert_ef:
        return
    _warned_inert_ef = True
    import warnings
    warnings.warn(
        "quantized grad_transform called without a residual while "
        "error_feedback=True: the quantization error is being discarded "
        "(error feedback is inert on this call path — e.g. the DCGAN "
        "updater's direct grad_transform use).  Pass "
        "error_feedback=False at communicator construction to make the "
        "ablation explicit, or use the multi-node optimizer, which "
        "threads the residual.", UserWarning, stacklevel=3)


class MeshCommunicator(CommunicatorBase):
    """Communicator over a 1-D device mesh axis.

    ``devices``: list of ``jax.Device`` (default: all).  ``axis_name``: the
    mesh axis this communicator's collectives run over.  For hybrid
    DP×MP (reference: ``CommunicatorBase.split`` + two communicators),
    construct one communicator per axis of a shared N-D mesh via
    :meth:`from_mesh_axis`.
    """

    def __init__(self, devices=None, axis_name="mn_world",
                 allreduce_grad_dtype=None, batch_collectives=False,
                 bucket_mb=None, name="jax_ici", _mesh=None,
                 intra_size=None, inter_size=None, error_feedback=True,
                 stripe_ratio=None):
        self.name = name
        self.hierarchy = None
        self._hier_sizes = None
        # knob PROVENANCE (ISSUE 19): which exchange knobs the caller
        # hand-set (explicit argument here; the env-read sites below OR
        # in their knobs).  The autotune planner only fills knobs left
        # free — hand knobs always win, and :meth:`retuned` carries
        # these flags onto clones and elastic rebuilds so a rebuilt
        # communicator remembers what was a human decision vs a derived
        # one (the elastic factory passes the OLD comm's knob values as
        # explicit arguments, which must not launder them into "hand").
        self._hand_knobs = {
            "bucket_mb": bucket_mb is not None,
            "stripe_ratio": stripe_ratio is not None,
            "grad_dtype": allreduce_grad_dtype is not None,
        }
        #: the agreed autotune plan this communicator runs under (None
        #: = hand-knobbed); attached by :meth:`retuned`
        self.autotune_plan = None
        self._autotune_mode = None
        want_hier = (name in ("hierarchical", "two_dimensional")
                     or intra_size is not None or inter_size is not None
                     or isinstance(axis_name, (tuple, list)))
        if isinstance(axis_name, (tuple, list)):
            names = tuple(axis_name)
            if len(names) != 2:
                raise ValueError(
                    f"a hierarchical axis_name is a (dcn, ici) 2-tuple; "
                    f"got {names!r}")
        elif want_hier:
            names = ("dcn", "ici")
        if _mesh is not None:
            self.mesh = _mesh
            self._devices = list(np.asarray(_mesh.devices).reshape(-1))
        else:
            self._devices = list(devices) if devices is not None else list(jax.devices())
            if want_hier:
                inter, intra = self._resolve_hierarchy(
                    len(self._devices), intra_size, inter_size)
                self.mesh = Mesh(np.asarray(self._devices)
                                 .reshape(inter, intra), names)
            else:
                self.mesh = Mesh(np.asarray(self._devices), (axis_name,))
        if want_hier:
            self.hierarchy = names
            self._hier_sizes = (int(self.mesh.shape[names[0]]),
                                int(self.mesh.shape[names[1]]))
            axis_name = names
        self.axis_name = axis_name
        # striped multi-path exchange (ISSUE 11): the DCN share of each
        # bucket's payload.  0 = the strict hierarchical schedule; the
        # env knob is read at CONSTRUCTION time (like bucket_mb) and
        # only where it can matter — a hierarchical mesh.  A flat
        # communicator has ONE fabric: an explicit ratio there is a
        # construction error, never a silent no-op.
        if stripe_ratio is None and want_hier:
            import os
            raw = os.environ.get("CHAINERMN_TPU_STRIPE_RATIO", "").strip()
            if raw:
                stripe_ratio = float(raw)
                self._hand_knobs["stripe_ratio"] = True
        if stripe_ratio is not None:
            stripe_ratio = float(stripe_ratio)
            if not 0.0 <= stripe_ratio <= 1.0:
                raise ValueError(
                    f"stripe_ratio must be in [0, 1], got {stripe_ratio}")
            if stripe_ratio > 0 and self.hierarchy is None:
                raise ValueError(
                    "stripe_ratio needs a hierarchical communicator "
                    "(name='hierarchical'/'two_dimensional' or an "
                    "intra_size/inter_size split): a flat mesh has one "
                    "fabric, there is nothing to stripe across")
        self.stripe_ratio = float(stripe_ratio or 0.0)
        self.dcn_grad_dtype = None
        self.error_feedback = bool(error_feedback)
        from ._memory_utility import is_quantized_dtype, resolve_grad_dtype
        if isinstance(allreduce_grad_dtype, dict):
            # per-hop compression (ISSUE 6): lossless ICI + compressed
            # DCN is the interesting point — the slow hop's bytes halve
            # while the fast hop keeps full precision
            if self.hierarchy is None:
                raise ValueError(
                    "per-hop allreduce_grad_dtype={'ici': ..., 'dcn': ...} "
                    "needs a hierarchical communicator "
                    "(name='hierarchical'/'two_dimensional' or an "
                    "intra_size/inter_size split)")
            unknown = set(allreduce_grad_dtype) - {"ici", "dcn"}
            if unknown:
                raise ValueError(
                    f"unknown per-hop dtype keys {sorted(unknown)} "
                    f"(hops are 'ici' and 'dcn')")
            ici_dt = allreduce_grad_dtype.get("ici")
            dcn_dt = allreduce_grad_dtype.get("dcn")
            if is_quantized_dtype(ici_dt):
                # the fast hop is lossless BY DESIGN (ISSUE 8): its
                # bytes are nearly free and a second quantization point
                # would need a second residual for no wire win
                raise ValueError(
                    f"quantized ici dtype {ici_dt!r}: the ICI hop is "
                    f"lossless by design — int8/fp8 compression is a "
                    f"slow-hop (dcn) knob")
            self.allreduce_grad_dtype = resolve_grad_dtype(ici_dt)
            self.dcn_grad_dtype = resolve_grad_dtype(dcn_dt)
        else:
            self.allreduce_grad_dtype = resolve_grad_dtype(
                allreduce_grad_dtype)
            if self.hierarchy is not None:
                self.dcn_grad_dtype = self.allreduce_grad_dtype
                if is_quantized_dtype(self.allreduce_grad_dtype):
                    # a scalar CAST dtype (bf16) compresses BOTH hops
                    # (flat-path parity), but a scalar QUANTIZED dtype
                    # compresses the DCN crossing only (ISSUE 8:
                    # lossless over ICI, compressed over DCN by
                    # default — int8 cannot ride a psum_scatter anyway)
                    self.allreduce_grad_dtype = None
        if self._compress_disabled():
            # CHAINERMN_TPU_COMPRESS=off — the factory-level escape
            # hatch (ISSUE 8): quantized wires fall back to LOSSLESS
            # (never to a silently different lossy dtype); plain cast
            # compression (bf16/fp16) is untouched — it predates the
            # quantized path and has its own knobs
            if is_quantized_dtype(self.allreduce_grad_dtype):
                self.allreduce_grad_dtype = None
            if is_quantized_dtype(self.dcn_grad_dtype):
                self.dcn_grad_dtype = None
        if batch_collectives not in (False, True, "bucketed"):
            raise ValueError(
                f"batch_collectives must be False (per-leaf collectives), "
                f"True (one flat bucket) or 'bucketed' (size-bounded "
                f"buckets); got {batch_collectives!r}")
        self.batch_collectives = batch_collectives
        # bucket bound for the "bucketed" exchange; the env knob is read
        # at CONSTRUCTION (not trace) time so every rank of a job traces
        # the same plan from the same communicator arguments.  Resolved
        # only when it can matter (explicit arg or bucketed exchange) —
        # a stray CHAINERMN_TPU_BUCKET_MB value must not break the
        # flavors that never plan buckets
        if bucket_mb is None and batch_collectives == "bucketed":
            import os
            from ._memory_utility import DEFAULT_BUCKET_MB
            raw = os.environ.get("CHAINERMN_TPU_BUCKET_MB")
            if raw:
                self._hand_knobs["bucket_mb"] = True
            bucket_mb = float(raw or DEFAULT_BUCKET_MB)
        if bucket_mb is not None:
            bucket_mb = float(bucket_mb)
            if bucket_mb <= 0:
                raise ValueError(
                    f"bucket_mb must be positive, got {bucket_mb}")
        self.bucket_mb = bucket_mb
        self._mailbox = {}
        self._obj_mailbox = {}
        self._lock = threading.Lock()
        self._jit_cache = {}
        # host topology (reference: init_ranks' hostname allgather at
        # communicator construction, SURVEY §2.1): with multiple
        # controller processes, intra_rank = this process's index among
        # the processes on the same host.  NOTE: under process_count > 1
        # communicator construction is a COLLECTIVE point — every process
        # must construct communicators (including from_mesh_axis /
        # split_all sub-communicators) in the same order, or peers block
        # in this allgather until the KV channel's timeout_ms expires
        # (the channel bounds every get/barrier, so a one-sided failure
        # surfaces as a timeout error on the peers, not a silent hang).
        # The except below only rescues THIS process (e.g. no object
        # channel at all); it cannot unblock peers already inside the
        # collective — they recover via the same timeout.
        self._intra = None
        if jax.process_count() > 1:
            try:
                import socket
                me = (socket.gethostname(), jax.process_index())
                peers = self._process_allgather_pickled(me)
                same = sorted(pi for host, pi in peers if host == me[0])
                self._intra = (same.index(me[1]), len(same))
            except Exception:
                self._intra = None  # no object channel: single-host default
        # observability (ISSUE 14): stamp the rank (and, on elastic
        # incarnations, the membership epoch) into the span tracer so
        # every subsequent event is rank/epoch-tagged — the merge tool
        # keys rank lanes off this.  No-op when tracing is off.
        from .. import observability
        if observability.ring_enabled():
            observability.tracer().configure(
                rank=self.rank, epoch=getattr(self, "epoch", None))

    def __deepcopy__(self, memo):
        # communicators are process-global transport handles (mesh, device
        # list, mailboxes) — model deepcopies (create_mnbn_model) share them
        return self

    @staticmethod
    def _compress_disabled():
        import os
        return os.environ.get("CHAINERMN_TPU_COMPRESS", "") \
            .strip().lower() in ("off", "0", "none")

    @staticmethod
    def _resolve_hierarchy(n_devices, intra_size, inter_size):
        """``(inter, intra)`` of the two-level split: explicit sizes win
        (the simulated-multihost knob); otherwise the controller
        topology decides — one DCN group per controller process, ICI =
        the devices each drives.  Validated so a bad split fails at
        construction, not as a reshape error inside the first traced
        step."""
        if intra_size is not None and inter_size is not None:
            if intra_size * inter_size != n_devices:
                raise ValueError(
                    f"intra_size({intra_size}) × inter_size({inter_size})"
                    f" != device count {n_devices}")
            return int(inter_size), int(intra_size)
        if inter_size is not None:
            if inter_size < 1 or n_devices % inter_size:
                raise ValueError(
                    f"inter_size={inter_size} does not divide the "
                    f"device count {n_devices}")
            return int(inter_size), n_devices // int(inter_size)
        if intra_size is not None:
            if intra_size < 1 or n_devices % intra_size:
                raise ValueError(
                    f"intra_size={intra_size} does not divide the "
                    f"device count {n_devices}")
            return n_devices // int(intra_size), int(intra_size)
        inter = jax.process_count()
        if n_devices % inter:
            # ragged host layouts (devices= subsets) have no canonical
            # split; require the explicit knob rather than guessing
            raise ValueError(
                f"cannot infer a (dcn, ici) split: {n_devices} devices "
                f"over {inter} processes; pass intra_size=/inter_size=")
        return inter, n_devices // inter

    @classmethod
    def from_mesh_axis(cls, mesh: Mesh, axis_name, **kwargs):
        """Communicator over one named axis of an existing N-D mesh —
        or, with a ``(dcn, ici)`` 2-tuple of axis names, a HIERARCHICAL
        communicator over that two-level sub-topology (the ISSUE 6
        construction path for meshes that already carry the split)."""
        if isinstance(axis_name, (tuple, list)):
            dcn, ici = tuple(axis_name)
            sub = np.moveaxis(
                mesh.devices,
                (mesh.axis_names.index(dcn), mesh.axis_names.index(ici)),
                (0, 1))
            grid = sub.reshape(sub.shape[0], sub.shape[1], -1)[:, :, 0]
            comm = cls(devices=list(grid.reshape(-1)),
                       axis_name=(dcn, ici),
                       inter_size=int(grid.shape[0]),
                       intra_size=int(grid.shape[1]), **kwargs)
            comm.mesh = mesh  # collectives address the enclosing mesh's axes
            return comm
        sub = np.moveaxis(mesh.devices,
                          mesh.axis_names.index(axis_name), 0)
        comm = cls(devices=list(sub.reshape(sub.shape[0], -1)[:, 0]),
                   axis_name=axis_name, **kwargs)
        comm.mesh = mesh  # collectives run inside programs over the full mesh
        return comm

    # -- topology ------------------------------------------------------------
    @property
    def rank(self):
        return jax.process_index()

    @property
    def size(self):
        return len(self._devices)

    @property
    def intra_rank(self):
        """First device slot this controller drives on its host, in
        DEVICE-SLOT units — the same units as ``intra_size``, so the
        reference idiom ``intra_rank in range(0, intra_size)`` and
        slot arithmetic hold on every host layout.  0 for the common
        single-controller-per-host layout; ``local_proc_idx ×
        local_device_count`` when several controller processes share a
        host."""
        local_proc_idx = self._intra[0] if self._intra is not None else 0
        return local_proc_idx * jax.local_device_count()

    @property
    def intra_size(self):
        """Device slots this host contributes (DEVICE-SLOT units, like
        ``intra_rank``): local device count × co-located controller
        processes (reference: ranks per node).  On a hierarchical
        communicator this is the ICI axis size — the mesh's own view of
        "ranks per node", which equals the controller-derived figure on
        a real multihost run and stays correct under the simulated
        splits (``inter_size=`` on one controller)."""
        if self.hierarchy is not None:
            return self._hier_sizes[1]
        n_local_procs = self._intra[1] if self._intra is not None else 1
        return jax.local_device_count() * n_local_procs

    @property
    def inter_rank(self):
        return jax.process_index()

    @property
    def inter_size(self):
        """Number of controller PROCESSES — the host/object-channel view
        (scatter_dataset, checkpoint consensus, multi-node iterators key
        off this).  The device-mesh view of the two-level split lives on
        ``dcn_size``/``ici_size``; the two coincide on a real multihost
        run and deliberately differ under a single-controller simulated
        split (one controller still feeds the whole global batch)."""
        return jax.process_count()

    # -- two-level (ici × dcn) topology (ISSUE 6) --------------------------
    @property
    def dcn_axis(self):
        """Slow-hop mesh axis name (``None`` on flat communicators)."""
        return self.hierarchy[0] if self.hierarchy is not None else None

    @property
    def ici_axis(self):
        """Fast-hop mesh axis name (``None`` on flat communicators)."""
        return self.hierarchy[1] if self.hierarchy is not None else None

    @property
    def dcn_size(self):
        """Groups on the slow hop (1 on flat communicators)."""
        return self._hier_sizes[0] if self.hierarchy is not None else 1

    @property
    def ici_size(self):
        """Devices per slow-hop group (== ``size`` on flat
        communicators: the whole world is one fast-hop group)."""
        return self._hier_sizes[1] if self.hierarchy is not None \
            else self.size

    def chunk_axes(self):
        """Axis names of the gradient reduce-scatter chain, FAST hop
        first — the full buffer crosses the cheap wire, the slow hop
        only ever sees the 1/ici chunk.  ``(axis,)`` on flat
        communicators; ``(ici, dcn)`` on hierarchical ones.  The
        optimizer's sharded update chains ``psum_scatter`` in this
        order and ``all_gather`` in reverse."""
        if self.hierarchy is not None:
            return (self.ici_axis, self.dcn_axis)
        return (self.axis_name,)

    def flat_chunk_spec(self):
        """``PartitionSpec`` of a flat padded vector sharded one chunk
        per rank in the layout the chained reduce-scatter of
        :meth:`chunk_axes` produces (fast hop major) — what the sharded
        optimizer state and the reduce-scatter stale buffer use."""
        if self.hierarchy is not None:
            return P((self.ici_axis, self.dcn_axis))
        return P(self.axis_name)

    def striped_chunk_specs(self):
        """``(fast_major, slow_major)`` pair of chunk specs for the
        STRIPED sharded update (ISSUE 11): the ICI-path slice's chained
        reduce-scatter lands chunks fast-hop-major (== the
        :meth:`flat_chunk_spec` layout) while the DCN-path slice's
        transposed chain lands them slow-hop-major — the two flat
        state vectors of the striped ZeRO layout each carry their own
        spec."""
        if self.hierarchy is None:
            raise ValueError("striped chunk specs need a hierarchical "
                             "communicator")
        return (P((self.ici_axis, self.dcn_axis)),
                P((self.dcn_axis, self.ici_axis)))

    # -- mode dispatch ---------------------------------------------------------
    def _axis_index(self):
        return lax.axis_index(self.axis_name)

    # -- ndarray collectives ----------------------------------------------------
    def allreduce(self, data, op="sum"):
        """Traced: ``lax`` reduction over the axis.  Eager: reduce the
        stacked leading axis and return the (identical-on-all-ranks) value."""
        if _is_traced(data):
            if op == "sum":
                return lax.psum(data, self.axis_name)
            if op == "mean":
                return lax.pmean(data, self.axis_name)
            if op == "max":
                return lax.pmax(data, self.axis_name)
            if op == "min":
                return lax.pmin(data, self.axis_name)
            raise ValueError(f"unsupported op {op!r}")
        data = jnp.asarray(data)
        self._check_stacked(data, "allreduce")
        red = {"sum": jnp.sum, "mean": jnp.mean,
               "max": jnp.max, "min": jnp.min}[op]
        return red(data, axis=0)

    def multi_node_mean(self, data):
        """Reference ``CommunicatorBase.multi_node_mean``: allreduce ÷ size."""
        return self.allreduce(data, op="mean")

    def allgather(self, x):
        """Traced: ``lax.all_gather`` → leading ``size`` axis.  Eager: the
        stacked input *is* the gathered result; returned as a tuple for
        reference-shape parity."""
        if _is_traced(x):
            return lax.all_gather(x, self.axis_name)
        x = jnp.asarray(x)
        self._check_stacked(x, "allgather")
        return tuple(x[i] for i in range(self.size))

    def alltoall(self, xs):
        """Traced: ``lax.all_to_all`` on the leading (destination) axis.
        Eager: input [src, dst, ...] → output [dst, src, ...]."""
        if _is_traced(xs):
            if isinstance(xs, (tuple, list)):
                xs = jnp.stack(list(xs))
            return lax.all_to_all(xs, self.axis_name,
                                  split_axis=0, concat_axis=0, tiled=False)
        if isinstance(xs, (tuple, list)):
            xs = jnp.stack([jnp.stack(list(row)) for row in xs]) \
                if isinstance(xs[0], (tuple, list)) else jnp.stack(list(xs))
        self._check_stacked(xs, "alltoall")
        if xs.ndim < 2 or xs.shape[1] != self.size:
            raise ValueError(
                "eager alltoall expects [src, dst, ...] stacked input")
        return jnp.swapaxes(xs, 0, 1)

    def bcast(self, data, root=0):
        """Traced: every rank gets rank ``root``'s value.  Eager: stacked
        input → the root slice."""
        if _is_traced(data):
            masked = jnp.where(self._axis_index() == root, data,
                               jnp.zeros_like(data))
            return lax.psum(masked, self.axis_name)
        data = jnp.asarray(data)
        self._check_stacked(data, "bcast")
        return data[root]

    def gather(self, data, root=0):
        """Traced: ``all_gather`` (SPMD has no root asymmetry inside a
        compiled program).  Eager: tuple of per-rank slices."""
        if _is_traced(data):
            return lax.all_gather(data, self.axis_name)
        data = jnp.asarray(data)
        self._check_stacked(data, "gather")
        return tuple(data[i] for i in range(self.size))

    def scatter(self, xs, root=0):
        """Traced: rank ``root``'s stacked [size, ...] value, own slice out.
        Eager: identity on the stacked representation."""
        if isinstance(xs, (tuple, list)):
            xs = jnp.stack(list(xs))
        if _is_traced(xs):
            from_root = self.bcast(xs, root)
            return jnp.take(from_root, self._axis_index(), axis=0)
        self._check_stacked(xs, "scatter")
        return xs

    # -- point-to-point -----------------------------------------------------------
    def send(self, data, dest, tag=0, source=None):
        """Eager host-mode send.  Traced point-to-point lives in
        ``chainermn_tpu.functions`` (ppermute with static src/dst).

        Same controller: mailbox append.  Other controller process:
        pickled ndarray over the coordination KV channel.  ``source`` is
        optional sender attribution for MPI-style matched receives — the
        single controller acts for many ranks, so identity must be
        declared, not inferred; undeclared sends match any ``recv``.
        """
        if _is_traced(data):
            raise RuntimeError(
                "inside compiled steps use chainermn_tpu.functions.send "
                "(ppermute); Communicator.send is the host-mode channel")
        if dest != self.rank:
            ch = self._host_channel()
            if ch is not None:
                # attribution travels with the payload; cross-process
                # matching is already exact by (process, tag, seq)
                ch.send_obj((source, np.asarray(data)), dest,
                            tag=f"nd{tag}")
                return
        with self._lock:
            self._mailbox.setdefault((dest, tag), []).append(
                (source, jnp.asarray(data)))

    def recv(self, source, tag=0):
        """Matched receive: only messages sent with this ``source``
        attribution (or sent without one) are delivered — two pending
        senders with declared sources can no longer cross wires
        (MPI source-matching semantics)."""
        if source != self.rank:
            ch = self._host_channel()
            if ch is not None:
                _attr, data = ch.recv_obj(source, tag=f"nd{tag}")
                return jnp.asarray(data)
        with self._lock:
            for key in list(self._mailbox):
                if key[1] != tag:
                    continue
                box = self._mailbox[key]
                for i, (src, _) in enumerate(box):
                    if src is None or source is None or src == source:
                        return box.pop(i)[1]
        raise RuntimeError(
            f"recv with no matching message (host mode, source={source}, "
            f"tag={tag})")

    # -- object channel ---------------------------------------------------------
    # Same-controller: loopback mailbox (the controller holds the one copy).
    # Cross-process: chunked pickled transport over the jax.distributed
    # coordination KV store (reference: pickled MPI channel, SURVEY §2.7;
    # see ``_host_channel.HostChannel``).  In single-controller SPMD the
    # host-object unit is the controller process, so ``dest``/``source``
    # here are controller ranks (== ``inter_rank``/``jax.process_index()``).
    def _host_channel(self):
        from ._host_channel import get_host_channel
        return get_host_channel()

    def send_obj(self, obj, dest, tag=0):
        if dest != self.rank:
            ch = self._host_channel()
            if ch is not None:
                ch.send_obj(obj, dest, tag)
                return
        with self._lock:
            self._obj_mailbox.setdefault((dest, tag), []).append(obj)

    def recv_obj(self, source, tag=0):
        if source != self.rank:
            ch = self._host_channel()
            if ch is not None:
                return ch.recv_obj(source, tag)
        with self._lock:
            for key in list(self._obj_mailbox):
                if key[1] == tag and self._obj_mailbox[key]:
                    return self._obj_mailbox[key].pop(0)
        raise RuntimeError("recv_obj with empty mailbox (host mode)")

    def bcast_obj(self, obj, root=0):
        # root is a CONTROLLER rank (inter_rank) in every mode — the
        # single-controller collapse validates identically so a root that
        # would be rejected at scale fails in development too
        root = self._owning_process(root)
        if self.inter_size > 1:
            ch = self._host_channel()
            if ch is not None:
                return ch.bcast(obj, root=root)
            gathered = self._process_allgather_pickled(obj)
            return gathered[root]
        return obj

    def _owning_process(self, root):
        """Validate an object-channel root as a controller rank.

        Host-mode object ops consistently address CONTROLLER processes
        (``inter_rank`` — see ``_MultiNodeIterator._is_master``,
        ``scatter_dataset``).  A mis-addressed root raises instead of
        silently re-rooting to 0 (every process computes the same root
        from the same arguments, so the error is raised symmetrically —
        no one-sided collective hang)."""
        if not 0 <= root < self.inter_size:
            raise ValueError(
                f"object-channel root {root} out of range for "
                f"{self.inter_size} controller processes")
        return root

    def gather_obj(self, obj, root=0):
        return self.allgather_obj(obj)

    def allgather_obj(self, obj):
        """One entry per *rank* (device), independent of host layout.

        Each controlling process contributes one object on behalf of each
        device it drives (single-controller SPMD: all local ranks hold the
        same host-side object), so reductions over the result scale with
        ``size`` identically on 1×8 and 2×4 host layouts.
        """
        if self.inter_size > 1:
            per_process = self._process_allgather_pickled(obj)
            out = []
            local_counts = self._local_device_counts()
            for host_obj, count in zip(per_process, local_counts):
                out.extend([host_obj] * count)
            return out
        return [obj] * self.size

    def _local_device_counts(self):
        counts = [0] * jax.process_count()
        for d in self._devices:
            counts[getattr(d, "process_index", 0)] += 1
        return counts

    def _process_allgather_pickled(self, obj):
        """Allgather arbitrary Python objects across processes.

        Primary path: the coordination-service KV channel (host data never
        enters XLA — the reference's object channel was likewise pure MPI,
        SURVEY §2.7).  Fallback (no coordination service, e.g. some
        multi-host TPU runtimes bootstrapped externally): length-padded
        pickled byte arrays over ``multihost_utils.process_allgather``.
        """
        ch = self._host_channel()
        if ch is not None:
            return ch.allgather(obj)
        import pickle
        from jax.experimental import multihost_utils
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        length = np.asarray([payload.size], dtype=np.int64)
        all_lengths = np.asarray(
            multihost_utils.process_allgather(length)).reshape(-1)
        max_len = int(all_lengths.max())
        padded = np.zeros(max_len, dtype=np.uint8)
        padded[: payload.size] = payload
        gathered = np.asarray(multihost_utils.process_allgather(padded))
        gathered = gathered.reshape(len(all_lengths), max_len)
        return [pickle.loads(gathered[i, : int(all_lengths[i])].tobytes())
                for i in range(len(all_lengths))]

    def allreduce_obj(self, obj):
        gathered = self.allgather_obj(obj)
        out = gathered[0]
        for other in gathered[1:]:
            out = jax.tree.map(lambda a, b: a + b, out, other)
        return out

    # -- model ops ------------------------------------------------------------------
    def bcast_data(self, model):
        """Make parameters explicitly replicated over the communicator mesh.

        In single-controller JAX, replication is a *sharding property*, not
        a message: this places every param/persistent array with a
        replicated ``NamedSharding`` so later sharded programs consume them
        without re-layout.  Multi-host agreement is handled by the runtime
        (same bytes on every host by construction of the program).
        """
        sharding = NamedSharding(self.mesh, P())
        for param in model.params():
            if param.array is not None:
                param.array = jax.device_put(param.array, sharding)
        from ..core.link import _persistent_slots
        for sublink, name, _ in _persistent_slots(model):
            value = getattr(sublink, name)
            if value is not None and not np.isscalar(value) \
                    and not isinstance(value, (int, float)):
                placed = jax.device_put(jnp.asarray(value), sharding)
                object.__setattr__(sublink, name, placed)
                sublink._persistent[name] = placed
        return model

    def multi_node_mean_grad(self, model, zero_fill=False):
        """Average per-rank gradients stored on the model (eager path).

        Grad layout contract (single-controller translation of "each rank
        holds its own grads"): a stacked gradient with leading axis ``size``
        (``grad.shape == (size,) + param.shape``) is averaged over that
        axis; an unstacked gradient is already global and is left as-is
        (÷1).  The *compiled* path — the one benchmarks use — is the
        ``grad_transform`` this communicator hands to the multi-node
        optimizer, where the same mean runs as an in-step ``pmean``.
        """
        named = [(path, p) for path, p in model.namedparams()
                 if p.array is not None]
        grads = {}
        for path, p in named:
            if p.grad is None:
                if zero_fill:
                    grads[path] = jnp.zeros((self.size,) + p.array.shape,
                                            p.array.dtype)
                else:
                    continue
            else:
                grads[path] = p.grad
        if not grads:
            return
        reduced = self._mean_grads_eager(grads, {path: p.array.shape
                                                 for path, p in named})
        for path, p in named:
            if path in reduced:
                p.grad = reduced[path]

    def _mean_grads_eager(self, grads, shapes):
        key = tuple(sorted((path, g.shape, str(g.dtype))
                           for path, g in grads.items()))
        fn = self._jit_cache.get(("mean_eager", key))
        if fn is None:
            size = self.size
            from ._memory_utility import is_quantized_dtype
            # quantization is a WIRE property (scale+codebook, not a
            # cast): the eager host-mode mean stays lossless
            dtype = None if is_quantized_dtype(self.allreduce_grad_dtype) \
                else self.allreduce_grad_dtype
            stacked = {path: (g.ndim == len(shapes[path]) + 1
                              and g.shape[0] == size
                              and tuple(g.shape[1:]) == tuple(shapes[path]))
                       for path, g in grads.items()}

            @jax.jit
            def fn(grads):
                out = {}
                for path, g in grads.items():
                    orig = g.dtype
                    if dtype is not None:
                        g = g.astype(dtype)
                    if stacked[path]:
                        g = jnp.mean(g, axis=0)
                    out[path] = g.astype(orig)
                return out

            self._jit_cache[("mean_eager", key)] = fn
        return fn(grads)

    # -- in-step gradient transform (the hot path) ---------------------------------
    @property
    def exchange(self):
        """Canonical name of this communicator's gradient-exchange
        structure: ``"per_leaf"`` | ``"flat"`` | ``"bucketed"`` (the
        vocabulary tools/comm_budgets.json and bench rows use)."""
        if self.batch_collectives == "bucketed":
            return "bucketed"
        return "flat" if self.batch_collectives else "per_leaf"

    @property
    def topology(self):
        """``"striped"`` (multi-path ici ∥ dcn exchange, ISSUE 11),
        ``"hierarchical"`` (strict two-level ici × dcn exchange) or
        ``"flat"`` (one mesh axis) — the topology column bench rows and
        the census carry, orthogonal to :attr:`exchange` (bucketing
        composes with any topology)."""
        if self.hierarchy is None:
            return "flat"
        return "striped" if self.striped else "hierarchical"

    @property
    def striped(self):
        """True when the gradient exchange stripes each bucket across
        BOTH fabrics concurrently (ISSUE 11): a hierarchical mesh with
        a nonzero :attr:`stripe_ratio`.  Ratio 0 is the strict
        hierarchical schedule — the degenerate collapse
        ``stripe_plan`` pins."""
        return self.hierarchy is not None and self.stripe_ratio > 0

    # -- self-tuning (ISSUE 19) --------------------------------------------
    def _clone_kwargs(self):
        """Constructor kwargs that rebuild THIS communicator (same
        devices, topology, knobs) — the base of :meth:`retuned`'s
        knob-override clone.  Subclasses extend (the elastic variant
        adds members/epoch/channel)."""
        kwargs = dict(devices=list(self._devices),
                      axis_name=self.axis_name,
                      batch_collectives=self.batch_collectives,
                      bucket_mb=self.bucket_mb,
                      name=self.name,
                      error_feedback=self.error_feedback)
        if self.hierarchy is not None:
            kwargs["axis_name"] = self.hierarchy
            kwargs["inter_size"], kwargs["intra_size"] = self._hier_sizes
            if self.allreduce_grad_dtype is not None \
                    or self.dcn_grad_dtype is not None:
                kwargs["allreduce_grad_dtype"] = {
                    "ici": self.allreduce_grad_dtype,
                    "dcn": self.dcn_grad_dtype}
            if self.stripe_ratio > 0:
                kwargs["stripe_ratio"] = self.stripe_ratio
        else:
            kwargs["allreduce_grad_dtype"] = self.allreduce_grad_dtype
        return kwargs

    def retuned(self, plan):
        """Apply an agreed autotune plan: a clone with the plan's knobs
        filled into every knob the caller did NOT hand-set (explicit
        argument or env var — the provenance ``_hand_knobs`` records at
        construction); hand knobs always win.  Returns ``self`` with
        the plan attached when nothing the plan proposes differs from
        the current knobs — the golden-trajectory contract: a plan that
        matches the hand knobs changes no compiled program.

        Collective when it rebuilds (communicator construction is a
        collective point) — safe because the plan itself is agreed
        (bcast from rank 0), so every rank takes the same branch.
        """
        hand = getattr(self, "_hand_knobs", {})
        kwargs = self._clone_kwargs()
        changed = False
        if plan.get("bucket_mb") is not None \
                and not hand.get("bucket_mb") \
                and self.batch_collectives == "bucketed":
            bucket = float(plan["bucket_mb"])
            if bucket != self.bucket_mb:
                kwargs["bucket_mb"] = bucket
                changed = True
        if plan.get("stripe_ratio") is not None \
                and not hand.get("stripe_ratio") \
                and self.hierarchy is not None:
            ratio = float(plan["stripe_ratio"])
            if ratio != self.stripe_ratio:
                kwargs["stripe_ratio"] = ratio
                changed = True
        if plan.get("grad_dtype") is not None \
                and not hand.get("grad_dtype") \
                and self.hierarchy is not None:
            from ._memory_utility import resolve_grad_dtype
            want = {hop: resolve_grad_dtype(dt)
                    for hop, dt in plan["grad_dtype"].items()}
            have = {"ici": self.allreduce_grad_dtype,
                    "dcn": self.dcn_grad_dtype}
            if want != have:
                kwargs["allreduce_grad_dtype"] = dict(plan["grad_dtype"])
                changed = True
        if not changed:
            self.autotune_plan = plan
            return self
        clone = type(self)(**kwargs)
        # provenance and plan CARRY FORWARD: the clone's constructor saw
        # explicit arguments (the applied plan values), which must not
        # read as hand-set on the next re-tune (elastic resizes re-tune
        # through the same path)
        clone._hand_knobs = dict(hand)
        clone._autotune_mode = self._autotune_mode
        clone.autotune_plan = plan
        return clone

    # -- quantized wire (ISSUE 8) ------------------------------------------
    @property
    def quantized(self):
        """True when any hop's wire dtype is a quantized (int8/fp8)
        codebook — the exchanges that carry a per-bucket symmetric
        scale and (with :attr:`error_feedback`) a residual buffer."""
        from ._memory_utility import is_quantized_dtype
        return (is_quantized_dtype(self.allreduce_grad_dtype)
                or is_quantized_dtype(self.dcn_grad_dtype))

    @property
    def quantized_wire_dtype(self):
        """The quantized wire dtype (the slow hop's on hierarchical
        communicators, the world wire on flat ones), or ``None``."""
        from ._memory_utility import is_quantized_dtype
        if self.hierarchy is not None:
            return self.dcn_grad_dtype \
                if is_quantized_dtype(self.dcn_grad_dtype) else None
        return self.allreduce_grad_dtype \
            if is_quantized_dtype(self.allreduce_grad_dtype) else None

    def grad_residual_len(self, shapes, dtypes):
        """LOCAL (per-device) length of the error-feedback residual the
        quantized ``grad_transform`` threads: per bucket, the quantized
        hop's per-device payload — the padded ``1/ici`` chunk on
        hierarchical communicators, the full bucket on flat ones —
        concatenated in plan order.  0 when the wire is not quantized.
        The global residual operand is this × ``size``, sharded by
        :meth:`flat_chunk_spec` (each device owns its slice — the same
        layout, donation, and resume plumbing as the reduce-scatter
        stale chunk)."""
        if self.quantized_wire_dtype is None:
            return 0
        total = 0
        from ._memory_utility import stripe_plan
        for idx in self.grad_buckets(shapes, dtypes):
            elems = sum(int(np.prod(shapes[i])) for i in idx)
            if self.striped:
                # per bucket: the DCN-path slice quantizes the full
                # pre-reduction slice per device, the ICI-path slice
                # quantizes its padded 1/ici chunk (layout: B then A —
                # the schedule's consumption order)
                n_i, n_d = stripe_plan(elems, self.stripe_ratio)
                total += n_d + (-(-n_i // self.ici_size) if n_i else 0)
            elif self.hierarchy is not None:
                intra = self.ici_size
                total += -(-elems // intra)
            else:
                total += elems
        return total

    def grad_residual_len_for(self, model):
        """:meth:`grad_residual_len` over ``model``'s gradient leaves,
        planned exactly like :meth:`grad_buckets_for` (post
        cast-compression, pre quantization) — the one length the hot
        path, the optimizer's zero-seed, and the resume template must
        agree on."""
        from ._memory_utility import is_quantized_dtype
        shapes, dtypes = self.grad_leaf_specs(model)
        if self.allreduce_grad_dtype is not None \
                and not is_quantized_dtype(self.allreduce_grad_dtype):
            dtypes = [self.allreduce_grad_dtype] * len(dtypes)
        return self.grad_residual_len(shapes, dtypes)

    def grad_dcn_stale_len_for(self, model):
        """Length of the DCN-slice-only stale buffer the
        ``double_buffering="dcn"`` variant threads (ISSUE 11): the
        DCN-path slice elements of every bucket, concatenated in plan
        order — the slow path's one-step-stale footprint, a
        ``stripe_ratio`` fraction of a full stale buffer.  0 on
        non-striped communicators."""
        if not self.striped:
            return 0
        from ._memory_utility import is_quantized_dtype, stripe_plan
        shapes, dtypes = self.grad_leaf_specs(model)
        if self.allreduce_grad_dtype is not None \
                and not is_quantized_dtype(self.allreduce_grad_dtype):
            dtypes = [self.allreduce_grad_dtype] * len(dtypes)
        total = 0
        for idx in self.grad_buckets(shapes, dtypes):
            elems = sum(int(np.prod(shapes[i])) for i in idx)
            total += stripe_plan(elems, self.stripe_ratio)[1]
        return total

    def grad_buckets(self, shapes, dtypes):
        """The bucket plan this communicator's ``grad_transform`` traces
        for leaves of the given shapes/dtypes (post dtype-compression):
        list of index lists in emission order.  Exposed so probes/tests
        census the SAME plan the hot path uses."""
        from ._memory_utility import plan_buckets
        if self.exchange == "per_leaf":
            return [[i] for i in reversed(range(len(shapes)))]
        if self.exchange == "flat":
            return [list(reversed(range(len(shapes))))] if shapes else []
        return plan_buckets(shapes, dtypes,
                            int(self.bucket_mb * 2 ** 20))

    @staticmethod
    def grad_leaf_specs(model):
        """``(shapes, dtypes)`` of ``model``'s params in the order
        ``grad_transform`` plans over: the params-tree FLATTEN order
        (sorted dict keys), NOT ``Link.params()`` registration order —
        the two orders yield different plans, so every bucket census
        must extract leaves through this one helper."""
        from ..core.link import extract_state
        leaves = jax.tree.leaves(extract_state(model)["params"])
        return [p.shape for p in leaves], [p.dtype for p in leaves]

    def grad_buckets_for(self, model):
        """The bucket plan ``grad_transform`` traces for ``model``'s
        gradients (leaves in hot-path order, post dtype-compression).
        A QUANTIZED wire dtype does not recast the leaves — quantization
        happens at the wire, so buckets are planned (and bounded) in the
        gradient's own dtype."""
        from ._memory_utility import is_quantized_dtype
        shapes, dtypes = self.grad_leaf_specs(model)
        if self.allreduce_grad_dtype is not None \
                and not is_quantized_dtype(self.allreduce_grad_dtype):
            dtypes = [self.allreduce_grad_dtype] * len(dtypes)
        return self.grad_buckets(shapes, dtypes)

    @property
    def grad_exchange_on_wire(self):
        """False when :meth:`grad_transform` exchanges nothing: the
        plain (one-axis, non-quantized) transform over an axis of ONE
        device, where the mean over ranks is the value itself.  The
        transform then emits no pack, collective or unpack, and the
        exchange telemetry announces no bucket.  Quantized wires stay
        on (quantization is lossy, so it is part of the result even at
        size 1), as do the hierarchical and striped transforms."""
        from ._memory_utility import is_quantized_dtype
        return not (self.size == 1 and self.hierarchy is None
                    and not is_quantized_dtype(self.allreduce_grad_dtype))

    def grad_transform(self):
        """Return ``grads -> grads`` for use inside a compiled train step.

        Implements the reference's ``allreduce_grad`` data path (SURVEY
        §3.2): optional cast to the compressed dtype (N3), mean-``psum``
        over the communicator axis, cast back.  The collective structure
        follows ``batch_collectives``:

        * ``False`` — one ``pmean`` per leaf (the ``naive`` flavor).
        * ``True`` — gradients flatten into ONE contiguous bucket (the
          ``flat`` flavor, N2): one large transfer, but it cannot start
          until the LAST gradient exists and the update waits for the
          whole round trip.
        * ``"bucketed"`` — K size-bounded buckets (``bucket_mb``) in
          reverse parameter-registration order: the reference pure_nccl
          pipeline's schedulable units.  Early buckets' collectives
          cover late backward compute under XLA's async scheduler, and
          the update of late-registered params can begin before early
          buckets land.

        All three produce bitwise-identical results (``pmean`` is
        elementwise — packing changes the schedule, not the math;
        golden-pinned by tests/core_tests/test_exchange_equivalence.py).
        Packing goes through ``_memory_utility.tree_pack``/``tree_unpack``
        — the one pack/unpack implementation (shared with ZeRO and the
        reduce-scatter update).

        Over an axis of ONE device (:attr:`grad_exchange_on_wire` is
        False) the mean is the value itself: none of the three packs or
        exchanges anything, only the dtype cast (part of the result)
        remains — bitwise the packed round trip
        (tests/communicator_tests/test_one_device_exchange.py).

        QUANTIZED wires (ISSUE 8): with an int8/fp8
        ``allreduce_grad_dtype`` the returned transform accepts an
        optional ``residual`` second argument (the error-feedback
        buffer) and, when given one, returns ``(grads, new_residual)``
        instead of bare grads — the multi-node optimizer threads it;
        legacy 1-arg callers get inline quantization with the residual
        discarded (error feedback off for that call).
        """
        if self.striped:
            return self._striped_grad_transform()
        if self.hierarchy is not None:
            return self._hierarchical_grad_transform()
        from ._memory_utility import is_quantized_dtype
        if is_quantized_dtype(self.allreduce_grad_dtype):
            return self._quantized_flat_grad_transform()
        axis = self.axis_name
        dtype = self.allreduce_grad_dtype
        comm = self

        def transform(grads):
            from ._memory_utility import tree_pack, tree_unpack
            leaves, treedef = jax.tree.flatten(grads)
            if not leaves:
                return grads
            orig_dtypes = [g.dtype for g in leaves]
            if dtype is not None:
                leaves = [g.astype(dtype) for g in leaves]
            if not comm.grad_exchange_on_wire:
                # one device on the axis: the mean over ranks is the
                # value itself, so nothing is packed, exchanged or
                # unpacked, whatever batch_collectives says.  The cast
                # above and back below is part of the result and stays
                leaves = [g.astype(d) for g, d in zip(leaves, orig_dtypes)]
                return jax.tree.unflatten(treedef, leaves)
            buckets = comm.grad_buckets([g.shape for g in leaves],
                                        [g.dtype for g in leaves])
            out = [None] * len(leaves)
            for k, idx in enumerate(buckets):
                if len(idx) == 1:
                    # single-leaf bucket: skip the pack/unpack reshape
                    # noise (identical math, cleaner program)
                    with jax.named_scope("mn_leaf_pmean"):
                        out[idx[0]] = lax.pmean(leaves[idx[0]], axis)
                    continue
                with jax.named_scope("mn_bucket_pmean"):
                    flat, spec = tree_pack([leaves[i] for i in idx])
                    flat = lax.pmean(flat, axis)
                    for i, g in zip(idx, tree_unpack(flat, spec)):
                        out[i] = g
            leaves = [g.astype(d) for g, d in zip(out, orig_dtypes)]
            return jax.tree.unflatten(treedef, leaves)

        return transform

    def _quantized_flat_grad_transform(self):
        """The quantized one-hop exchange (ISSUE 8; also what the
        ``CHAINERMN_TPU_HIERARCHY=flat`` escape hatch collapses a
        quantized-DCN hierarchical communicator onto): per bucket,
        quantize ``v = grads (+ residual)`` with a per-bucket symmetric
        scale, ``all_gather`` the quantized payload + the scale scalar
        over the axis, and dequantize-sum — each rank reconstructs the
        mean from every rank's ``(q, scale)`` pair, so the wire carries
        the quantized fraction of the bytes while the accumulation
        stays f32 (an int8 ``psum`` would overflow at size 2, and ranks
        quantize with DIFFERENT scales — summing codewords is
        meaningless; DynamiQ's gather-then-dequantize shape).

        Error feedback: ``transform(grads, residual)`` adds the
        previous step's residual slice before quantizing and returns
        ``(grads, new_residual)`` with ``new_residual = v − Q(v)`` per
        bucket — the quantization error is carried, not lost, so the
        applied updates telescope to the true gradient sum
        (tests/communicator_tests/test_quantization.py).
        """
        axis = self.axis_name
        size = self.size
        wire = self.allreduce_grad_dtype
        comm = self

        def transform(grads, residual=None):
            from ._memory_utility import (dequantize_sum,
                                          quantize_with_feedback,
                                          tree_pack, tree_unpack)
            if residual is None and comm.error_feedback:
                _warn_inert_error_feedback()
            leaves, treedef = jax.tree.flatten(grads)
            if not leaves:
                return grads if residual is None else (grads, residual)
            orig_dtypes = [g.dtype for g in leaves]
            buckets = comm.grad_buckets([g.shape for g in leaves],
                                        [g.dtype for g in leaves])
            out = [None] * len(leaves)
            new_res = []
            offset = 0
            for k, idx in enumerate(buckets):
                with jax.named_scope("mn_q_bucket_exchange"):
                    flat, spec = tree_pack([leaves[i] for i in idx])
                    n = flat.shape[0]
                    r = None
                    if residual is not None:
                        r = residual[offset:offset + n]
                        offset += n
                    q, scale, nr = quantize_with_feedback(flat, r, wire)
                    if nr is not None:
                        new_res.append(nr)
                    qg = lax.all_gather(q, axis)
                    sg = lax.all_gather(scale, axis)
                    mean = dequantize_sum(qg, sg) / size
                    for i, g in zip(idx, tree_unpack(mean, spec)):
                        out[i] = g
            leaves = [g.astype(d) for g, d in zip(out, orig_dtypes)]
            grads = jax.tree.unflatten(treedef, leaves)
            if residual is None:
                return grads
            return grads, jnp.concatenate(new_res)

        return transform

    def _hierarchical_grad_transform(self):
        """The two-level exchange (ISSUE 6): per bucket, intra-host
        ``psum_scatter`` over ICI → inter-host allreduce over DCN on the
        1/ici chunk → intra-host ``all_gather`` over ICI.  DCN — the hop
        that is an order of magnitude slower on a real pod — only ever
        carries ``1/ici_size`` of the gradient bytes.

        Emission follows ``_memory_utility.hop_schedule`` literally:
        each bucket's DCN collective is issued right after its ICI
        reduce-scatter (in reverse-registration plan order, so the
        first bucket backward closes reaches the slow wire first), and
        ALL DCN ops precede ALL ICI all-gathers — the slow hop starts
        as early as dataflow allows and the fast-hop rebuilds overlap
        the remaining DCN traffic (the hop-overlap schedule HiCCL and
        the multi-process-per-GPU allreduce paper measure; pinned by
        the ordered census in tests/test_comm_budget.py).

        Per-hop compression: ``allreduce_grad_dtype`` casts the leaves
        for the ICI hop (as on the flat path); ``dcn_grad_dtype`` —
        ``allreduce_grad_dtype={"dcn": ...}`` — additionally compresses
        only the chunk crossing DCN, so ICI stays lossless while the
        slow hop's bytes halve (the first brick of ROADMAP item 2).
        The mean divide happens once, on the 1/ici chunk (fewer flops,
        same math).

        QUANTIZED DCN (ISSUE 8, the second brick): an int8/fp8
        ``dcn_grad_dtype`` replaces the chunk ``psum`` with
        quantize → ``all_gather(q + scale)`` over DCN →
        dequantize-sum: ranks quantize with their OWN per-bucket scale
        (computed on the reduce-scattered chunk), so summing codewords
        is impossible — each rank reconstructs the sum from every
        group's ``(q, scale)`` instead, and the slow wire carries the
        quantized fraction of the bytes.  With ``transform(grads,
        residual)`` the quantization error is fed back (per bucket, per
        device) and the call returns ``(grads, new_residual)``.
        """
        ici, dcn = self.ici_axis, self.dcn_axis
        intra = self.ici_size
        size = self.size
        dtype = self.allreduce_grad_dtype
        dcn_dtype = self.dcn_grad_dtype
        from ._memory_utility import is_quantized_dtype
        q_dcn = is_quantized_dtype(dcn_dtype)
        comm = self

        def transform(grads, residual=None):
            from ._memory_utility import (dequantize_sum, hop_schedule,
                                          pad_to_multiple,
                                          quantize_with_feedback,
                                          tree_pack, tree_unpack)
            if residual is None and q_dcn and comm.error_feedback:
                _warn_inert_error_feedback()
            leaves, treedef = jax.tree.flatten(grads)
            if not leaves:
                return grads if residual is None else (grads, residual)
            orig_dtypes = [g.dtype for g in leaves]
            if dtype is not None:
                leaves = [g.astype(dtype) for g in leaves]
            buckets = comm.grad_buckets([g.shape for g in leaves],
                                        [g.dtype for g in leaves])
            out = [None] * len(leaves)
            specs = {}
            chunks = {}
            new_res = {}
            offset = 0
            for op, b in hop_schedule(len(buckets)):
                idx = buckets[b]
                if op == "ici_reduce_scatter":
                    with jax.named_scope("mn_hier_rs_ici"):
                        flat, spec = tree_pack([leaves[i] for i in idx])
                        flat, n_true = pad_to_multiple(flat, intra)
                        specs[b] = (spec, n_true)
                        chunks[b] = lax.psum_scatter(
                            flat, ici, scatter_dimension=0, tiled=True)
                elif op == "dcn_exchange" and q_dcn:
                    with jax.named_scope("mn_hier_quantized_dcn"):
                        c = chunks[b]
                        wire = c.dtype
                        n = c.shape[0]
                        r = None
                        if residual is not None:
                            r = residual[offset:offset + n]
                            offset += n
                        q, scale, nr = quantize_with_feedback(
                            c, r, dcn_dtype)
                        if nr is not None:
                            new_res[b] = nr
                        qg = lax.all_gather(q, dcn)
                        sg = lax.all_gather(scale, dcn)
                        chunks[b] = (dequantize_sum(qg, sg)
                                     / size).astype(wire)
                elif op == "dcn_exchange":
                    with jax.named_scope("mn_hier_allreduce_dcn"):
                        c = chunks[b]
                        wire = c.dtype
                        if dcn_dtype is not None:
                            c = c.astype(dcn_dtype)
                        c = lax.psum(c, dcn)
                        chunks[b] = c.astype(wire) / size
                else:  # ici_all_gather
                    with jax.named_scope("mn_hier_ag_ici"):
                        full = lax.all_gather(chunks[b], ici, tiled=True)
                    spec, n_true = specs[b]
                    for i, g in zip(idx, tree_unpack(full[:n_true], spec)):
                        out[i] = g
            leaves = [g.astype(d) for g, d in zip(out, orig_dtypes)]
            grads = jax.tree.unflatten(treedef, leaves)
            if residual is None:
                return grads
            return grads, jnp.concatenate(
                [new_res[b] for b in range(len(buckets))])

        return transform

    def _striped_grad_transform(self):
        """The multi-path striped exchange (ISSUE 11): each bucket's
        flat payload splits by ``stripe_plan(n, stripe_ratio)`` into an
        ICI-path slice and a DCN-path slice, and BOTH fabrics carry
        bulk traffic at once instead of hierarchically (FlexLink's
        use-every-link-simultaneously result; HiCCL-style compositional
        schedule — the plan is the pure function
        ``hop_schedule(k, mode="striped")`` and emission follows it
        literally).

        * **ICI path** (share ``1 − ratio``): the PR 6 fast-hop-major
          exchange — ``psum_scatter`` over ICI → chunk allreduce over
          DCN (per-hop dtype / int8+EF quantization apply here exactly
          as on the hierarchical exchange) → ``all_gather`` over ICI.
        * **DCN path** (share ``ratio``): the TRANSPOSED slow-hop-major
          exchange — ``psum_scatter`` over DCN (the bulk rides the slow
          wire, compressed under the per-hop dtype) → chunk allreduce
          over ICI (lossless by design: the chunk upcasts to f32 before
          the fast hop) → ``all_gather`` over DCN.  With a QUANTIZED
          ``dcn_grad_dtype`` the slow wire cannot carry a psum_scatter
          of codewords, so the path reshapes to lossless ``psum`` over
          ICI first, then quantize (+ error feedback) →
          ``all_gather(q + scale)`` over DCN → dequantize-sum — the
          DynamiQ gather shape on the slice's single slow crossing.

        Both paths' scatter+exchange ops are emitted before ANY
        bucket's gather epilogue (the generalized hop_schedule
        contract), so XLA's async scheduler can drain the two fabrics
        concurrently.

        ``stale_dcn`` (the DCN-slice-only double-buffering variant,
        ``double_buffering="dcn"``): the assembled gradient uses the
        PREVIOUS step's DCN-path results while this step's fresh
        DCN-path values are returned (appended last) to become the next
        stale buffer — the PR 5/6 one-step-stale contract applied
        per-path, hiding the slow path's latency entirely behind
        compute while the ICI path stays fresh.  Return shape:
        ``grads`` | ``(grads, new_residual)`` | ``(grads, fresh_dcn)``
        | ``(grads, new_residual, fresh_dcn)`` depending on which
        optional operands were threaded.
        """
        ici, dcn = self.ici_axis, self.dcn_axis
        intra, inter = self.ici_size, self.dcn_size
        size = self.size
        ratio = self.stripe_ratio
        dtype = self.allreduce_grad_dtype
        dcn_dtype = self.dcn_grad_dtype
        from ._memory_utility import is_quantized_dtype
        q_dcn = is_quantized_dtype(dcn_dtype)
        comm = self

        def transform(grads, residual=None, stale_dcn=None):
            from ._memory_utility import (dequantize_sum, hop_schedule,
                                          pad_to_multiple,
                                          quantize_with_feedback,
                                          stripe_plan, tree_pack,
                                          tree_unpack)
            if residual is None and q_dcn and comm.error_feedback:
                _warn_inert_error_feedback()
            leaves, treedef = jax.tree.flatten(grads)
            if not leaves:
                out = [grads]
                if residual is not None:
                    out.append(residual)
                if stale_dcn is not None:
                    out.append(stale_dcn)
                return out[0] if len(out) == 1 else tuple(out)
            orig_dtypes = [g.dtype for g in leaves]
            if dtype is not None:
                leaves = [g.astype(dtype) for g in leaves]
            buckets = comm.grad_buckets([g.shape for g in leaves],
                                        [g.dtype for g in leaves])
            # pre-pass: per-bucket split sizes and the residual /
            # stale-buffer offsets (pure python over the plan — the
            # schedule consumes buckets out of offset order, so a
            # running counter cannot work)
            n_i, n_d, chunk_a = [], [], []
            off_a, off_b, off_s = [], [], []
            r_off = s_off = 0
            for idx in buckets:
                n_b = sum(int(np.prod(leaves[i].shape)) for i in idx)
                a, d = stripe_plan(n_b, ratio)
                n_i.append(a)
                n_d.append(d)
                chunk_a.append(-(-a // intra) if a else 0)
                off_a.append(r_off + d)   # residual layout per bucket:
                off_b.append(r_off)       # [B slice, then A chunk] —
                r_off += d + chunk_a[-1]  # consumption order of the
                off_s.append(s_off)       # schedule (dcn path first)
                s_off += d
            out = [None] * len(leaves)
            specs = {}
            a_chunk = {}
            b_chunk = {}
            b_full = {}
            new_res = {}
            fresh_b = {}
            for op, b in hop_schedule(len(buckets), mode="striped"):
                idx = buckets[b]
                if op == "dcn_path_scatter":
                    with jax.named_scope("mn_stripe_pack_scatter_dcn"):
                        flat, spec = tree_pack([leaves[i] for i in idx])
                        specs[b] = (spec, flat.dtype)
                        a_flat = flat[:n_i[b]]
                        b_slice = flat[n_i[b]:]
                        a_chunk[b] = a_flat  # scattered at ici_path_scatter
                        if not n_d[b]:
                            continue
                        if q_dcn:
                            # quantized slow wire: each device quantizes
                            # its OWN pre-reduction slice (+ its own
                            # error-feedback residual — quantizing after
                            # any cross-device reduce would mix distinct
                            # residuals into codewords that disagree
                            # across the ICI axis and de-replicate the
                            # params), and the slice's single DCN
                            # crossing is this gather of codewords —
                            # issued FIRST in the bucket, so the slow
                            # wire starts as early as possible
                            r = None
                            if residual is not None:
                                r = residual[off_b[b]:off_b[b] + n_d[b]]
                            q, scale, nr = quantize_with_feedback(
                                b_slice, r, dcn_dtype)
                            if nr is not None:
                                new_res[(b, "b")] = nr
                            b_chunk[b] = (lax.all_gather(q, dcn),
                                          lax.all_gather(scale, dcn))
                        else:
                            b_pad, _ = pad_to_multiple(b_slice, inter)
                            if dcn_dtype is not None:
                                b_pad = b_pad.astype(dcn_dtype)
                            b_chunk[b] = lax.psum_scatter(
                                b_pad, dcn, scatter_dimension=0,
                                tiled=True)
                elif op == "ici_path_scatter":
                    if not n_i[b]:
                        continue
                    with jax.named_scope("mn_stripe_rs_ici"):
                        a_pad, _ = pad_to_multiple(a_chunk[b], intra)
                        a_chunk[b] = lax.psum_scatter(
                            a_pad, ici, scatter_dimension=0, tiled=True)
                elif op == "dcn_path_exchange":
                    if not n_d[b]:
                        continue
                    if q_dcn:
                        with jax.named_scope("mn_stripe_dequant_psum_ici"):
                            # decode every DCN group's (q, scale) pair,
                            # then finish the reduction across ICI in
                            # f32 — the lossless fast hop, same
                            # contract as the hierarchical exchange
                            qg, sg = b_chunk[b]
                            s = dequantize_sum(qg, sg)
                            b_full[b] = lax.psum(s, ici) / size
                    else:
                        with jax.named_scope("mn_stripe_allreduce_ici"):
                            # the DCN-path chunk's cross-fabric
                            # allreduce rides the LOSSLESS fast hop:
                            # upcast to f32 before accumulating
                            c = lax.psum(
                                b_chunk[b].astype(jnp.float32), ici)
                            b_chunk[b] = c / size
                elif op == "ici_path_exchange":
                    if not n_i[b]:
                        continue
                    c = a_chunk[b]
                    wire = c.dtype
                    if q_dcn:
                        with jax.named_scope("mn_stripe_quantized_chunk"):
                            n = c.shape[0]
                            r = None
                            if residual is not None:
                                r = residual[off_a[b]:off_a[b] + n]
                            q, scale, nr = quantize_with_feedback(
                                c, r, dcn_dtype)
                            if nr is not None:
                                new_res[(b, "a")] = nr
                            qg = lax.all_gather(q, dcn)
                            sg = lax.all_gather(scale, dcn)
                            a_chunk[b] = (dequantize_sum(qg, sg)
                                          / size).astype(wire)
                    else:
                        with jax.named_scope("mn_stripe_allreduce_dcn"):
                            if dcn_dtype is not None:
                                c = c.astype(dcn_dtype)
                            c = lax.psum(c, dcn)
                            a_chunk[b] = c.astype(wire) / size
                elif op == "dcn_path_gather":
                    if not n_d[b] or q_dcn:
                        continue  # quantized path is already full
                    with jax.named_scope("mn_stripe_ag_dcn"):
                        c = b_chunk[b]
                        if dcn_dtype is not None:
                            c = c.astype(dcn_dtype)
                        full = lax.all_gather(c, dcn, tiled=True)
                        b_full[b] = full[:n_d[b]].astype(jnp.float32)
                else:  # ici_path_gather: rebuild + assemble the bucket
                    spec, wire = specs[b]
                    parts = []
                    if n_i[b]:
                        with jax.named_scope("mn_stripe_ag_ici"):
                            full = lax.all_gather(a_chunk[b], ici,
                                                  tiled=True)
                        parts.append(full[:n_i[b]].astype(wire))
                    if n_d[b]:
                        fresh = b_full[b].astype(wire)
                        if stale_dcn is not None:
                            fresh_b[b] = fresh.astype(jnp.float32)
                            applied = stale_dcn[
                                off_s[b]:off_s[b] + n_d[b]].astype(wire)
                        else:
                            applied = fresh
                        parts.append(applied)
                    flat = parts[0] if len(parts) == 1 \
                        else jnp.concatenate(parts)
                    for i, g in zip(idx, tree_unpack(flat, spec)):
                        out[i] = g
            leaves = [g.astype(d) for g, d in zip(out, orig_dtypes)]
            grads = jax.tree.unflatten(treedef, leaves)
            ret = [grads]
            if residual is not None:
                res_parts = []
                for b in range(len(buckets)):
                    if (b, "b") in new_res:
                        res_parts.append(new_res[(b, "b")])
                    if (b, "a") in new_res:
                        res_parts.append(new_res[(b, "a")])
                ret.append(jnp.concatenate(res_parts) if res_parts
                           else residual)
            if stale_dcn is not None:
                ret.append(jnp.concatenate(
                    [fresh_b[b] for b in range(len(buckets))
                     if b in fresh_b]) if fresh_b else stale_dcn)
            return ret[0] if len(ret) == 1 else tuple(ret)

        return transform

    # -- SPMD launcher ----------------------------------------------------------------
    def run_spmd(self, fn, *args, in_specs=None, out_specs=None,
                 static_out=False):
        """Run ``fn`` as a ``shard_map``ped program over this communicator's
        axis: rank-local code with this communicator's methods emitting real
        collectives.  Default specs: every arg/result is stacked on its
        leading axis (one slice per rank); pass ``P()`` in ``in_specs``/
        ``out_specs`` for replicated values.
        """
        from jax import shard_map
        axis = self.axis_name
        if self._axis_in_scope():
            # already inside a shard_map binding this axis (e.g. the
            # plain optimizer's SPMD step wraps the whole train step):
            # args are rank-local; run the rank-local body directly —
            # nesting another shard_map over the same axis is an error
            return fn(*args)
        if in_specs is None:
            in_specs = tuple(P(axis) for _ in args)
        if out_specs is None:
            out_specs = P(axis)
        mapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        if _is_traced(args):
            # inside an outer jit/grad trace — inline the shard_mapped
            # computation.  NOTE: the outer jit must be mesh-aware for
            # this to lower (a single-device jit cannot host an N-device
            # shard_map); Optimizer._make_step handles that by making
            # the whole step a shard_map when the target is SPMD.
            return mapped(*args)
        return jax.jit(mapped)(*args)

    def axis_in_scope(self):
        """Public form of the axis-environment query: True when EVERY
        mesh axis this communicator's collectives address is bound by
        an enclosing ``shard_map`` of the current trace.  The dispatch
        guard model code uses (``models.transformer._axis_bound``,
        ``parallel.moe``) — a hierarchical communicator binds TWO axes
        and a bare ``axis_exists(self.axis_name)`` probe is False for
        the tuple, which is exactly how the MoE layer used to fall
        back to DENSE routing on a two-level mesh without a word
        (ISSUE 12 guard rail)."""
        return self._axis_in_scope()

    def _axis_in_scope(self):
        """True when this communicator's mesh axis is bound by an
        enclosing shard_map of the current trace — an explicit
        axis-environment query (``utils.compat.axis_env_contains``),
        NOT a probe-``lax.axis_index``-and-catch: this check dispatches
        between eager and traced collectives, and exception control
        flow here would silently flip modes under a jax behavior change
        (VERDICT open item 7; pinned by
        ``tests/communicator_tests/test_axis_in_scope.py``).  A
        hierarchical communicator binds TWO axes; both must be in scope
        (a partial binding cannot host the two-level exchange)."""
        from chainermn_tpu.utils.compat import axis_env_contains
        names = self.axis_name if isinstance(self.axis_name, tuple) \
            else (self.axis_name,)
        return all(axis_env_contains(n) for n in names)

    # -- split ------------------------------------------------------------------------
    def split(self, color, key):
        """Partition devices into sub-communicators (reference:
        ``MPI_Comm_Split`` semantics over device ranks).

        ``color``/``key`` follow the per-rank convention: sequences of
        length ``size`` (device rank i gets color[i]); scalars apply the
        same value to every rank (the common "all same group" case).
        Returns the sub-communicator containing the CALLING controller's
        devices (MPI semantics: rank r's ``MPI_Comm_Split`` returns r's
        group).  All of this controller's local devices must share one
        color — a straddling split has no single "my sub-communicator"
        under single-controller SPMD.  The full set is available as
        ``.split_all(color, key)``.
        """
        size = self.size
        colors = [color] * size if np.isscalar(color) else list(color)
        if len(colors) != size:
            raise ValueError("color/key must be scalars or length-size")
        local = [i for i, d in enumerate(self._devices)
                 if getattr(d, "process_index", 0) == jax.process_index()]
        my_colors = {colors[i] for i in (local or [0])}
        if len(my_colors) > 1:
            raise ValueError(
                f"this controller's devices straddle split colors "
                f"{sorted(my_colors)}; use split_all() for the full set")
        my_color = my_colors.pop()
        comms = self.split_all(color, key)
        return comms[sorted(set(colors)).index(my_color)]

    def split_all(self, color, key):
        """All sub-communicators of the split, ordered by sorted color.

        Sub-communicators are FLAT (one axis): an arbitrary color
        partition has no canonical two-level structure, so a
        hierarchical parent's split members drop the (dcn, ici) split —
        rebuild one with ``intra_size=``/``inter_size=`` if a subgroup
        spans hosts and needs it.  A hierarchical parent's per-hop
        compression degrades onto the subgroup's single hop — the DCN
        entry wins (slow-hop intent), else the ICI entry (the same
        keep-the-bytes-low convention as the
        ``CHAINERMN_TPU_HIERARCHY=flat`` escape hatch) — never silently
        to lossless."""
        size = self.size
        colors = [color] * size if np.isscalar(color) else list(color)
        keys = [key] * size if np.isscalar(key) else list(key)
        if len(colors) != size or len(keys) != size:
            raise ValueError("color/key must be scalars or length-size")
        base = self.axis_name if isinstance(self.axis_name, str) \
            else "_".join(self.axis_name)
        groups = {}
        for i, (c, k) in enumerate(zip(colors, keys)):
            groups.setdefault(c, []).append((k, i))
        comms = []
        for c in sorted(groups):
            members = [i for _, i in sorted(groups[c])]
            comms.append(MeshCommunicator(
                devices=[self._devices[i] for i in members],
                axis_name=f"{base}_s{c}",
                allreduce_grad_dtype=(
                    self.dcn_grad_dtype or self.allreduce_grad_dtype
                    if self.hierarchy is not None
                    else self.allreduce_grad_dtype),
                batch_collectives=self.batch_collectives,
                bucket_mb=self.bucket_mb,
                error_feedback=self.error_feedback,
                # a hierarchical name would re-trigger the two-level
                # split on the subgroup's arbitrary device subset
                name="jax_ici" if self.hierarchy is not None
                else self.name))
        return comms

    # -- diagnostics --------------------------------------------------------------------
    def __repr__(self):
        topo = (f" hierarchy={self.dcn_size}x{self.ici_size}"
                if self.hierarchy is not None else "")
        if self.striped:
            topo += f" stripe_ratio={self.stripe_ratio}"
        return (f"<{type(self).__name__} name={self.name!r} size={self.size} "
                f"axis={self.axis_name!r}{topo} "
                f"grad_dtype={self.allreduce_grad_dtype}>")

    def _check_stacked(self, x, what):
        if x.ndim == 0 or x.shape[0] != self.size:
            raise ValueError(
                f"eager {what} expects a stacked array with leading axis "
                f"size={self.size} (one slice per rank); got shape {x.shape}. "
                f"Inside compiled steps (run_spmd) pass the rank-local value.")


class ElasticMeshCommunicator(MeshCommunicator):
    """A :class:`MeshCommunicator` over the LIVE subset of controller
    processes (ISSUE 10 — the rebuilt transport after an elastic
    shrink/grow).

    ``members`` are GLOBAL controller ranks (the stable process
    identities membership decides over); the communicator maps them to
    dense slots 0..n-1 for collective addressing — ``rank`` /
    ``inter_rank`` are the SLOT, ``stable_rank`` keeps the global
    identity (checkpoint filenames key off it, so a process re-reads
    its OWN snapshots across any number of resizes).  ``epoch`` is the
    membership epoch the member set was decided at; the mesh axis name
    and the object-channel namespace are both epoch-suffixed, so a
    rebuilt incarnation can never match a dead one's compiled programs
    or stranded KV keys.

    Construction is COLLECTIVE over the members (every live member
    builds the communicator for the same view, lock-step — the elastic
    supervisor's rebuild step guarantees this); a dead peer is, by
    definition of the view, not required.

    ``channel`` (optional): the previous incarnation's
    :class:`~._host_channel.HostChannel`, donated as a template — its
    client and timeout/retry knobs carry over to the members-only
    sub-channel.  ``devices``: explicit device list override (the
    single-controller simulated-elasticity knob tier-1 uses — shrink a
    world of local devices without any real process leaving).
    """

    def __init__(self, members, epoch=0, channel=None, devices=None,
                 axis_name=None, **kwargs):
        members = tuple(sorted(int(m) for m in members))
        if not members:
            raise ValueError("an elastic communicator needs >= 1 member")
        self.members = members
        self.epoch = int(epoch)
        me = jax.process_index()
        if jax.process_count() > 1 and me not in members:
            raise ValueError(
                f"process {me} is not in the elastic view {members}; "
                f"non-members must re-join through the membership "
                f"protocol before constructing the communicator")
        self._member_slot = members.index(me) if me in members else 0
        self._stable_rank = me
        # the members-only object channel must exist BEFORE the base
        # constructor runs (its intra-topology allgather is the first
        # collective of the new incarnation)
        self._elastic_channel = self._derive_channel(channel)
        if devices is None:
            by_proc = {}
            for d in jax.devices():
                by_proc.setdefault(getattr(d, "process_index", 0),
                                   []).append(d)
            devices = [d for m in members
                       for d in sorted(by_proc.get(m, ()),
                                       key=lambda d: d.id)]
            if not devices:
                raise ValueError(
                    f"no devices owned by members {members}")
        if axis_name is None:
            axis_name = f"elastic_e{self.epoch}"
        super().__init__(devices=devices, axis_name=axis_name, **kwargs)

    def _derive_channel(self, template):
        """Members-only sub-channel: same client and tolerance knobs as
        the template, namespace scoped by membership epoch (keys of any
        other incarnation can never match), process ids remapped to the
        view's dense slots."""
        from ._host_channel import HostChannel, get_host_channel
        if template is None:
            template = get_host_channel()
        if template is None or len(self.members) <= 1:
            # single live controller (or no coordination service): the
            # object channel degenerates to loopback like any
            # single-process run
            return None
        ns_root = template._ns.split("/el", 1)[0]
        return HostChannel(
            namespace=f"{ns_root}/el{self.epoch}",
            client=template._client,
            chunk_bytes=template._chunk,
            timeout_ms=template._timeout_ms,
            op_timeouts=dict(template._op_timeouts),
            max_retries=template.max_retries,
            backoff_base_s=template.backoff_base_s,
            backoff_max_s=template.backoff_max_s,
            clock=template._clock, sleep=template._sleep,
            process_id=self._member_slot,
            num_processes=len(self.members))

    def _host_channel(self):
        return self._elastic_channel

    def _clone_kwargs(self):
        # a retuned elastic clone is the SAME incarnation (same members,
        # same epoch, same channel template) with different exchange
        # knobs — the epoch-suffixed axis name already rides in via the
        # base kwargs, so the re-tuned plan artifact is per-epoch
        kwargs = super()._clone_kwargs()
        kwargs["members"] = self.members
        kwargs["epoch"] = self.epoch
        kwargs["channel"] = self._elastic_channel
        return kwargs

    # -- topology: slots for collectives, stable ids for identity ----------
    @property
    def rank(self):
        return self._member_slot

    @property
    def inter_rank(self):
        return self._member_slot

    @property
    def inter_size(self):
        return len(self.members)

    @property
    def stable_rank(self):
        """This process's GLOBAL controller rank — invariant across
        resizes (snapshot filenames and membership announcements key
        off it, never off the per-view slot)."""
        return self._stable_rank

    def _local_device_counts(self):
        # base indexes by jax process id over process_count slots; the
        # elastic view has len(members) slots keyed by member order
        slot = {m: i for i, m in enumerate(self.members)}
        counts = [0] * len(self.members)
        for d in self._devices:
            counts[slot[getattr(d, "process_index", 0)]] += 1
        return counts

    def _process_allgather_pickled(self, obj):
        # NEVER fall back to multihost_utils.process_allgather: that
        # path spans every BOOT process, and an elastic world exists
        # precisely because some of them are gone — the fallback would
        # hang on the dead peers.  Members-only channel, or loopback.
        ch = self._host_channel()
        if ch is not None:
            return ch.allgather(obj)
        return [obj]

    def __repr__(self):
        return (f"<ElasticMeshCommunicator epoch={self.epoch} "
                f"members={self.members} size={self.size} "
                f"axis={self.axis_name!r}>")
