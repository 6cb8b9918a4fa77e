"""Communicator factory.

Reference: ``chainermn/communicators/__init__.py · create_communicator``
(SURVEY.md §2.1) — maps a name string to a communicator.  All reference
names are accepted; on TPU they are flavors of one mesh-backed
implementation (SURVEY §2.7: the classification collapses to mesh-axis +
dtype + bucketing choices):

===================  ========================================================
name                 TPU realization
===================  ========================================================
``naive``            per-parameter mean collectives (correctness baseline)
``flat``             single flat-bucket collective (``batch_collectives``)
``pure_nccl``        fused bucket + optional compressed-dtype gradient psum
                     (``batch_collectives="bucketed"`` restores the
                     reference's SIZE-BOUNDED bucket pipeline — K
                     ``bucket_mb``-bounded collectives in reverse
                     registration order, overlappable with backward)
``hierarchical``     REAL two-level (dcn × ici) exchange (ISSUE 6, no
                     longer an alias): intra-host reduce-scatter over
                     ICI → inter-host allreduce over DCN on the 1/intra
                     chunk → intra-host all-gather, so DCN only ever
                     carries ``1/ici_size`` of the gradient bytes.  The
                     split is inferred from process_count × local
                     devices, forced with ``intra_size=``/
                     ``inter_size=``, or taken from a 2-axis mesh via
                     ``MeshCommunicator.from_mesh_axis(mesh, (dcn,
                     ici))``.  Pays off whenever the mesh spans >1 DCN
                     hop (multi-host pods/slices); on one host it
                     degenerates to a size-1 DCN axis (measure — the
                     schedule is free there, not harmful).  Per-hop
                     compression: ``allreduce_grad_dtype={"dcn":
                     "bfloat16"}``.  ``CHAINERMN_TPU_HIERARCHY=flat``
                     is the escape hatch back to the flat alias.
``two_dimensional``  same two-level exchange as ``hierarchical`` (the
                     reference's leader-staged vs chunked-2D variants
                     collapse on TPU: every chip is DCN-attached, so
                     the chunked form strictly dominates — kept as a
                     distinct name for reference parity)
``single_node``      asserts one host, otherwise ``pure_nccl``
``non_cuda_aware``   alias of ``naive`` (host staging has no TPU analog)
``jax_ici``          canonical native name (= ``pure_nccl`` defaults)
``dummy``            no-op loopback
===================  ========================================================
"""

from __future__ import annotations

import jax

from ._autotune import (agree_exchange_plan, derive_exchange_plan,
                        measure_fabric, measurements_from_trace,
                        plan_fingerprint, record_plan, reduce_measurements,
                        retune_communicator, topology_summary)
from ._host_channel import (ChannelError, ChannelTimeoutError, PeerLostError,
                            HostChannel, HeartbeatMonitor)
from ._membership import (ElasticMembership, MembershipView,
                          multicast_tree_plan)
from .communicator_base import CommunicatorBase
from .debug_communicator import DebugCommunicator
from .dummy_communicator import DummyCommunicator
from .fault_injection_communicator import (FaultInjectionCommunicator,
                                           bind_host_channel)
from .fault_schedule import (FaultSchedule, FaultSpec, InjectedFault,
                             RankPreempted, schedule_from_env)
from .mesh_communicator import ElasticMeshCommunicator, MeshCommunicator

__all__ = ["create_communicator", "CommunicatorBase", "MeshCommunicator",
           "ElasticMeshCommunicator", "DummyCommunicator",
           "DebugCommunicator",
           "FaultInjectionCommunicator", "FaultSchedule", "FaultSpec",
           "InjectedFault", "RankPreempted", "bind_host_channel",
           "schedule_from_env",
           "ChannelError", "ChannelTimeoutError", "PeerLostError",
           "HostChannel", "HeartbeatMonitor",
           "ElasticMembership", "MembershipView", "multicast_tree_plan",
           "agree_exchange_plan", "derive_exchange_plan", "measure_fabric",
           "measurements_from_trace", "plan_fingerprint", "record_plan",
           "reduce_measurements", "retune_communicator",
           "topology_summary"]

_NAMES = ("naive", "flat", "hierarchical", "two_dimensional", "single_node",
          "non_cuda_aware", "pure_nccl", "jax_ici", "dummy", "debug",
          "fault")

def create_communicator(communicator_name="jax_ici", devices=None,
                        axis_name="mn_world", allreduce_grad_dtype=None,
                        batch_collectives=None, bucket_mb=None,
                        fault_schedule=None, intra_size=None,
                        inter_size=None, error_feedback=True,
                        stripe_ratio=None, autotune=None, **kwargs):
    """Create a communicator by reference name.

    ``allreduce_grad_dtype``: gradient-compression dtype for the collective
    (reference fp16 path; bf16 recommended on TPU).  On the hierarchical
    flavors a ``{"ici": ..., "dcn": ...}`` dict compresses per hop
    (lossless ICI + bf16 DCN is the interesting point).  ISSUE 8 adds
    the QUANTIZED wires ``"int8"`` / ``"float8_e4m3"`` /
    ``"float8_e5m2"``: per-bucket symmetric-scale quantization of the
    slow hop (the DCN crossing on hierarchical flavors — a scalar
    quantized dtype compresses DCN only, ICI stays lossless; the whole
    exchange on flat ones), with ``error_feedback=True`` (default)
    carrying the quantization residual in a persistent buffer so the
    error telescopes instead of accumulating (docs/performance.md §9;
    convergence is parity-gated, not bit-exact).
    ``CHAINERMN_TPU_COMPRESS=off`` is the factory-level escape hatch:
    quantized wires fall back to lossless (bf16 casts untouched).
    ISSUE 12: on hierarchical flavors the ``dcn`` entry ALSO
    compresses the MoE token dispatch's slow crossing
    (``parallel.moe`` two-stage exchange: bf16 cast, or int8/fp8
    codewords with per-segment scales) — one knob, every slow-hop
    traffic class; the ICI stage of the dispatch is lossless by
    design like every fast hop.
    ``devices``:
    subset of ``jax.devices()`` (default all).  ``batch_collectives``:
    ``False`` (per-leaf collectives), ``True`` (one flat bucket — the
    per-name default for the fused flavors) or ``"bucketed"`` (K
    size-bounded buckets, the reference pure_nccl pipeline; ``bucket_mb``
    / ``CHAINERMN_TPU_BUCKET_MB`` bounds each bucket, default ~4 MB —
    composes with the hierarchical flavors: each bucket runs the
    two-level rs/allreduce/ag).  ``intra_size``/``inter_size``: force
    the (dcn, ici) split of the hierarchical flavors instead of
    inferring it from the controller topology (the simulated-multihost
    knob tier-1 uses).  ``stripe_ratio`` (ISSUE 11, hierarchical
    flavors only; ``CHAINERMN_TPU_STRIPE_RATIO`` is the no-code-change
    env knob): the DCN share of each bucket's payload in the STRIPED
    multi-path exchange — that slice runs the transposed slow-hop-major
    exchange concurrently with the fast-hop-major remainder, so both
    fabrics carry bulk traffic at once instead of hierarchically
    (docs/performance.md §10; 0 = the strict hierarchical schedule).
    ``CHAINERMN_TPU_HIERARCHY=flat`` collapses
    ``hierarchical``/``two_dimensional`` back to the flat one-axis
    alias (sizes ignored, striping dropped — one fabric has no second
    path) — the no-code-change escape hatch.
    ``fault_schedule`` (``fault`` name only): a :class:`FaultSchedule` or
    spec dict; defaults to ``CHAINERMN_TPU_FAULT_SCHEDULE`` from the
    environment — the chaos harness's entry point (see
    ``docs/resilience.md``).
    ``autotune`` (ISSUE 19, docs/performance.md §12): self-tune the
    exchange knobs from MEASURED fabric numbers instead of guesses.
    ``True``/``"startup"`` runs the seconds-scale startup micro-bench
    now (collective — every rank enters), agrees the plan (measurements
    all-gathered + reduced deterministically, plan broadcast from rank
    0) and returns the retuned communicator; ``"online"`` defers — the
    multi-node optimizer re-tunes after its first N steps from the span
    tracer's ``train/grad_exchange*`` payload-tagged spans; a dict is a
    RECORDED plan (e.g. the committed ``tools/autotune_plan.json``
    ``plan`` object) applied directly with no measurement.  The plan
    only fills knobs not hand-set here (explicit argument or env var) —
    hand knobs always win, so pinning ``bucket_mb=``/``stripe_ratio=``
    alongside ``autotune=`` keeps those knobs yours and derives the
    rest.
    """
    name = communicator_name
    if name not in _NAMES:
        raise ValueError(
            f"unknown communicator {name!r}; choose from {_NAMES}")
    if fault_schedule is not None and name != "fault":
        raise ValueError(
            f"fault_schedule= is only honored by the 'fault' "
            f"communicator, not {name!r} — a silently dropped schedule "
            f"would make a chaos run pass vacuously")
    if autotune not in (None, False, True, "startup", "online") \
            and not isinstance(autotune, dict):
        raise ValueError(
            f"autotune must be True/'startup' (micro-bench now), "
            f"'online' (re-tune from the first N steps' trace), or a "
            f"recorded plan dict; got {autotune!r}")
    if autotune and name in ("dummy", "debug"):
        raise ValueError(
            f"autotune= is a mesh-communicator knob, not {name!r} — a "
            f"silently dropped plan would make an autotune run pass "
            f"vacuously")
    if name == "dummy":
        return DummyCommunicator()
    if name == "fault":
        schedule = fault_schedule if fault_schedule is not None \
            else schedule_from_env()
        if schedule is None:
            raise ValueError(
                "communicator 'fault' needs fault_schedule= or the "
                "CHAINERMN_TPU_FAULT_SCHEDULE env var")
        if isinstance(schedule, dict):
            schedule = FaultSchedule.from_dict(schedule)
        base = create_communicator(
            "jax_ici", devices=devices, axis_name=axis_name,
            allreduce_grad_dtype=allreduce_grad_dtype,
            batch_collectives=batch_collectives, bucket_mb=bucket_mb,
            intra_size=intra_size, inter_size=inter_size,
            error_feedback=error_feedback, stripe_ratio=stripe_ratio,
            autotune=autotune, **kwargs)
        # the hc.* transport hook gets its own schedule CLONE (same
        # specs + seed, separate RNG stream/counters): transport call
        # counts are inherently per-rank asymmetric (root puts,
        # non-root gets, retries), and sharing one RNG stream would let
        # that asymmetry desync the communicator-surface draws across
        # ranks — breaking the lock-step same-call-site guarantee the
        # wrapper documents.  hc faults are recorded on the clone.
        comm = FaultInjectionCommunicator(base, schedule)
        channel = base._host_channel()
        if channel is not None:
            # the clone re-binds the wrapper's rank: to_dict carries the
            # specs' rank targeting but a schedule's OWN binding is
            # process-local state
            comm.hc_schedule = bind_host_channel(
                channel, FaultSchedule.from_dict(schedule.to_dict())
                .bind_rank(schedule.rank))
        return comm
    if name == "debug":
        return DebugCommunicator(devices=devices, axis_name=axis_name,
                                 allreduce_grad_dtype=allreduce_grad_dtype,
                                 batch_collectives=batch_collectives or False,
                                 bucket_mb=bucket_mb)
    if name == "single_node" and jax.process_count() != 1:
        raise ValueError("single_node communicator requires one host "
                         f"(process_count={jax.process_count()})")
    if allreduce_grad_dtype is not None and name not in (
            "pure_nccl", "jax_ici", "hierarchical", "two_dimensional"):
        raise ValueError(
            f"allreduce_grad_dtype is supported by the fused-bucket "
            f"communicators, not {name!r} (reference: pure_nccl-only)")
    if isinstance(allreduce_grad_dtype, dict) \
            and name not in ("hierarchical", "two_dimensional") \
            and intra_size is None and inter_size is None:
        # an explicit intra/inter split makes ANY fused flavor
        # hierarchical (MeshCommunicator's own contract), so the dict
        # is only nonsense when the result will be a flat one-hop mesh
        raise ValueError(
            f"per-hop allreduce_grad_dtype dicts are a hierarchical-"
            f"communicator knob, not {name!r} without an intra_size/"
            f"inter_size split (a flat exchange has one hop)")
    if batch_collectives is None:
        batch_collectives = name in ("flat", "pure_nccl", "jax_ici",
                                     "hierarchical", "two_dimensional",
                                     "single_node")
    if name in ("hierarchical", "two_dimensional"):
        import os
        if os.environ.get("CHAINERMN_TPU_HIERARCHY", "") \
                .strip().lower() in ("flat", "off", "0"):
            # escape hatch (docs/performance.md §8): flat one-axis alias,
            # split knobs dropped — one env var, zero call-site edits
            intra_size = inter_size = None
            if isinstance(axis_name, (tuple, list)):
                # a (dcn, ici) tuple would re-trigger the two-level
                # split inside MeshCommunicator — flatten the name too
                axis_name = "_".join(axis_name)
            if isinstance(allreduce_grad_dtype, dict):
                # the flat alias has one hop; keep whatever compression
                # the dict asked for on it — the DCN entry wins (the
                # slow-hop intent), else the ICI entry — never a silent
                # drop to lossless (wire bytes must not silently grow).
                # The degradation is NOT silent (ISSUE 8 satellite): the
                # per-hop intent cannot survive a one-hop mesh, so name
                # what was kept and what was dropped, once per distinct
                # dict
                chosen_key = "dcn" if allreduce_grad_dtype.get("dcn") \
                    is not None else "ici"
                dropped = sorted(k for k, v in allreduce_grad_dtype.items()
                                 if k != chosen_key and v is not None)
                _warn_hierarchy_flat_dict_degraded(
                    allreduce_grad_dtype, chosen_key, dropped)
                allreduce_grad_dtype = (allreduce_grad_dtype.get("dcn")
                                        or allreduce_grad_dtype.get("ici"))
            try:
                eff_stripe = stripe_ratio if stripe_ratio is not None \
                    else float(os.environ.get(
                        "CHAINERMN_TPU_STRIPE_RATIO", "") or 0)
            except ValueError:
                eff_stripe = 0
            if eff_stripe:
                # striping needs two fabrics; the flat alias has one.
                # NOT silent (same contract as the per-hop dict
                # degradation): the caller asked for multi-path wire
                # use and gets the flat single-path exchange instead
                _warn_hierarchy_flat_stripe_dropped(eff_stripe)
            comm = MeshCommunicator(
                devices=devices, axis_name=axis_name,
                allreduce_grad_dtype=allreduce_grad_dtype,
                batch_collectives=batch_collectives,
                bucket_mb=bucket_mb, name="jax_ici",
                error_feedback=error_feedback)
            # the hatch DEGRADED a requested hierarchy to one axis:
            # record it, so downstream topology-aware consumers (the
            # MoE two-stage dispatch) can warn precisely — a comm that
            # was never hierarchical must not trigger hatch warnings
            comm._hierarchy_flattened_by_env = True
            return _apply_autotune(comm, autotune)
    comm = MeshCommunicator(devices=devices, axis_name=axis_name,
                            allreduce_grad_dtype=allreduce_grad_dtype,
                            batch_collectives=batch_collectives,
                            bucket_mb=bucket_mb, name=name,
                            intra_size=intra_size, inter_size=inter_size,
                            error_feedback=error_feedback,
                            stripe_ratio=stripe_ratio)
    return _apply_autotune(comm, autotune)


def _apply_autotune(comm, autotune):
    """Resolve the factory's ``autotune=`` knob against a freshly built
    mesh communicator: measure+agree+apply now (``"startup"``), defer
    to the optimizer face (``"online"`` — the mode rides on the comm),
    or apply a RECORDED plan dict directly.  Both the retune and the
    clone it may build are collective, lock-step on every rank — the
    plan is agreed before anyone rebuilds."""
    if autotune in (None, False):
        return comm
    if isinstance(autotune, dict):
        return comm.retuned(autotune)
    if autotune == "online":
        comm._autotune_mode = "online"
        return comm
    comm._autotune_mode = "startup"
    from ._autotune import retune_communicator
    return retune_communicator(comm, mode="startup")


#: distinct degraded dicts already warned about (one-time per intent —
#: a training loop constructing communicators repeatedly must not spam)
_WARNED_FLAT_DICTS = set()

#: stripe ratios already warned about under the flat escape hatch
_WARNED_FLAT_STRIPES = set()

#: one-time latch for the MoE two-stage drop under the flat hatch
#: (ISSUE 12 satellite — same not-silent pattern as striping: the
#: caller asked for multi-fabric wire use and gets the single-axis
#: exchange instead)
_WARNED_FLAT_TWO_STAGE = set()


def _warn_hierarchy_flat_two_stage_dropped():
    """CHAINERMN_TPU_HIERARCHY=flat is active and an MoE dispatch that
    would have run the two-stage (ici → dcn) token exchange is running
    the flat single-axis ``all_to_all`` instead.  Warn once per process
    (``parallel.moe`` calls this at dispatch resolution time — the
    factory cannot know at construction that a communicator will carry
    MoE traffic)."""
    import warnings
    if _WARNED_FLAT_TWO_STAGE:
        return
    _WARNED_FLAT_TWO_STAGE.add(True)
    warnings.warn(
        "CHAINERMN_TPU_HIERARCHY=flat drops two-stage MoE routing: the "
        "flat one-axis alias has a single fabric, so token dispatch "
        "runs the flat single-axis all_to_all (on-host tokens ride the "
        "same collective as off-host ones and the DCN crossing cannot "
        "be compressed separately).  Unset CHAINERMN_TPU_HIERARCHY to "
        "restore the two-stage ici × dcn dispatch.",
        UserWarning, stacklevel=4)


def _warn_hierarchy_flat_stripe_dropped(stripe_ratio):
    import warnings
    if stripe_ratio in _WARNED_FLAT_STRIPES:
        return
    _WARNED_FLAT_STRIPES.add(stripe_ratio)
    warnings.warn(
        f"CHAINERMN_TPU_HIERARCHY=flat drops stripe_ratio="
        f"{stripe_ratio}: the flat one-axis alias has a single fabric, "
        f"so the multi-path striped exchange degrades to the flat "
        f"single-path allreduce.  Unset CHAINERMN_TPU_HIERARCHY to "
        f"restore the striped two-fabric schedule.",
        UserWarning, stacklevel=3)


def _warn_hierarchy_flat_dict_degraded(dtype_dict, chosen_key, dropped):
    import warnings
    key = tuple(sorted((k, str(v)) for k, v in dtype_dict.items()))
    if key in _WARNED_FLAT_DICTS:
        return
    _WARNED_FLAT_DICTS.add(key)
    kept = dtype_dict.get(chosen_key)
    detail = (f"dropped per-hop entries {dropped} "
              if dropped else "per-hop structure dropped ")
    warnings.warn(
        f"CHAINERMN_TPU_HIERARCHY=flat degrades per-hop "
        f"allreduce_grad_dtype={dtype_dict!r} to its {chosen_key!r} "
        f"entry ({kept!r}) on the ONE flat hop: {detail}— the full "
        f"gradient now rides the {chosen_key} compression instead of "
        f"only that hop's chunk.  Unset CHAINERMN_TPU_HIERARCHY to "
        f"restore the two-level exchange.",
        UserWarning, stacklevel=3)
