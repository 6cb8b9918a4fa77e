"""Elastic serving fleet: autoscaling decode replicas behind a router.

ISSUE 15 (ROADMAP item 3) — the first subsystem where training-side
resilience and inference-side scheduling share code paths.  A **fleet**
is a set of decode replicas, each a full
:class:`~chainermn_tpu.serving.engine.ServingEngine`, registered in an
:class:`~chainermn_tpu.communicators.ElasticMembership` group under the
serving role namespace (``<ns>/fleet`` — fully key-disjoint from the
training ``<ns>/elastic`` group sharing the same KV store), fronted by
a host-side :class:`~chainermn_tpu.serving.router.FleetRouter`.

Three moves, mirroring the elastic trainer's (``extensions/elastic.py``)
shrink/leave/grow on the inference side:

* **shed** — a replica preempt (:class:`RankPreempted` from the fault
  schedule / the real scheduler's signal, or a typed
  :class:`~chainermn_tpu.communicators.ChannelError` from a remote
  replica's dead worker) triggers detect → resolve (the membership
  consensus, leave-excluded fast path, settle-timeout backstop) → the
  dead replica's in-flight sequences REROUTE to survivors by replaying
  from their prompts.  This is the engine's own eviction/recompute path
  one level up: generated tokens fold into the prompt, the request
  re-queues, one prefill re-materializes the KV — so a kill under load
  drops ZERO requests and every rerouted sequence finishes with its
  solo-run trajectory (greedy decode is deterministic).  The p99 spike
  is bounded by the detection timeout (the typed channel deadline /
  the announced-leave fast path), chaos-gated.
* **join** — a cold replica announces ``join``, the resolve admits it,
  and its weights sync over a **multicast tree**
  (:func:`~chainermn_tpu.communicators.multicast_tree_plan`): the
  lowest survivor roots a binomial broadcast over ``{root} ∪ joiners``,
  so N joining replicas cold-start in ``ceil(log2(N + 1))`` transfer
  rounds instead of N sequential root bcasts.  Transfers ride the host
  channel's existing chunked object machinery cross-process
  (``send_obj``/``recv_obj``), or direct serialized copies in a
  single-controller fleet — bit-identical weights on every joiner
  either way (pinned).
* **scale** — :class:`QueueDepthScalePolicy` turns the PR 14 metrics
  registry's per-tenant fleet queue-depth gauges into +1/-1/0 scale
  decisions; the fleet SURFACES the decision (``step()`` stats) and
  applies it only through the explicit :meth:`ReplicaFleet.join` /
  :meth:`ReplicaFleet.retire` calls — capacity is the deployer's to
  grant.

Topology note: a single-controller fleet (the bench, tier-1 tests)
hosts every replica in-process and consensus degenerates to local
bookkeeping (:class:`_LocalConsensus` — same view surface, nothing to
agree with); a multi-controller fleet binds one
``ElasticMembership(role="fleet")`` per replica process and runs the
REAL protocol (the gloo chaos gate).  ``CHAINERMN_TPU_FLEET=off`` is
the escape hatch: the fleet clamps to ONE replica and the router
degenerates to a pass-through — single-engine serving, exactly PR 13's
shape.

Observability (ISSUE 14 vocabulary): spans ``fleet/route`` (router),
``fleet/shed`` (replica loss + reroute), ``fleet/weight_sync`` (tree
sync); counters ``chainermn_tpu_fleet_reroutes_total``; gauges
``chainermn_tpu_fleet_replicas`` and the per-tenant
``chainermn_tpu_fleet_queue_depth`` the scale policy reads.
"""

from __future__ import annotations

import os
import pickle
import time

import numpy as np

from .. import observability
from ..communicators._host_channel import ChannelError
from ..communicators._membership import (MembershipView,
                                         multicast_tree_plan)
from ..communicators.fault_schedule import RankPreempted
from ..extensions.failure_recovery import RecoveryGivingUp
from .errors import PagePoolExhaustedError, QueueSaturatedError
from .router import FleetRouter
from .scheduler import Request

__all__ = ["ReplicaFleet", "LocalReplica", "RemoteReplica", "FleetWorker",
           "QueueDepthScalePolicy", "fleet_mode", "serialize_state",
           "deserialize_state", "FLEET_ENV", "FLEET_ROLE",
           "FLEET_CTRL_TAG", "FLEET_SYNC_TAG"]

FLEET_ENV = "CHAINERMN_TPU_FLEET"
FLEET_ROLE = "fleet"
#: host-channel tags of the fleet's control / weight-sync planes (a
#: namespace of their own so fleet p2p never aliases user object p2p)
FLEET_CTRL_TAG = 7001
FLEET_SYNC_TAG = 7002


def fleet_mode(enabled=None):
    """Resolve the fleet knob: ``CHAINERMN_TPU_FLEET=off`` is the
    single-engine escape hatch and wins over everything (a one-replica
    fleet behaves exactly like the bare engine — pinned); otherwise the
    constructor's intent (default on — constructing a fleet means you
    want one).  Resolved ONCE at fleet construction, like the engine's
    paged-attention and disagg knobs."""
    if os.environ.get(FLEET_ENV, "").lower() == "off":
        return False
    return True if enabled is None else bool(enabled)


# -- weight payloads ---------------------------------------------------------

def serialize_state(state):
    """Engine state pytree -> bytes (host arrays, pickle).  Exact:
    fp32/bf16 leaves round-trip bit-identically — the joiner's adopted
    weights are byte-equal to the root's (pinned by the chaos gate)."""
    import jax
    leaves = [np.asarray(x) for x in jax.tree.leaves(state)]
    return pickle.dumps(leaves, protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_state(like, payload):
    """Bytes -> state pytree shaped like ``like`` (the joiner's own
    freshly built state supplies the treedef; the payload supplies
    every leaf's value)."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree.flatten(like)
    new = pickle.loads(payload)
    if len(new) != len(leaves):
        raise ValueError(f"weight payload has {len(new)} leaves, "
                         f"engine state has {len(leaves)}")
    return jax.tree.unflatten(treedef, [jnp.asarray(a) for a in new])


# -- replica handles ---------------------------------------------------------

class LocalReplica:
    """A decode replica hosted in THIS controller process: a thin
    handle over a :class:`~.engine.ServingEngine` giving the fleet the
    uniform surface (``submit``/``step``/``queue_depth``/
    ``drain_for_reroute``/``state_bytes``).

    ``kill_at``: seeded preemption — the replica raises
    :class:`RankPreempted` when its engine reaches that decode step
    (the chaos tests' kill-under-load injection point)."""

    remote = False

    def __init__(self, rid, engine, kill_at=None):
        self.rid = int(rid)
        self.engine = engine
        self.live = True
        self.kill_at = kill_at
        self._completed_seen = 0

    def submit(self, request):
        self.engine.submit(request)

    def step(self, now=None):
        if self.kill_at is not None \
                and self.engine.decode_steps >= self.kill_at:
            raise RankPreempted("fleet.step", self.engine.decode_steps,
                                rank=self.rid,
                                note="seeded replica preemption")
        return self.engine.step(now=now)

    def queue_depth(self, tenant=None):
        return self.engine.scheduler.pending(tenant)

    def tenant_depths(self):
        return self.engine.scheduler.tenant_depths()

    def can_ever_hold(self, request):
        """Whether this replica's pool could EVER serve the request
        (the engine's submit-time fit check, without submitting)."""
        total = int(request.prompt.size) + request.max_new_tokens
        return total <= self.engine.max_context \
            and self.engine.allocator.pages_for(total) \
            <= self.engine.allocator.num_pages

    def force_requeue(self, request):
        """Bound-exempt FRONT-OF-LINE enqueue for rerouted in-flight
        work: admission backpressure is an ingress contract, and a
        sequence that was already admitted once must not drop because
        the survivor's queue is momentarily full (the engine's own
        eviction requeue is bound-exempt for the same reason)."""
        self.engine.scheduler.requeue_front(request, preempted=False)

    def busy(self):
        # getattr: test doubles and pre-round-20 engine stand-ins have
        # no chunked-prefill pool
        return bool(self.engine.running
                    or getattr(self.engine, "prefilling", ())
                    or self.engine.scheduler.pending())

    def pop_completed(self):
        """Requests retired since the last poll (the fleet's ledger
        scrub + merged-completions feed)."""
        new = self.engine.completed[self._completed_seen:]
        self._completed_seen = len(self.engine.completed)
        return list(new)

    def drain_for_reroute(self, now=None):
        """Every in-flight sequence of a dead replica, ready to replay:
        running sequences fold their generated tokens into the prompt
        (the engine's eviction idiom — completed work is kept, its KV
        recomputed by the survivor's re-admit prefill) and queued ones
        come out in fairness order.  The requeue stamp books the gap
        until re-admission as queue wait (the detection-bounded p99
        spike the chaos gate measures), never as decode time."""
        sched = self.engine.scheduler
        # requeue stamp in the ENGINE's clock domain: the caller's
        # ``now`` when driving synthetic clocks, else the monotonic
        # clock engines default to — a missing stamp would book the
        # request's whole prior life (decode time included) as queue
        # wait at re-admission
        t_requeue = now if now is not None else time.monotonic()
        # a decode run the dead replica had in flight never lands: its
        # tokens are recomputed with the rest
        self.engine.drop_in_flight()
        for req in list(self.engine.running):
            self.engine.allocator.free(req.request_id)
            self.engine.running.remove(req)
            req.requeue_time = t_requeue
            sched.requeue_front(req)   # folds tokens, preemptions += 1
        for req in list(getattr(self.engine, "prefilling", ())):
            # mid-chunk prompts on the dead replica: no tokens yet, so
            # the fold is a no-op — the requeue resets their chunk
            # cursor and the survivor re-admits from chunk 0
            self.engine.allocator.free(req.request_id)
            self.engine.prefilling.remove(req)
            req.requeue_time = t_requeue
            sched.requeue_front(req)
        reqs = []
        while True:
            req = sched.next_admission(arrived_by=None)
            if req is None:
                break
            # never-admitted queued requests keep their arrival-based
            # wait accounting (no requeue stamp: their whole dwell IS
            # queue wait, on the dead replica or the survivor alike)
            reqs.append(req)
        return reqs

    def state_bytes(self):
        return serialize_state(self.engine.state)

    def adopt_state(self, payload):
        self.engine.state = deserialize_state(self.engine.state, payload)


class RemoteReplica:
    """Router-side handle to a replica served by ANOTHER controller
    process's :class:`FleetWorker`, over the host channel's chunked
    object machinery.  Each ``step()`` is one pump round-trip; a dead
    worker surfaces as the channel's typed timeout — the detection
    bound the chaos gate budgets.

    The handle keeps the ORIGINAL request objects it shipped
    (``outstanding``): on a preempt they replay from their prompts on a
    survivor — the remote side only ever mutated its own copies."""

    remote = True

    def __init__(self, rid, channel, process):
        self.rid = int(rid)
        self.channel = channel
        self.process = int(process)
        self.live = True
        self.kill_at = None
        self.outstanding = {}       # request_id -> original Request
        self.completed = []         # Requests finished remotely
        self._depths = {}           # tenant -> last reported depth

    def submit(self, request):
        self.channel.send_obj(
            ("admit", {"prompt": np.asarray(request.prompt,
                                            dtype=np.int32),
                       "max_new_tokens": request.max_new_tokens,
                       "tenant": request.tenant,
                       "request_id": request.request_id,
                       "arrival_time": request.arrival_time}),
            self.process, tag=FLEET_CTRL_TAG)
        kind, *rest = self.channel.recv_obj(self.process,
                                            tag=FLEET_CTRL_TAG)
        if kind == "saturated":
            raise QueueSaturatedError(*rest)
        if kind == "oom":
            raise PagePoolExhaustedError(*rest)
        assert kind == "ok", kind
        self.outstanding[request.request_id] = request

    def step(self, now=None):
        """One remote decode pump.  Raises the channel's typed errors
        when the worker is gone (``ChannelTimeoutError`` — the fleet's
        shed path catches it)."""
        self.channel.send_obj(("pump",), self.process,
                              tag=FLEET_CTRL_TAG)
        kind, report = self.channel.recv_obj(self.process,
                                             tag=FLEET_CTRL_TAG)
        assert kind == "pumped", kind
        t = time.monotonic() if now is None else now
        for req_id, toks, times in report["finished"]:
            req = self.outstanding.pop(req_id, None)
            if req is None:
                continue
            req.tokens = list(toks)
            req.token_times = list(times) if times else [t] * len(toks)
            if req.token_times:
                req.first_token_time = req.token_times[0]
            req.finish_time = t
            self.completed.append(req)
        self._depths = dict(report.get("depths", {}))
        return {"admitted": 0, "evicted": report.get("evicted", 0),
                "running": report.get("running", 0),
                "decoded": report.get("decoded", 0),
                "occupancy": report.get("occupancy", 0.0),
                "capacity_x": report.get("capacity_x", 1.0)}

    def stop(self):
        """Graceful worker shutdown (drain done)."""
        try:
            self.channel.send_obj(("stop",), self.process,
                                  tag=FLEET_CTRL_TAG)
            self.channel.recv_obj(self.process, tag=FLEET_CTRL_TAG)
        except ChannelError:
            pass

    def queue_depth(self, tenant=None):
        if tenant is not None:
            return self._depths.get(tenant, 0)
        return sum(self._depths.values())

    def tenant_depths(self):
        return dict(self._depths)

    def can_ever_hold(self, request):
        return True   # the remote submit's typed fit check decides

    def force_requeue(self, request):
        # no bound-exempt remote enqueue exists: the worker's submit
        # path (typed) is the only ingress — callers fall to the next
        # candidate on refusal
        self.submit(request)

    def busy(self):
        return bool(self.outstanding)

    def pop_completed(self):
        done, self.completed = self.completed, []
        return done

    def drain_for_reroute(self, now=None):
        """Replay set of a dead remote replica: everything shipped but
        never acked finished — replayed from the ORIGINAL prompts (the
        remote copies died with the worker; greedy decode regenerates
        the identical trajectory)."""
        reqs = list(self.outstanding.values())
        self.outstanding = {}
        t_requeue = now if now is not None else time.monotonic()
        for req in reqs:
            req.preemptions += 1
            req.requeue_time = t_requeue
        return reqs

    def state_bytes(self):
        raise NotImplementedError(
            "remote replicas ship weights worker-to-worker along the "
            "tree plan; the router only transfers on pairs it is an "
            "endpoint of")

    def adopt_state(self, payload):
        self.channel.send_obj(payload, self.process, tag=FLEET_SYNC_TAG)


class FleetWorker:
    """Replica-side serve loop of a multi-controller fleet: one engine,
    one process, driven by the router's control messages over the host
    channel (strict request/reply, so a wedge is always a TYPED timeout
    on the router side, never a hang).

    On a preemption (``kill_at`` reached, or the deployer's signal) the
    worker announces ``leave`` in the fleet membership group and stops
    replying — the router's next pump times out typed within the
    channel deadline, which is exactly the detection bound the chaos
    gate asserts."""

    def __init__(self, engine, channel, membership=None,
                 router_process=0):
        self.engine = engine
        self.channel = channel
        self.membership = membership
        self.router_process = int(router_process)
        self._reported = 0

    def _report(self):
        done = self.engine.completed[self._reported:]
        self._reported = len(self.engine.completed)
        return {
            "finished": [(r.request_id, list(r.tokens),
                          list(r.token_times)) for r in done],
            "depths": self.engine.scheduler.tenant_depths(),
            "running": len(self.engine.running),
        }

    def serve(self, kill_at=None, now=None):
        """Message loop; returns ``"preempted"`` or ``"stopped"``."""
        while True:
            msg = self.channel.recv_obj(self.router_process,
                                        tag=FLEET_CTRL_TAG)
            kind = msg[0]
            if kind == "admit":
                spec = msg[1]
                try:
                    self.engine.submit(Request(
                        spec["prompt"], spec["max_new_tokens"],
                        tenant=spec["tenant"],
                        arrival_time=spec["arrival_time"],
                        request_id=spec["request_id"]))
                except QueueSaturatedError as e:
                    self.channel.send_obj(
                        ("saturated", e.tenant, e.depth, e.bound),
                        self.router_process, tag=FLEET_CTRL_TAG)
                    continue
                except PagePoolExhaustedError as e:
                    self.channel.send_obj(
                        ("oom", e.requested, e.free, e.total),
                        self.router_process, tag=FLEET_CTRL_TAG)
                    continue
                self.channel.send_obj(("ok",), self.router_process,
                                      tag=FLEET_CTRL_TAG)
            elif kind == "pump":
                if kill_at is not None \
                        and self.engine.decode_steps >= kill_at:
                    # preempted: announce the leave (survivors skip the
                    # settle timeout) and go silent — the router's recv
                    # times out TYPED within the channel deadline
                    if self.membership is not None:
                        self.membership.announce_leave(
                            note="replica preempted")
                    return "preempted"
                st = self.engine.step(now=now)
                report = self._report()
                report.update(decoded=st["decoded"],
                              evicted=st["evicted"],
                              occupancy=st["occupancy"],
                              capacity_x=st["capacity_x"])
                self.channel.send_obj(("pumped", report),
                                      self.router_process,
                                      tag=FLEET_CTRL_TAG)
            elif kind == "stop":
                self.channel.send_obj(("stopped", self._report()),
                                      self.router_process,
                                      tag=FLEET_CTRL_TAG)
                return "stopped"
            else:
                raise ValueError(f"unknown fleet control message "
                                 f"{kind!r}")

    def sync_weights(self, view, joiners, root=None):
        """Walk the view's multicast tree plan from this worker's seat:
        receive the weight payload when this rank is a ``dst``, forward
        it when a later round names this rank a ``src``.  Pure-plan
        symmetric counterpart of :meth:`ReplicaFleet._sync_weights`."""
        me = self.membership.rank
        survivors = [m for m in view.members if m not in joiners]
        root = min(survivors) if root is None else root
        plan = multicast_tree_plan((root, *joiners), root=root)
        payload = None
        if me == root:
            payload = serialize_state(self.engine.state)
        for rnd in plan:
            for src, dst in rnd:
                if me == dst:
                    payload = self.channel.recv_obj(
                        src, tag=FLEET_SYNC_TAG)
                elif me == src:
                    self.channel.send_obj(payload, dst,
                                          tag=FLEET_SYNC_TAG)
        if me in joiners and payload is not None:
            self.engine.state = deserialize_state(self.engine.state,
                                                  payload)
        return len(plan)


# -- consensus (single-controller degenerate form) ---------------------------

class _LocalConsensus:
    """Membership surface of a single-controller fleet: every replica
    lives in this process, so there is nobody to disagree with — the
    'consensus' is epoch bookkeeping with the SAME view/role surface
    the real protocol produces (multi-controller fleets bind a real
    ``ElasticMembership(role='fleet')`` per replica process instead)."""

    role = FLEET_ROLE

    def __init__(self):
        self._epoch = 0
        self._members = ()

    def resolve(self, expect=None, require=None, timeout_ms=None):
        self._epoch += 1
        self._members = tuple(sorted(expect or ()))
        return MembershipView(self._epoch, self._members,
                              role=FLEET_ROLE)

    def current_epoch(self):
        return self._epoch

    def current_view(self):
        return MembershipView(self._epoch, self._members,
                              role=FLEET_ROLE)

    def pending_joins(self, view=None):
        return ()

    def announce_leave(self, note="", rank=None):
        pass

    def announce_join(self, note="", rank=None):
        pass


# -- scale policy ------------------------------------------------------------

class QueueDepthScalePolicy:
    """Scale decisions from the PR 14 metrics registry: reads the
    per-tenant ``chainermn_tpu_fleet_queue_depth`` gauges the fleet
    publishes every step and returns ``+1`` (any tenant's backlog above
    the ``scale_up_depth`` high-water mark and room below
    ``max_replicas``), ``-1`` (every tenant at or below the
    ``scale_down_depth`` low-water mark AND more than ``min_replicas``
    live), or ``0``.  Pure read — the fleet surfaces the decision;
    applying it is the deployer's `join`/`retire` call, or the ISSUE 16
    :class:`~chainermn_tpu.elastic.CapacityBroker` (capacity is
    granted, not conjured).

    Hysteresis (ISSUE 16 satellite): one sustained excursion past a
    water mark collapses to ONE decision.  After emitting in a
    direction, that direction is DISARMED until the gauge first
    returns inside the band (past the opposite side of its own mark),
    and — when the caller supplies ``now`` — until that direction's
    cooldown window has elapsed.  Distinct high/low marks plus the
    per-direction re-arm rule mean oscillating load cannot thrash
    +1/-1 every step the way the PR 15 stateless read did."""

    GAUGE = "chainermn_tpu_fleet_queue_depth"

    def __init__(self, scale_up_depth=8, scale_down_depth=0,
                 min_replicas=1, max_replicas=8,
                 up_cooldown_s=0.0, down_cooldown_s=0.0):
        self.scale_up_depth = float(scale_up_depth)
        self.scale_down_depth = float(scale_down_depth)
        if self.scale_down_depth > self.scale_up_depth:
            raise ValueError(
                f"scale_down_depth ({self.scale_down_depth}) must not "
                f"exceed scale_up_depth ({self.scale_up_depth})")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.up_cooldown_s = float(up_cooldown_s)
        self.down_cooldown_s = float(down_cooldown_s)
        self._armed = {1: True, -1: True}
        self._last_emit = {1: None, -1: None}

    def decide(self, registry, n_live, now=None):
        gauge = registry.gauge(self.GAUGE)
        depths = [gauge.value(**dict(key)) for key in gauge.labels()]
        depths = [d for d in depths if d is not None]
        if not depths:
            return 0
        peak = max(depths)
        # re-arm: a direction only becomes eligible again once the
        # gauge has crossed back past its own water mark
        if peak <= self.scale_up_depth:
            self._armed[1] = True
        if peak > self.scale_down_depth:
            self._armed[-1] = True
        if peak > self.scale_up_depth and n_live < self.max_replicas:
            want = 1
        elif peak <= self.scale_down_depth and n_live > self.min_replicas:
            want = -1
        else:
            return 0
        if not self._armed[want]:
            return 0  # same sustained excursion: already answered
        cooldown = self.up_cooldown_s if want == 1 else self.down_cooldown_s
        last = self._last_emit[want]
        if now is not None and last is not None \
                and now - last < cooldown:
            return 0  # inside this direction's cooldown window
        self._armed[want] = False
        if now is not None:
            self._last_emit[want] = now
        return want


# -- the fleet ---------------------------------------------------------------

class ReplicaFleet:
    """The replica set + supervisor (see module docstring).

    ``engine_factory``: ``factory(rid) -> ServingEngine`` — builds the
    initial replicas and any joiner the caller does not hand an engine
    (a joiner's factory-built weights are whatever the factory seeds;
    the tree sync overwrites them bit-identically from the root).
    ``replicas``: initial replica count (clamped to 1 under the
    ``CHAINERMN_TPU_FLEET=off`` hatch).
    ``engines``: pre-built ``{rid: engine-or-replica}`` instead of the
    factory (the gloo scenario attaches a :class:`RemoteReplica` here).
    ``membership``: a membership-protocol object for the fleet role
    group (default: the single-controller :class:`_LocalConsensus`; a
    multi-controller router passes its own real
    ``ElasticMembership(role="fleet")``).
    ``min_replicas``: shed floor — losing the last live replica (or
    shrinking below the floor) raises :class:`RecoveryGivingUp`
    carrying the FLEET-role view (the operator reads which group died).
    ``scale_policy``: optional :class:`QueueDepthScalePolicy`; its
    decision rides ``step()`` stats.
    """

    def __init__(self, engine_factory=None, replicas=2, engines=None,
                 membership=None, min_replicas=1, scale_policy=None,
                 enabled=None, clock=time.monotonic):
        self.enabled = fleet_mode(enabled)
        self.engine_factory = engine_factory
        self.membership = membership if membership is not None \
            else _LocalConsensus()
        self.min_replicas = int(min_replicas)
        self.scale_policy = scale_policy
        self._clock = clock
        self.replicas = {}
        if engines:
            for rid, eng in engines.items():
                self.replicas[int(rid)] = eng \
                    if isinstance(eng, (LocalReplica, RemoteReplica)) \
                    else LocalReplica(rid, eng)
        else:
            n = int(replicas) if self.enabled else 1
            if engine_factory is None:
                raise ValueError("ReplicaFleet needs engine_factory= "
                                 "or engines=")
            for rid in range(n):
                self.replicas[rid] = LocalReplica(rid,
                                                  engine_factory(rid))
        if not self.replicas:
            raise ValueError("a fleet needs at least one replica")
        # boot: adopt the membership's current view when it already
        # covers the replica set (the real protocol's bootstrap view —
        # remote workers are not in a resolve loop at construction);
        # resolve only when it does not (the local consensus, scripted
        # memberships, a recovered fleet)
        rids = [r.rid for r in self.replicas.values()]
        boot = self.membership.current_view() \
            if hasattr(self.membership, "current_view") else None
        if boot is not None and set(rids) <= set(boot.members):
            self.view = boot
        else:
            self.view = self._resolve(rids)
        self.completed = []
        self.steps = 0
        self.sheds = 0
        self.reroutes = 0
        self.joins = 0
        self.weight_syncs = 0
        self.weight_sync_s = 0.0
        self.weight_sync_rounds = 0
        self.weight_sync_bytes = 0
        self.last_detection_s = None
        self.router = FleetRouter(self)
        self._publish_gauges()

    # -- membership ----------------------------------------------------------

    def live_replicas(self):
        return [self.replicas[rid] for rid in sorted(self.replicas)
                if self.replicas[rid].live]

    def _resolve(self, members, require=None):
        view = self.membership.resolve(expect=set(members),
                                       require=require)
        return view

    def _fleet_view(self, members):
        """A FLEET-role view for diagnostics when no resolve can run
        (e.g. the last replica just died)."""
        epoch = self.membership.current_epoch() + 1
        return MembershipView(epoch, members, role=FLEET_ROLE)

    # -- ingress -------------------------------------------------------------

    def submit(self, request):
        """Route one request (typed backpressure surfaces unchanged)."""
        return self.router.route(request)

    # -- the step loop -------------------------------------------------------

    def step(self, now=None):
        """One fleet step: every live replica takes one decode step; a
        replica's typed failure sheds it (detect → resolve → reroute)
        without dropping a request.  Returns aggregated stats."""
        stats = {"admitted": 0, "decoded": 0, "running": 0,
                 "evicted": 0, "rerouted": 0}
        occ, cap = [], []
        for replica in self.live_replicas():
            try:
                st = replica.step(now=now)
            except (RankPreempted, ChannelError) as exc:
                stats["rerouted"] += self._shed(replica, exc, now=now)
                continue
            for k in ("admitted", "decoded", "running", "evicted"):
                stats[k] += st.get(k, 0)
            occ.append(st.get("occupancy", 0.0))
            cap.append(st.get("capacity_x", 1.0))
            for req in replica.pop_completed():
                self.router.ledger.pop(req.request_id, None)
                self.completed.append(req)
        stats["occupancy"] = float(np.mean(occ)) if occ else 0.0
        stats["capacity_x"] = float(np.mean(cap)) if cap else 1.0
        stats["replicas"] = len(self.live_replicas())
        self.steps += 1
        self._publish_gauges()
        if self.scale_policy is not None:
            stats["scale_decision"] = self.scale_policy.decide(
                observability.registry(), stats["replicas"], now=now)
        return stats

    def pending(self):
        """Live replicas still holding queued or running work."""
        return sum(1 for r in self.live_replicas() if r.busy())

    def drain(self, max_steps=10000, now=None):
        steps = 0
        while self.pending() and steps < max_steps:
            self.step(now=now)
            steps += 1
        return steps

    # -- shed (replica loss) -------------------------------------------------

    def _shed(self, replica, exc, now=None):
        """Detect → resolve → reroute.  Returns the reroute count."""
        t_detect = self._clock()
        observability.instant("fleet/preempt_detect",
                              tags={"replica": replica.rid,
                                    "exc": type(exc).__name__})
        with observability.span("fleet/shed",
                                tags={"replica": replica.rid,
                                      "exc": type(exc).__name__}):
            replica.live = False
            survivors = [r.rid for r in self.live_replicas()]
            if len(survivors) < self.min_replicas:
                raise RecoveryGivingUp(
                    f"fleet shrank below min_replicas="
                    f"{self.min_replicas}",
                    membership=self._fleet_view(survivors)) from exc
            self.view = self._resolve(survivors)
            reqs = replica.drain_for_reroute(now=now)
            self._reroute(reqs, exclude=(replica.rid,))
            self.sheds += 1
            self.reroutes += len(reqs)
            self.last_detection_s = self._clock() - t_detect
            observability.registry().counter(
                "chainermn_tpu_fleet_reroutes_total",
                help="in-flight sequences replayed onto survivors "
                     "after a replica loss").inc(len(reqs))
        self._publish_gauges()
        return len(reqs)

    def _reroute(self, reqs, exclude):
        """Replay ``reqs`` on survivors under the ZERO-DROP contract:
        a router refusal (saturation / fit check) must not abort the
        replay mid-list — a refused request forces FRONT-OF-LINE onto
        the least-loaded survivor whose pool could ever hold it
        (bound-exempt: backpressure is an ingress contract, not a
        license to drop admitted work).  Only a request NO survivor
        could ever serve re-raises, and only after every other request
        has been placed."""
        unserveable = None
        for req in reqs:
            try:
                self.router.route(req, exclude=exclude, reroute=True)
                continue
            except (QueueSaturatedError, PagePoolExhaustedError) as exc:
                candidates = sorted(
                    (r for r in self.live_replicas()
                     if r.rid not in exclude and r.can_ever_hold(req)),
                    key=lambda r: (r.queue_depth(), r.rid))
                for target in candidates:
                    try:
                        target.force_requeue(req)
                    except (QueueSaturatedError, PagePoolExhaustedError,
                            ChannelError):
                        continue
                    self.router.ledger[req.request_id] = target.rid
                    self.router.routed += 1
                    self.router.rerouted += 1
                    self.router.by_replica[target.rid] = \
                        self.router.by_replica.get(target.rid, 0) + 1
                    break
                else:
                    unserveable = unserveable or exc
        if unserveable is not None:
            raise unserveable

    def discard(self, rid):
        """Remove a replica that never went LIVE — the carcass a
        capacity conversion that died mid-``join`` leaves behind
        (``live=False`` replicas are never routed to, so its queues
        are empty by construction).  Live replicas must go through
        :meth:`preempt`/:meth:`retire` so their work reroutes."""
        replica = self.replicas.get(rid)
        if replica is None:
            return False
        if replica.live:
            raise ValueError(f"replica {rid} is live; use preempt() "
                             f"or retire(), not discard()")
        del self.replicas[rid]
        self._publish_gauges()
        return True

    def preempt(self, rid, exc=None, now=None):
        """Deployer/test-facing preemption: shed replica ``rid`` NOW
        (the in-process analog of the spot scheduler's reclaim
        signal).  ``now`` threads the caller's engine-clock value for
        the requeue stamps when driving synthetic clocks."""
        replica = self.replicas[rid]
        return self._shed(replica, exc or RankPreempted(
            "fleet.preempt", self.steps, rank=rid,
            note="capacity reclaimed"), now=now)

    # -- join (scale-up via the multicast tree) ------------------------------

    def join(self, engines=None, count=1, warmup=False):
        """Admit cold replica(s): resolve the grown view, then sync the
        root's weights over the multicast tree — ``ceil(log2(J + 1))``
        rounds for J joiners, each round's transfers independent (the
        O(log N) scale-up the fleet exists for).  Returns the new
        replica ids."""
        if not self.enabled:
            raise RecoveryGivingUp(
                "fleet is disabled (CHAINERMN_TPU_FLEET=off): a "
                "single-engine deployment cannot grow",
                membership=self.view)
        if engines is None:
            if self.engine_factory is None:
                raise ValueError("join() needs engines= or a fleet "
                                 "engine_factory")
            next_rid = max(self.replicas) + 1
            engines = {next_rid + i: self.engine_factory(next_rid + i)
                       for i in range(count)}
        elif not isinstance(engines, dict):
            engines = {max(self.replicas) + 1: engines}
        joiners = {}
        for rid, eng in engines.items():
            joiners[int(rid)] = eng \
                if isinstance(eng, (LocalReplica, RemoteReplica)) \
                else LocalReplica(rid, eng)
        survivors = [r.rid for r in self.live_replicas()]
        for rid, replica in joiners.items():
            replica.live = False       # live only once weights landed
            self.replicas[rid] = replica
        # the joiner announced its own join (remote workers do; local
        # consensus has nobody to tell) — the resolve admits it, with
        # require= the survivors so a joiner can never settle a world
        # by itself (the elastic split-brain guard, reused)
        self.view = self._resolve(set(survivors) | set(joiners),
                                  require=set(survivors))
        self._sync_weights(sorted(joiners), survivors)
        for rid in joiners:
            self.replicas[rid].live = True
            if warmup and not self.replicas[rid].remote:
                self.replicas[rid].engine.warmup()
        self.joins += len(joiners)
        self._publish_gauges()
        return sorted(joiners)

    def _sync_weights(self, joiners, survivors):
        """Tree-sync the root's weights to every joiner.  The tree is
        built over ``{root} ∪ joiners`` only — survivors already hold
        the weights, so (unlike the elastic snapshot bcast) no live
        replica downloads bytes it discards.  Per pair: local→local
        copies the serialized bytes directly; local→remote ships them
        over the host channel's chunked object machinery (the remote
        worker runs the symmetric :meth:`FleetWorker.sync_weights`
        walk); remote→remote pairs are entirely between the workers and
        the fleet does nothing."""
        if not joiners:
            return
        root = min(survivors)
        plan = multicast_tree_plan((root, *joiners), root=root)
        t0 = self._clock()
        with observability.span("fleet/weight_sync",
                                tags={"root": root,
                                      "joiners": list(joiners),
                                      "rounds": len(plan)}):
            payloads = {}   # rid -> bytes held in THIS process

            def local_payload(rid):
                if rid not in payloads:
                    payloads[rid] = self.replicas[rid].state_bytes()
                return payloads[rid]

            for rnd in plan:
                for src, dst in rnd:
                    src_rep = self.replicas.get(src)
                    dst_rep = self.replicas.get(dst)
                    if src_rep is None or dst_rep is None:
                        continue
                    if src_rep.remote and dst_rep.remote:
                        continue   # worker-to-worker transfer
                    if src_rep.remote:
                        # remote src -> local dst: the worker's walk
                        # sends on the sync tag; receive and adopt
                        payload = src_rep.channel.recv_obj(
                            src_rep.process, tag=FLEET_SYNC_TAG)
                    else:
                        payload = local_payload(src)
                    dst_rep.adopt_state(payload)
                    payloads[dst] = payload
                    self.weight_sync_bytes += len(payload)
            self.weight_sync_rounds += len(plan)
            self.weight_syncs += 1
        self.weight_sync_s += self._clock() - t0

    # -- scale-down ----------------------------------------------------------

    def retire(self, rid, now=None):
        """Graceful scale-down: the replica leaves AFTER its in-flight
        work reroutes (no detection timeout to pay — this is the
        announced-leave fast path)."""
        replica = self.replicas[rid]
        survivors = [r.rid for r in self.live_replicas()
                     if r.rid != rid]
        if len(survivors) < self.min_replicas:
            raise RecoveryGivingUp(
                f"retiring replica {rid} would shrink the fleet below "
                f"min_replicas={self.min_replicas}",
                membership=self._fleet_view(survivors))
        with observability.span("fleet/shed",
                                tags={"replica": rid, "retire": True}):
            replica.live = False
            # the leave belongs to the RETIRING replica's rank, not the
            # router's: over a real multi-controller membership, posting
            # it for self would exclude the router from its own resolve
            self.membership.announce_leave(note=f"retire {rid}",
                                           rank=rid)
            self.view = self._resolve(survivors)
            reqs = replica.drain_for_reroute(now=now)
            self._reroute(reqs, exclude=(rid,))
            self.reroutes += len(reqs)
            if replica.remote:
                replica.stop()
        self._publish_gauges()
        return len(reqs)

    # -- observability -------------------------------------------------------

    def _publish_gauges(self):
        """The PR 14 registry surface the scale policy reads: one
        per-tenant fleet-wide queue-depth gauge + the live replica
        count.  Published unconditionally — metrics are cheap host
        objects and the policy must work trace-off."""
        reg = observability.registry()
        depth = reg.gauge(
            "chainermn_tpu_fleet_queue_depth",
            help="pending requests per tenant, summed over live "
                 "replicas")
        totals = {}
        for replica in self.live_replicas():
            for tenant, d in replica.tenant_depths().items():
                totals[tenant] = totals.get(tenant, 0) + d
        for tenant, d in totals.items():
            depth.set(d, tenant=tenant)
        reg.gauge("chainermn_tpu_fleet_replicas",
                  help="live decode replicas").set(
            len(self.live_replicas()))

    def stats(self):
        return {"replicas": len(self.live_replicas()),
                "sheds": self.sheds, "reroutes": self.reroutes,
                "joins": self.joins,
                "weight_syncs": self.weight_syncs,
                "weight_sync_rounds": self.weight_sync_rounds,
                "weight_sync_bytes": self.weight_sync_bytes,
                "weight_sync_s": self.weight_sync_s,
                "last_detection_s": self.last_detection_s,
                "routed": self.router.routed,
                "rerouted": self.router.rerouted,
                "spills": self.router.spills}

    def __repr__(self):
        return (f"<ReplicaFleet replicas={sorted(self.replicas)} "
                f"live={[r.rid for r in self.live_replicas()]} "
                f"epoch={self.view.epoch}>")
