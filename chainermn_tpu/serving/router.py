"""Fleet router: admission + load shedding across live decode replicas.

The serving fleet's ingress tier (ISSUE 15): one host-side router owns
the request ledger and spreads admissions over the fleet's LIVE
replicas.  Policy pieces, mirroring the single-engine scheduler's
discipline one level up:

* **Per-tenant fair spread**: each tenant has its own persistent
  rotation cursor over the live replica list, so one tenant's flood
  spreads evenly across replicas AND two tenants' rotations are
  decorrelated (tenant A hammering replica 0 does not steer tenant B
  there too).  Rotation order is deterministic in the call sequence —
  the bench's seeded trace reproduces bit-identical placements.
* **Bounded per-replica queues** (typed backpressure): a replica whose
  tenant queue is saturated raises the existing
  :class:`~chainermn_tpu.serving.errors.QueueSaturatedError` from its
  own scheduler; the router SHEDS the request sideways to the next
  replica in rotation and only re-raises (the same typed error — the
  ingress classification is unchanged) when EVERY live replica refused.
  :class:`~chainermn_tpu.serving.errors.PagePoolExhaustedError` (the
  could-never-fit submit check) sheds the same way — identical pools
  will all refuse, heterogeneous fleets may not.
* **Reroute on replica loss**: the fleet's shed path
  (:meth:`~chainermn_tpu.serving.fleet.ReplicaFleet._shed`) calls back
  into :meth:`FleetRouter.route` with the dead replica excluded; the
  ledger (``request_id -> replica id``) is how the fleet knows which
  in-flight requests a remote replica held.

The router is pure host bookkeeping — no device state, no threads.
Every admission records a ``fleet/route`` span (ISSUE 14 vocabulary)
tagged with the granted replica and the number of sideways sheds.
"""

from __future__ import annotations

from .. import observability
from ..communicators._host_channel import ChannelError
from .errors import PagePoolExhaustedError, QueueSaturatedError, ServingError

__all__ = ["FleetRouter", "NoLiveReplicaError"]


class NoLiveReplicaError(ServingError):
    """The router has no live replica to admit into (the fleet shrank
    to nothing, or every replica was excluded).  Distinct from
    :class:`QueueSaturatedError`: there is no queue to wait on — the
    caller needs capacity, not patience."""

    def __init__(self, excluded=()):
        self.excluded = tuple(excluded)
        super().__init__(
            "no live replica to route to"
            + (f" (excluded: {list(self.excluded)})" if self.excluded
               else ""))


class FleetRouter:
    """Admission router over a :class:`~.fleet.ReplicaFleet` (or any
    object with a ``live_replicas()`` list of replica handles exposing
    ``rid``/``submit``/``queue_depth``).

    ``fleet`` is held by reference — the live set is re-read on every
    route, so replicas joining/leaving need no router surgery.
    """

    def __init__(self, fleet):
        self.fleet = fleet
        self._cursor = {}       # tenant -> monotone rotation counter
        self.routed = 0
        self.rerouted = 0
        self.spills = 0         # sideways sheds on saturation
        self.by_replica = {}    # rid -> admissions granted
        self.ledger = {}        # request_id -> rid (current placement)

    # -- placement -----------------------------------------------------------

    def _rotation(self, tenant, exclude):
        live = [r for r in self.fleet.live_replicas()
                if r.rid not in exclude]
        if not live:
            raise NoLiveReplicaError(exclude)
        k = self._cursor.get(tenant, 0) % len(live)
        return live[k:] + live[:k]

    def route(self, request, exclude=(), reroute=False):
        """Admit ``request`` into a live replica (typed backpressure).

        Tries the tenant's rotation order, shedding sideways past
        saturated replicas; re-raises the last typed error when every
        candidate refused.  Returns the granted replica id.
        ``exclude``: replica ids never considered (the fleet's shed
        path passes the dead replica).  ``reroute``: marks a replayed
        in-flight request (counted separately; span-tagged).
        """
        obs_on = observability.enabled()
        dead = []
        try:
            with observability.span(
                    "fleet/route",
                    tags={"tenant": request.tenant,
                          "request": request.request_id,
                          "reroute": reroute} if obs_on else None):
                order = self._rotation(request.tenant, exclude)
                last_exc = None
                for i, replica in enumerate(order):
                    try:
                        replica.submit(request)
                    except (QueueSaturatedError,
                            PagePoolExhaustedError) as e:
                        last_exc = e
                        self.spills += 1
                        continue
                    except ChannelError as e:
                        # a dead remote worker discovered at INGRESS
                        # (not just at step time): skip it for this
                        # placement and shed it below, so the replica
                        # does not stay live charging every future
                        # admission the full channel deadline
                        last_exc = e
                        dead.append(replica)
                        continue
                    self._cursor[request.tenant] = \
                        self._cursor.get(request.tenant, 0) + 1 + i
                    self.ledger[request.request_id] = replica.rid
                    self.by_replica[replica.rid] = \
                        self.by_replica.get(replica.rid, 0) + 1
                    self.routed += 1
                    if reroute:
                        self.rerouted += 1
                    if observability.ring_enabled():
                        observability.instant(
                            "fleet/route",
                            tags={"replica": replica.rid,
                                  "request": request.request_id,
                                  "spills": i, "reroute": reroute})
                    return replica.rid
                # every live replica refused: surface the typed
                # classification unchanged (the caller's retry-after
                # contract)
                raise last_exc
        finally:
            # shed channel-dead replicas AFTER this placement resolved
            # (their own outstanding work then replays through the
            # fleet's shed path; recursion is bounded by replica count)
            shed = getattr(self.fleet, "preempt", None)
            for replica in dead:
                if replica.live and shed is not None:
                    shed(replica.rid, exc=last_exc)

    # -- introspection -------------------------------------------------------

    def queue_depths(self, tenant=None):
        """``{rid: depth}`` over live replicas (per-tenant or total)."""
        return {r.rid: r.queue_depth(tenant)
                for r in self.fleet.live_replicas()}

    def pressure(self):
        """The deepest per-tenant backlog summed over live replicas —
        the same aggregation as the fleet's queue-depth gauge (what
        the scale policy's water marks compare against), readable
        without the metrics registry.  ``0`` with no pending work."""
        totals = {}
        for replica in self.fleet.live_replicas():
            for tenant, d in replica.tenant_depths().items():
                totals[tenant] = totals.get(tenant, 0) + d
        return max(totals.values()) if totals else 0

    def placements(self, rid):
        """Request ids currently placed on replica ``rid`` (ledger
        view; completed requests are scrubbed by the fleet)."""
        return tuple(req_id for req_id, r in self.ledger.items()
                     if r == rid)
