"""Typed serving errors — the backpressure half of the PR 1 classification.

The resilience subsystem's rule (``communicators._host_channel``): a
failure crossing a subsystem boundary is a TYPED exception carrying the
diagnostics the supervisor needs to act, never a bare ``RuntimeError``
string.  Serving has two boundaries where load must push back instead
of corrupting state:

* admission (``submit``): the queue is a bounded buffer — a saturated
  tenant queue raises :class:`QueueSaturatedError` with the depths, so
  an ingress tier can shed load / retry-after instead of growing an
  unbounded host-side backlog;
* the page pool (``BlockAllocator``): exhaustion raises
  :class:`PagePoolExhaustedError` with the shortfall.  Inside the
  engine this is a *scheduling event* (preempt-by-eviction, recompute
  on re-admit); it escapes to the caller only at ``submit``, which
  rejects any request whose FULL eventual context (prompt +
  max_new_tokens) could never fit the pool — growth-time eviction can
  only free OTHER sequences' pages, so such a request would otherwise
  evict-and-readmit forever.

Both derive from :class:`ServingError` so ``except ServingError`` is
the one backpressure catch-point, mirroring ``ChannelError`` as the
host-channel catch-point.
"""

from __future__ import annotations

__all__ = ["ServingError", "PagePoolExhaustedError", "QueueSaturatedError",
           "EvictionStalledError", "UnsupportedProgramError"]


class ServingError(RuntimeError):
    """Base of the serving subsystem's typed errors."""


class PagePoolExhaustedError(ServingError):
    """The page pool cannot cover a requested allocation.

    Raised with the allocator state UNCHANGED (allocation is atomic:
    either every page of the request is granted or none is), so the
    scheduler can evict and retry without repair work."""

    def __init__(self, requested, free, total):
        self.requested = int(requested)
        self.free = int(free)
        self.total = int(total)
        super().__init__(
            f"page pool exhausted: need {self.requested} page(s), "
            f"{self.free}/{self.total} free")


class QueueSaturatedError(ServingError):
    """Admission backpressure: the tenant's wait queue is at its bound.

    Carries the tenant, its queue depth, and the bound so the caller
    can surface a retry-after instead of buffering unboundedly."""

    def __init__(self, tenant, depth, bound):
        self.tenant = tenant
        self.depth = int(depth)
        self.bound = int(bound)
        super().__init__(
            f"tenant {tenant!r} queue saturated ({self.depth}/{self.bound})"
            " — shed load or retry later")


class EvictionStalledError(ServingError):
    """Eviction cannot free a single page: every running sequence's
    pages are all SHARED (refcount > 1), so no victim's ``free`` would
    return anything to the pool and the pool-dry loop would spin
    forever (the round-14 prefix-sharing livelock).  Carries the
    running-batch size so a supervisor can decide between shedding load
    and growing the pool.  The victim policy accounts uniquely-owned
    pages and escalates youngest -> oldest before raising this."""

    def __init__(self, n_running):
        self.n_running = int(n_running)
        super().__init__(
            f"eviction stalled: none of the {self.n_running} running "
            "sequence(s) owns a uniquely-held page — evicting any of "
            "them would free nothing (all pages shared)")



class UnsupportedProgramError(ServingError):
    """The engine was asked for a program the model does not provide.

    A served model owns its block: the engine calls the model's
    ``serve_<program>``.  Prefill, suffix prefill and decode every model
    has; the speculative ``verify`` and the ``pool_sharding`` of
    tensor-parallel decode are a model's to offer.  Asking for one that
    is not there is refused here, at construction, never answered by
    another program in its place.  Carries the model's class name and
    the program."""

    def __init__(self, model, program):
        self.model = str(model)
        self.program = str(program)
        super().__init__(
            f"{self.model} has no serve_{self.program}: the engine "
            f"cannot run its {self.program!r} program for this model")
