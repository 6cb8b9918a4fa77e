"""Updaters (consumed-Chainer surface: ``chainer.training.updaters``).

Reference: ``chainer/training/updaters/standard_updater.py ·
StandardUpdater`` (SURVEY.md §3.2 call stack — ``trainer.run →
StandardUpdater.update → optimizer.update``).  The updater stays thin: the
whole compute step is inside ``Optimizer.update``'s jitted program.
"""

from __future__ import annotations

import time

from .. import observability
from ..dataset.convert import concat_examples

__all__ = ["Updater", "StandardUpdater", "FusedUpdater"]


class Updater:
    def connect_trainer(self, trainer):
        pass

    def finalize(self):
        pass

    def get_optimizer(self, name):
        raise NotImplementedError

    def get_all_optimizers(self):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def serialize(self, serializer):
        raise NotImplementedError


class StandardUpdater(Updater):
    def __init__(self, iterator, optimizer, converter=concat_examples,
                 device=None, loss_func=None, loss_scale=None):
        if not isinstance(iterator, dict):
            iterator = {"main": iterator}
        self._iterators = iterator
        if not isinstance(optimizer, dict):
            optimizer = {"main": optimizer}
        self._optimizers = optimizer
        self.converter = converter
        self.device = device
        self.loss_func = loss_func
        self.iteration = 0

    @property
    def epoch(self):
        return self._iterators["main"].epoch

    @property
    def epoch_detail(self):
        return self._iterators["main"].epoch_detail

    @property
    def previous_epoch_detail(self):
        return self._iterators["main"].previous_epoch_detail

    @property
    def is_new_epoch(self):
        return self._iterators["main"].is_new_epoch

    def get_optimizer(self, name="main"):
        return self._optimizers[name]

    def get_all_optimizers(self):
        return dict(self._optimizers)

    def get_iterator(self, name="main"):
        return self._iterators[name]

    def update(self):
        self.update_core()
        self.iteration += 1

    def update_core(self):
        iterator = self._iterators["main"]
        optimizer = self._optimizers["main"]
        batch = self._next_reporting_stall(iterator)
        with observability.span("train/convert"):
            in_arrays = self.converter(batch, self.device)
        loss_func = self.loss_func or optimizer.target
        with observability.span("train/optimizer_update"):
            if isinstance(in_arrays, tuple):
                optimizer.update(loss_func, *in_arrays)
            elif isinstance(in_arrays, dict):
                optimizer.update(loss_func, **in_arrays)
            else:
                optimizer.update(loss_func, in_arrays)
        if self.is_new_epoch:
            optimizer.new_epoch()

    @staticmethod
    def _report_stall_delta(iterator, stall_before):
        """Report the feed-stall accrued since ``stall_before`` into the
        current observation — LogReport can then surface how much of
        the input pipeline the overlap fails to hide, per iteration."""
        if stall_before is not None:
            from ..core.reporter import report
            report({"input_stall_ms":
                    iterator.input_stall_ms - stall_before})

    @classmethod
    def _record_stall_metric(cls, iterator, stall_before, t0):
        """ONE home for the universal input-stall counter semantics
        (ISSUE 14 satellite; both updater paths call this): accounted
        stall where the iterator measures it
        (``DevicePrefetchIterator.input_stall_ms`` — blocked-on-feed
        time, overlap subtracted), the pull's wall time where it does
        not (for a non-prefetching iterator the consumer is blocked
        for exactly that long) — labeled by iterator kind and updater
        path, pinned by the contract test."""
        stall_ms = (iterator.input_stall_ms - stall_before
                    if stall_before is not None
                    else (time.monotonic() - t0) * 1e3)
        observability.registry().counter(
            "chainermn_tpu_input_stall_ms_total",
            help="cumulative input-feed stall (ms) by iterator kind "
                 "and updater path").inc(
            stall_ms, iterator=type(iterator).__name__,
            updater=cls.__name__)

    @classmethod
    def _next_reporting_stall(cls, iterator):
        """``iterator.next()`` with the stall delta reported.

        Observation reporting keeps the original contract — only an
        iterator that ACCOUNTS its own stall reports into the
        per-iteration observation.  The observability counter
        (:meth:`_record_stall_metric`) is universal."""
        stall_before = getattr(iterator, "input_stall_ms", None)
        if not observability.enabled():
            batch = iterator.next()
            cls._report_stall_delta(iterator, stall_before)
            return batch
        # the stall counter lives in the registry, which is on with
        # the ring; a profiler session alone takes the span
        ring_on = observability.ring_enabled()
        t0 = time.monotonic() if ring_on else 0.0
        with observability.span(
                "train/input_stall",
                tags={"iterator": type(iterator).__name__}):
            batch = iterator.next()
        cls._report_stall_delta(iterator, stall_before)
        if ring_on:
            cls._record_stall_metric(iterator, stall_before, t0)
        return batch

    def finalize(self):
        for iterator in self._iterators.values():
            iterator.finalize()

    def serialize(self, serializer):
        self.iteration = int(serializer("iteration", self.iteration))
        for name, iterator in self._iterators.items():
            iterator.serialize(serializer["iterator:" + name])
        for name, optimizer in self._optimizers.items():
            optimizer.serialize(serializer["optimizer:" + name])


class FusedUpdater(StandardUpdater):
    """Runs ``n_fused`` optimizer steps per host dispatch.

    TPU-idiomatic tightening of the reference's update loop: pulls
    ``n_fused`` batches from the iterator, stacks them along a new
    leading step axis, and hands the stack to the multi-node optimizer's
    ``update_scan`` — ONE compiled program containing a ``lax.scan`` over
    the steps, so host/dispatch latency is paid once per K steps instead
    of per step.

    Semantics vs ``StandardUpdater``: ``iteration`` advances by
    ``n_fused`` per ``update()`` call, so iteration-interval triggers
    fire at dispatch granularity (a LogReport every 100 iterations still
    logs every 100 — just observed in K-sized jumps), and a stop trigger
    of ``(N, "iteration")`` stops at the first multiple of ``n_fused``
    ≥ N — pick ``N % n_fused == 0`` for an exact training budget;
    observations reported by the step reflect the last fused step.
    Requires a multi-node optimizer (``create_multi_node_optimizer``).
    """

    def __init__(self, iterator, optimizer, n_fused=4,
                 converter=concat_examples, device=None, loss_func=None,
                 loss_scale=None):
        super().__init__(iterator, optimizer, converter=converter,
                         device=device, loss_func=loss_func,
                         loss_scale=loss_scale)
        if n_fused < 1:
            raise ValueError("n_fused must be >= 1")
        self.n_fused = n_fused

    def update(self):
        self.update_core()
        self.iteration += self.n_fused

    def update_core(self):
        import jax.numpy as jnp
        iterator = self._iterators["main"]
        optimizer = self._optimizers["main"]
        if not hasattr(optimizer, "update_scan"):
            raise TypeError("FusedUpdater requires a multi-node optimizer "
                            "(create_multi_node_optimizer)")
        epoch_before = iterator.epoch
        # one stall observation across all K pulls (per-pull reports
        # would overwrite each other inside a single observation)
        stall_before = getattr(iterator, "input_stall_ms", None)
        # lazy tags (the near-zero-cost-off contract — same pattern as
        # _next_reporting_stall and the serving engine)
        obs_on = observability.enabled()
        ring_on = observability.ring_enabled()
        t0 = time.monotonic() if ring_on else 0.0
        with observability.span(
                "train/input_stall",
                tags={"iterator": type(iterator).__name__,
                      "n_fused": self.n_fused} if obs_on else None):
            batches = [self.converter(iterator.next(), self.device)
                       for _ in range(self.n_fused)]
        self._report_stall_delta(iterator, stall_before)
        if ring_on:
            # the shared counter semantics (converter included here —
            # this path stacks K batches host-side, and that cost is
            # exposed feed latency)
            self._record_stall_metric(iterator, stall_before, t0)
        loss_func = self.loss_func or optimizer.target
        first = batches[0]
        with observability.span(
                "train/optimizer_update",
                tags={"n_fused": self.n_fused} if obs_on else None):
            if isinstance(first, tuple):
                stacked = tuple(jnp.stack([b[i] for b in batches])
                                for i in range(len(first)))
                optimizer.update_scan(loss_func, *stacked)
            elif isinstance(first, dict):
                stacked = {k: jnp.stack([b[k] for b in batches])
                           for k in first}
                optimizer.update_scan(loss_func, **stacked)
            else:
                optimizer.update_scan(loss_func, jnp.stack(batches))
        # epoch boundaries can land on ANY of the K pulls (is_new_epoch
        # only reflects the last one) — fire new_epoch once per boundary
        # crossed so epoch-driven schedules stay in step
        for _ in range(iterator.epoch - epoch_before):
            optimizer.new_epoch()
