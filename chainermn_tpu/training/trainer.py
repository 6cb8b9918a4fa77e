"""Trainer loop (consumed-Chainer surface: ``chainer.training.Trainer``).

Reference: ``chainer/training/trainer.py · Trainer`` (SURVEY.md §2.8, §3.2).
Runs the updater until ``stop_trigger`` fires, invoking extensions by
priority inside a per-iteration ``Reporter`` observation scope — the exact
interposition surface the multi-node evaluator / checkpointer / log
extensions rely on.
"""

from __future__ import annotations

import collections
import os
import sys
import time
import traceback
import warnings

from ..core import reporter as reporter_module
from .triggers import get_trigger

__all__ = ["Trainer", "Extension", "make_extension",
           "PRIORITY_WRITER", "PRIORITY_EDITOR", "PRIORITY_READER"]

PRIORITY_WRITER = 300
PRIORITY_EDITOR = 200
PRIORITY_READER = 100


class Extension:
    """Base extension (reference: ``chainer/training/extension.py``)."""

    trigger = (1, "iteration")
    priority = PRIORITY_READER
    name = None

    @property
    def default_name(self):
        return type(self).__name__

    def __call__(self, trainer):
        raise NotImplementedError

    def initialize(self, trainer):
        pass

    def finalize(self):
        pass

    def on_error(self, trainer, exc, tb):
        pass

    def serialize(self, serializer):
        pass


def make_extension(trigger=(1, "iteration"), default_name=None,
                   priority=PRIORITY_READER, initializer=None):
    def decorator(ext):
        ext.trigger = trigger
        ext.default_name = default_name or getattr(ext, "__name__", "extension")
        ext.priority = priority
        if initializer is not None:
            ext.initialize = initializer
        return ext
    return decorator


class _ExtensionEntry:
    def __init__(self, extension, name, trigger, priority):
        self.extension = extension
        self.name = name
        self.trigger = get_trigger(trigger)
        self.priority = priority


class Trainer:
    def __init__(self, updater, stop_trigger=None, out="result"):
        self.updater = updater
        # None → train until interrupted (reference semantics)
        self.stop_trigger = get_trigger(stop_trigger) or (lambda trainer: False)
        self.out = out
        self.observation = {}
        self.reporter = reporter_module.Reporter()
        for name, optimizer in updater.get_all_optimizers().items():
            self.reporter.add_observer(name, optimizer.target)
            self.reporter.add_observers(
                name, optimizer.target.namedlinks(skipself=True))
        self._extensions = collections.OrderedDict()
        self._start_at = None
        self._snapshot_elapsed_time = 0.0
        self._done = False
        updater.connect_trainer(self)

    @property
    def elapsed_time(self):
        if self._start_at is None:
            return self._snapshot_elapsed_time
        return time.time() - self._start_at + self._snapshot_elapsed_time

    def extend(self, extension, name=None, trigger=None, priority=None,
               call_before_training=False):
        if name is None:
            name = getattr(extension, "name", None) or \
                getattr(extension, "default_name", None) or \
                getattr(extension, "__name__", None) or \
                type(extension).__name__
        if trigger is None:
            trigger = getattr(extension, "trigger", (1, "iteration"))
        if priority is None:
            priority = getattr(extension, "priority", PRIORITY_READER)
        original = name
        ordinal = 0
        while name in self._extensions:
            ordinal += 1
            name = f"{original}_{ordinal}"
        entry = _ExtensionEntry(extension, name, trigger, priority)
        entry.call_before_training = call_before_training
        self._extensions[name] = entry

    def get_extension(self, name):
        return self._extensions[name].extension

    def _fire_on_error(self, extensions, exc, tb):
        """Fire every extension's ``on_error`` (recovery prologue and
        crash epilogue alike).  A faulty handler must not mask the
        original failure or abort recovery, so handler exceptions are
        reported and swallowed."""
        for entry in extensions:
            on_error = getattr(entry.extension, "on_error", None)
            if on_error:
                try:
                    on_error(self, exc, tb)
                except Exception as handler_exc:
                    print(f"Exception in on_error of extension "
                          f"{entry.name}: {handler_exc}", file=sys.stderr)

    def _find_recovery(self, extensions):
        for entry in extensions:
            ext = entry.extension
            if hasattr(ext, "can_recover") and hasattr(ext, "recover"):
                return ext
        return None

    def run(self, show_loop_exception_msg=True):
        """Run the training loop until ``stop_trigger`` fires.

        Supervisor semantics (see ``docs/resilience.md``): if a
        :class:`~chainermn_tpu.extensions.FailureRecovery` extension is
        registered and the escaping exception is one it can recover, the
        trainer fires ``on_error`` on all extensions, hands the failure
        to the recovery extension (consensus checkpoint resume +
        transport quiesce + optional communicator rebuild), and re-enters
        the loop.  Unrecoverable failures keep the reference fail-stop
        path: ``on_error`` fan-out, then raise.
        """
        if self._done:
            raise RuntimeError("cannot run training loop multiple times")
        os.makedirs(self.out, exist_ok=True)
        extensions = sorted(self._extensions.values(),
                            key=lambda e: -e.priority)
        self._start_at = time.time()
        for entry in extensions:
            initializer = getattr(entry.extension, "initialize", None)
            if initializer:
                initializer(self)
        for entry in extensions:
            if getattr(entry, "call_before_training", False):
                entry.extension(self)
        update = self.updater.update
        recovery = self._find_recovery(extensions)
        try:
            while True:
                try:
                    while not self.stop_trigger(self):
                        self.observation = {}
                        with self.reporter.scope(self.observation):
                            update()
                            for entry in extensions:
                                if entry.trigger is None \
                                        or entry.trigger(self):
                                    entry.extension(self)
                    break
                except Exception as e:
                    tb = e.__traceback__
                    self._fire_on_error(extensions, e, tb)
                    if recovery is not None and recovery.can_recover(e):
                        if show_loop_exception_msg:
                            print("Recoverable exception in main training "
                                  "loop:", e, file=sys.stderr)
                        recovery.recover(self, e)
                        continue
                    if show_loop_exception_msg:
                        print("Exception in main training loop:", e)
                        traceback.print_exc()
                    raise
        finally:
            # exception-isolated: one extension's failing finalize must
            # not starve the others' cleanup (a Profile extension mid-
            # trace-window would leak an open jax.profiler trace —
            # ISSUE 14 satellite, pinned by regression test).  The
            # first finalize failure is re-raised after every finalizer
            # (and the updater's) has run — unless the loop itself is
            # already unwinding with an exception, which must win.
            finalize_exc = None
            for entry in extensions:
                finalize = getattr(entry.extension, "finalize", None)
                if finalize:
                    try:
                        finalize()
                    except BaseException as e:  # noqa: BLE001
                        print(f"Exception in finalize of extension "
                              f"{entry.name}: {e}", file=sys.stderr)
                        if finalize_exc is None:
                            finalize_exc = e
            # the updater's finalize rides the same isolation: its
            # failure must not swallow a captured extension-finalize
            # exception, nor skip the trace export below
            try:
                self.updater.finalize()
            except BaseException as e:  # noqa: BLE001
                print(f"Exception in updater.finalize: {e}",
                      file=sys.stderr)
                if finalize_exc is None:
                    finalize_exc = e
            self._done = True
            # observability (ISSUE 14): with tracing on, every run
            # leaves its rank's Chrome-trace shard next to its outputs
            # (merge shards with tools/trace_merge.py).  Off = the
            # default: no file, no cost.
            from .. import observability
            if observability.ring_enabled():
                try:
                    tr = observability.tracer()
                    tr.export(os.path.join(
                        self.out, f"trace-rank{tr.rank}.jsonl"))
                except Exception as e:  # noqa: BLE001 — never mask
                    print(f"trace export failed: {e}", file=sys.stderr)
            if finalize_exc is not None and sys.exc_info()[0] is None:
                raise finalize_exc

    def serialize(self, serializer):
        self.updater.serialize(serializer["updater"])
        if hasattr(self.stop_trigger, "serialize"):
            # Guarded like extension triggers: snapshots written before
            # triggers grew serialize() lack these keys, and a strict
            # reader would otherwise KeyError on resume.  The trigger
            # keeps its fresh state in that case.
            try:
                self.stop_trigger.serialize(serializer["stop_trigger"])
            except KeyError as e:
                # KeyError only — the strict reader's missing-key signal.
                # Corrupt present keys must still fail loudly, and the
                # writer must never silently drop state from a snapshot.
                if serializer.is_writer:
                    raise
                warnings.warn(
                    f"snapshot lacks stop-trigger state ({e}); the stop "
                    "trigger keeps its fresh (possibly partially "
                    "restored) state — snapshots written before triggers "
                    "gained serialize() resume this way by design",
                    stacklevel=2)
        s = serializer["extensions"]
        t = serializer["extension_triggers"]
        for name, entry in self._extensions.items():
            if hasattr(entry.extension, "serialize"):
                try:
                    entry.extension.serialize(s[name])
                except Exception:
                    pass
            if hasattr(entry.trigger, "serialize"):
                try:
                    entry.trigger.serialize(t[name])
                except Exception:
                    pass
        if serializer.is_writer:
            serializer("_snapshot_elapsed_time", self.elapsed_time)
        else:
            self._snapshot_elapsed_time = float(
                serializer("_snapshot_elapsed_time", 0.0))
