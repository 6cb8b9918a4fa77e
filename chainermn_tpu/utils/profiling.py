"""Profiling helpers (SURVEY.md §5 tracing note).

The reference's story was TimerHook/CupyMemoryProfileHook + nvprof; the
TPU rebuild rides ``jax.profiler`` (XProf/TensorBoard traces with HLO,
fusion, and ICI collective timelines) — strictly better out of the box.
These helpers wrap it in the framework's vocabulary, plus a trainer
extension that captures a trace window mid-run.  The ``dummy``
communicator remains the tool for compute-vs-communication attribution
(run the same script twice, diff the step times — the reference's own
methodology).
"""

from __future__ import annotations

import contextlib

import jax

from ..training.trainer import Extension

__all__ = ["trace", "annotate", "Profile"]


@contextlib.contextmanager
def trace(log_dir="/tmp/chainermn_tpu_trace"):
    """Capture a jax.profiler trace (open with TensorBoard/XProf)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def annotate(name):
    """Named scope visible in trace timelines (``jax.named_scope``): the
    name lands in the compiled program's ``op_name`` and on every device
    operation of a profile.  For the parts of a model use the ROLES
    (``chainermn_tpu.observability.role``: ``attn``, ``mlp``, ...), which
    are the names the benchmark's readers know; this alias is for names
    of your own (docs/observability.md, "Names on the device")."""
    return jax.named_scope(name)


class Profile(Extension):
    """Trainer extension: trace iterations [start, start+n_steps).

    ``trainer.extend(Profile(start=10, n_steps=3))`` captures steady-state
    steps (skipping compilation) into ``<out>/trace``.

    Leak contract (ISSUE 14 satellite): a run that ends — or RAISES —
    inside the trace window must still stop the trace.  Three layers
    close it:

    * ``on_error`` stops the trace the moment a failure escapes the
      training loop — BEFORE any recovery supervisor resumes, so a
      recovered run's capture cannot silently bleed across the failure
      (and a fail-stop run doesn't rely on finalizers at all);
    * ``finalize`` (the trainer's ``finally``) stops it on any exit,
      and ``Trainer.run`` exception-isolates the finalize fan-out so
      another extension's failing ``finalize`` can no longer starve
      this one (the leak the regression test pins);
    * ``_stop`` itself is idempotent and swallows ``stop_trace``'s own
      errors into a warning — a profiler wedge must not mask the
      original exception.
    """

    trigger = (1, "iteration")
    priority = 400  # before anything else each iteration

    def __init__(self, start=10, n_steps=3, log_dir=None):
        self.start = start
        self.n_steps = n_steps
        self.log_dir = log_dir
        self._active = False

    def __call__(self, trainer):
        it = trainer.updater.iteration
        if not self._active and it == self.start:
            jax.profiler.start_trace(
                self.log_dir or f"{trainer.out}/trace")
            self._active = True
        elif self._active and it >= self.start + self.n_steps:
            self._stop()

    def _stop(self):
        if not self._active:
            return
        self._active = False   # first: a failing stop must not re-fire
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — never mask the caller
            import warnings
            warnings.warn(f"jax.profiler.stop_trace failed while "
                          f"closing a Profile window: {e}", stacklevel=2)

    def on_error(self, trainer, exc, tb):
        self._stop()

    def finalize(self):
        self._stop()
