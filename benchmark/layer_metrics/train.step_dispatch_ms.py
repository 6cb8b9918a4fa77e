"""Median of ``train/step_dispatch``: the call of the compiled step in
``_MultiNodeOptimizer.update``, from operands ready to the call
returning (the enqueue, and the wait where the device's queue is full)."""

from benchmark import program_spans


def read(view):
    return program_spans.median_ms(
        program_spans.durations(view, "train/step_dispatch"))
