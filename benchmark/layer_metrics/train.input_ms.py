"""The host's input work a step: ``train/input_stall``
(``iterator.next()``) plus ``train/convert`` (the converter: stacking the
batch and its transfer), the program's own spans on the profiler's
clock; median over the window's steps."""

from benchmark import program_spans


def read(view):
    return program_spans.per_step_ms(
        view, ("train/input_stall", "train/convert"))
