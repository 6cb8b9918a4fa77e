"""The input stall the device felt: the seconds in which no operation
ran on the first chip while ``train/input_stall`` or ``train/convert``
was open, over the window's steps."""

from benchmark import program_spans

SPANS = ("train/input_stall", "train/convert")


def read(view):
    idle = program_spans.idle_under(view, SPANS)
    steps = len(program_spans.named(view, SPANS[0]))
    return idle * 1e3 / steps if idle is not None and steps else None
