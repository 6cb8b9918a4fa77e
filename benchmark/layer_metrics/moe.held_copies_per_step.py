"""Mean token-copies a decode step that land on held experts, per expert
layer: the mean of ``held_copies`` over the window's
``serve/decode_window`` spans (the decode program counts them on the
device; lanes · k · held / experts if routing is even).  A program
without the stat gives None."""

import statistics

from benchmark import program_spans


def read(view):
    copies = program_spans.stat(view, "serve/decode_window", "held_copies")
    return statistics.fmean(copies) if copies else None
