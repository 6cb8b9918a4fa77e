"""Mean ``batch`` over the window's ``serve/decode_window`` spans: the
lanes that decoded a step."""

import statistics

from benchmark import program_spans


def read(view):
    lanes = program_spans.stat(view, "serve/decode_window", "batch")
    return statistics.fmean(lanes) if lanes else None
