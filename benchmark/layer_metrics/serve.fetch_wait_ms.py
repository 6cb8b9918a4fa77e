"""Median of ``serve/decode_fetch``: the host waiting for the chip's
next tokens.  It falls when the chip gets faster and rises when the
host does: read it beside ``serve.step_host_ms``."""

from benchmark import program_spans


def read(view):
    return program_spans.median_ms(
        program_spans.durations(view, "serve/decode_fetch"))
