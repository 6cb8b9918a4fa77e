"""Lanes that did work over lanes computed: the sum of ``batch`` over the
sum of ``bucket`` (the padded batch the decode program ran at) over the
window's ``serve/decode_window`` spans."""

from benchmark import program_spans


def read(view):
    computed = sum(program_spans.stat(view, "serve/decode_window", "bucket"))
    used = sum(program_spans.stat(view, "serve/decode_window", "batch"))
    return 100.0 * used / computed if computed else None
