"""Median self time of ``train/optimizer_update``: what
``optimizer.update`` does around the compiled step's call (state
extraction, hyper-parameters, write-back, the reporter)."""

from benchmark import program_spans


def read(view):
    return program_spans.median_ms(
        program_spans.self_time(view, "train/optimizer_update"))
