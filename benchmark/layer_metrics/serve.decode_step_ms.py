"""Median device duration of the decode program (``decode_program`` under
the engine's jit), on the chip."""

from benchmark import trace_reduce


def read(view):
    needles = view["run"].traffic["programs"]["decode"]
    value = trace_reduce.median_or_none(
        trace_reduce.module_runs(view["trace"], needles))
    return None if value is None else value * 1e3
