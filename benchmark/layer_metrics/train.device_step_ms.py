"""Median device duration of the step's XLA module (the program that
``optimizers._make_step`` compiles), on the first chip."""

from benchmark import trace_reduce


def read(view):
    needles = view["run"].traffic["programs"]["step"]
    runs = trace_reduce.module_runs(view["trace"], needles)
    value = trace_reduce.median_or_none(runs)
    return None if value is None else value * 1e3
