"""Median device duration over the prefill programs (``prefill_program``
and its prefix variant), on the chip."""

from benchmark import trace_reduce


def read(view):
    needles = view["run"].traffic["programs"]["prefill"]
    value = trace_reduce.median_or_none(
        trace_reduce.module_runs(view["trace"], needles))
    return None if value is None else value * 1e3
