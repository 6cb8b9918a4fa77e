"""Median device-idle gap between consecutive engine programs (end of one
to start of the next) inside the window: the host's share of a decode
step."""

from benchmark import trace_reduce


def read(view):
    programs = view["run"].traffic["programs"]
    needles = tuple(programs["decode"]) + tuple(programs["prefill"])
    gaps = trace_reduce.gaps_between(view["trace"], needles,
                                     view["lo"], view["hi"])
    value = trace_reduce.median_or_none(gaps)
    return None if value is None else value * 1e3
