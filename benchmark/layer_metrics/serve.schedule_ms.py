"""The scheduler's own work a step: the self time of ``serve/capacity``
(the ensure-or-evict pass) plus that of ``serve/admission`` (the
admission loop; the prefills are its children, so left out); median over
the window's steps that had a batch running (the spins of an empty
engine between arrivals are left out)."""

from benchmark import program_spans

PASSES = ("serve/capacity", "serve/admission")


def read(view):
    per_step = {}
    for s in program_spans.spans(view):
        if s.name in PASSES and s.parent is not None \
                and s.parent.stats.get("running"):
            per_step[id(s.parent)] = per_step.get(id(s.parent), 0.0) \
                + s.self_s
    return program_spans.median_ms(list(per_step.values()))
