"""The program's own spans, read from the run's ``.xplane.pb``.

While a profiler session records, ``chainermn_tpu.observability.span``
opens a ``jax.profiler.TraceAnnotation``: the program's ``train/...``
and ``serve/...`` spans land on plane ``/host:CPU`` of the same file as
the device's ``XLA Ops`` line, on the same clock, each with its counts
as the event's stats.  This module reads them (and the driver's own
``bench/...`` spans around them, which name what the program's spans
leave over) with ``jax.profiler.ProfileData``, as a tree per host
thread, for the readers under ``layer_metrics/``.

A program without such spans (every commit before PR 24) gives an empty
list, and every function here then gives an empty list or ``None``.
All times are seconds.
"""

from __future__ import annotations

import dataclasses
import statistics

from . import harness, trace_reduce

PROGRAM = ("train/", "serve/")
DRIVER = "bench/"


@dataclasses.dataclass
class Span:
    name: str
    thread: str
    start: float
    dur: float
    stats: dict
    parent: "Span | None" = None
    children_s: float = 0.0     # what its direct children cover

    @property
    def end(self):
        return self.start + self.dur

    @property
    def self_s(self):
        """The span's duration less what its children on the same
        thread cover."""
        return self.dur - self.children_s

    def ancestor(self, name):
        p = self.parent
        while p is not None and p.name != name:
            p = p.parent
        return p


def load(path):
    """Every ``train/``, ``serve/`` and ``bench/`` event of the host
    plane, by start, each linked to the span on its thread that
    encloses it."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            for e in line.events:
                if e.name.startswith(PROGRAM) or e.name.startswith(DRIVER):
                    spans.append(Span(e.name, thread, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, dict(e.stats)))
    return link(spans)


def link(spans):
    """Sort by start and set each span's ``parent`` and ``children_s``."""
    spans = sorted(spans, key=lambda s: (s.start, -s.dur))
    open_by_thread = {}
    for s in spans:
        stack = open_by_thread.setdefault(s.thread, [])
        while stack and s.end > stack[-1].end + 1e-9:
            stack.pop()
        if stack:
            s.parent = stack[-1]
            stack[-1].children_s += s.dur
        stack.append(s)
    return spans


def spans(view):
    """The run's spans that lie inside the window, parsed once a run
    and kept in the view.  The first call also says, on an earlier
    output line, where the device's idle seconds lie."""
    if "program_spans" not in view:
        # a result without a trace directory (a test's stand-in) has none
        trace_dir = getattr(view["result"].get("tracing"), "dir", None)
        found = load(trace_reduce.find_xplane(trace_dir)) \
            if trace_dir else []
        lo, hi = view["lo"], view["hi"]
        view["program_spans"] = [s for s in found
                                 if s.start >= lo and s.end <= hi]
        if view["trace"].devices \
                and any(s.name.startswith(PROGRAM) for s in found):
            say_idle(view, [s for s in found
                            if s.end > lo and s.start < hi])
    return view["program_spans"]


def named(view, name):
    return [s for s in spans(view) if s.name == name]


def durations(view, name):
    return [s.dur for s in named(view, name)]


def self_time(view, name):
    return [s.self_s for s in named(view, name)]


def stat(view, name, key):
    """The values of one stat over the spans of that name that carry
    it."""
    return [s.stats[key] for s in named(view, name) if key in s.stats]


def median_ms(values):
    return statistics.median(values) * 1e3 if values else None


def per_step_ms(view, names):
    """Median over steps of the summed durations of the spans named.  A
    step's spans are those that share an enclosing span (the driver's
    ``bench/update``); a step counts where that span lies wholly inside
    the window and holds every name, so one cut at the window's edge
    lends no span to its neighbour."""
    steps = {}
    for s in spans(view):
        if s.name in names and s.parent is not None:
            steps.setdefault(id(s.parent), []).append(s)
    lo, hi = view["lo"], view["hi"]
    return median_ms([
        sum(s.dur for s in step) for step in steps.values()
        if step[0].parent.start >= lo and step[0].parent.end <= hi
        and {s.name for s in step} == set(names)])


# -- the device's idle time under the host's spans ----------------------------

def device_idle(view):
    """``[(start, end)]`` inside the window in which no operation ran on
    the first device."""
    trace = view["trace"]
    if not trace.devices:
        return []
    d = trace.devices[0]
    busy = trace_reduce.union(trace.ops[d] or trace.modules[d])
    return trace_reduce.subtract([(view["lo"], view["hi"])], busy)


def overlap(a, b):
    """Seconds that two sorted lists of disjoint intervals share."""
    return sum(shared(a, b))


def shared(a, b):
    """For each interval of ``a`` the seconds it shares with ``b``; both
    sorted by start, the intervals of ``b`` disjoint, those of ``a``
    too."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, got = j, 0.0
        while k < len(b) and b[k][0] < hi:
            got += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
        out.append(got)
    return out


def idle_under(view, names):
    """Seconds in which no operation ran on the first device and a span
    of one of ``names`` was open: what the device waited through there."""
    mine = [s for s in spans(view) if s.name in names]
    if not mine or not view["trace"].devices:
        return None
    return overlap(trace_reduce.union(mine), device_idle(view))


def idle_by_innermost(all_spans, idle):
    """``{span name: seconds}``: each idle second under the innermost
    span open on its thread (spans open at once on two threads both
    count it); ``outside`` is what no span covers."""
    kids = {}
    for s in all_spans:
        if s.parent is not None:
            kids.setdefault(id(s.parent), []).append(s)
    by_name = {}
    for thread in {s.thread for s in all_spans}:
        own = sorted((piece, s.name) for s in all_spans
                     if s.thread == thread
                     for piece in trace_reduce.subtract(
                         [(s.start, s.end)],
                         trace_reduce.union(kids.get(id(s), ()))))
        for (_, name), got in zip(own, shared([p for p, _ in own], idle)):
            by_name[name] = by_name.get(name, 0.0) + got
    by_name["outside"] = trace_reduce.covered(idle) \
        - overlap(trace_reduce.union(all_spans), idle)
    return by_name


def say_idle(view, all_spans):
    idle = device_idle(view)
    rows = idle_by_innermost(all_spans, idle)
    harness.say({"device_idle_s_by_innermost_span": dict(sorted(
        rows.items(), key=lambda kv: -kv[1])),
        "device_idle_s": trace_reduce.covered(idle),
        "window_s": view["window_s"]})
