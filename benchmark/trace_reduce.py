"""From the profiler's ``.xplane.pb`` to the numbers per-layer readers
ask for.  Read with ``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per run
of a compiled program (named ``jit_<function>(<fingerprint>)``) and
whose line ``XLA Ops`` has one event per HLO operation that ran, named
by its HLO instruction; the host plane ``/host:CPU`` carries the
driver's own ``TraceAnnotation`` spans (``bench/...``) on the thread
that made them.  Every plane's events are on one clock.

``load`` gives a plain ``Trace``; the functions below reduce it.  All
times are seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import statistics

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass
class Event:
    name: str            # an operation's name is its whole HLO text
    start: float
    dur: float

    @property
    def end(self):
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    modules: dict        # device plane name -> [Event] program runs
    ops: dict            # device plane name -> [Event] operations
    spans: list          # [Event] the driver's host spans

    @property
    def devices(self):
        return sorted(self.ops)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules, ops, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
                elif line.name == OP_LINE:
                    ops[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns * 1e-9,
                                           e.duration_ns * 1e-9))
    for d in modules:
        ops.setdefault(d, [])
    for d in ops:
        modules.setdefault(d, [])
    spans.sort(key=lambda e: e.start)
    return Trace(modules, ops, spans)


# -- intervals -------------------------------------------------------------------

def union(events):
    """Merged ``[(start, end)]`` of the events' intervals."""
    out = []
    for s, e in sorted((ev.start, ev.end) for ev in events if ev.dur > 0):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes):
    """The part of ``intervals`` that no interval of ``holes`` covers."""
    out = []
    holes = list(holes)
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def window(trace, between=None):
    """(start, end) of the traced window: the span named ``between``
    where the driver recorded one, else first to last device event."""
    if between:
        for s in trace.spans:
            if s.name == between:
                return s.start, s.end
    evs = [e for d in trace.devices for e in trace.ops[d] + trace.modules[d]]
    if not evs:
        raise ValueError("no operation ran on a device in this trace")
    return min(e.start for e in evs), max(e.end for e in evs)


def _clip(events, lo, hi):
    return [Event(e.name, max(e.start, lo), min(e.end, hi) - max(e.start, lo))
            for e in events if e.end > lo and e.start < hi]


def busy(trace, lo, hi):
    """Seconds in which an operation ran, averaged over the devices."""
    per = [covered(union(_clip(trace.ops[d] or trace.modules[d], lo, hi)))
           for d in trace.devices]
    return sum(per) / len(per) if per else 0.0


# -- programs and operations -------------------------------------------------------

def is_match(event, needles):
    """Whether the event's own name holds one of ``needles``: for an
    operation that is the instruction's name, ahead of `` = ``, not the
    operands named in the rest of its text."""
    head = event.name.split(" = ", 1)[0]
    return any(n in head for n in needles)


def module_runs(trace, needles, device=None):
    """Durations of the program runs whose name holds one of
    ``needles``, on one device (the first by default)."""
    d = device or (trace.devices[0] if trace.devices else None)
    if d is None:
        return []
    return [e.dur for e in trace.modules[d] if is_match(e, needles)]


def median_or_none(values):
    return statistics.median(values) if values else None


def op_seconds(trace, needles, lo, hi, device=None):
    """Summed duration (and count) of the operations that match, on one
    device, inside the window."""
    d = device or (trace.devices[0] if trace.devices else None)
    if d is None:
        return 0.0, 0
    evs = [e for e in _clip(trace.ops[d], lo, hi) if is_match(e, needles)]
    return sum(e.dur for e in evs), len(evs)


def collective_seconds(trace, lo, hi):
    """(total, exposed) collective time inside the window, averaged over
    the devices: exposed is the part during which no other operation ran
    on that device."""
    totals, exposed = [], []
    for d in trace.devices:
        evs = _clip(trace.ops[d], lo, hi)
        coll = [e for e in evs if is_match(e, COLLECTIVES)]
        rest = [e for e in evs if not is_match(e, COLLECTIVES)
                and not _is_container(e)]
        cu = union(coll)
        totals.append(covered(cu))
        exposed.append(covered(subtract(cu, union(rest))))
    n = max(len(totals), 1)
    return sum(totals) / n, sum(exposed) / n


def _is_container(event):
    """Operations that only hold others (loops, calls, conditionals):
    their interval spans their children's and says nothing of overlap."""
    base = event.name.split(" = ")[0].split(".")[0].lstrip("%")
    return base in ("while", "call", "conditional")


def short_name(name, width=120):
    """An operation's event name is its whole HLO text: keep the
    instruction's name and the start of what it computes."""
    head, _, rest = name.partition(" = ")
    return (head.lstrip("%") + " " + rest)[:width].strip()


def top_ops(trace, lo, hi, n=10):
    """``[[name, seconds], ...]``: operations by summed time on the first
    device, containers left out."""
    if not trace.devices:
        return []
    sums = {}
    for e in _clip(trace.ops[trace.devices[0]], lo, hi):
        if not _is_container(e):
            sums[e.name] = sums.get(e.name, 0.0) + e.dur
    return [[short_name(k), v] for k, v in sorted(
        sums.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace, lo, hi, n=10, outer="bench/window"):
    """``[[what the host was doing, seconds], ...]``: the time in which no
    operation ran on the first device, each gap named by the driver's
    span that covers most of it (the span around the whole window,
    ``outer``, names nothing), summed by name."""
    if not trace.devices:
        return []
    d = trace.devices[0]
    busy_iv = union(_clip(trace.ops[d] or trace.modules[d], lo, hi))
    gaps = subtract([(lo, hi)], busy_iv)
    named = {}
    for s, e in gaps:
        best, best_cover = "unattributed", 0.0
        for sp in trace.spans:
            if sp.start >= e:
                break
            if sp.name == outer:
                continue
            cover = min(sp.end, e) - max(sp.start, s)
            if cover > best_cover:
                best, best_cover = sp.name, cover
        named[best] = named.get(best, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(named.items(),
                                      key=lambda kv: -kv[1])[:n]]


def gaps_between(trace, needles, lo, hi):
    """Idle seconds on the first device between consecutive program runs
    that match ``needles`` (end of one to start of the next)."""
    if not trace.devices:
        return []
    d = trace.devices[0]
    runs = sorted((e for e in _clip(trace.modules[d], lo, hi)),
                  key=lambda e: e.start)
    out = []
    for a, b in zip(runs, runs[1:]):
        if is_match(a, needles) and is_match(b, needles):
            out.append(max(0.0, b.start - a.end))
    return out
