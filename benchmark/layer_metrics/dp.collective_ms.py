"""Device time of the collective operations a step, averaged over the
chips, over the step program's runs on the first chip inside the window.

JAX names an instruction after its primitive (on the chip the gradient
all-reduce is ``%psum.14 = f32[406286336] all-reduce(...)``), so a
collective is known by its opcode in the event's HLO text, behind
`` = ``, and not by its name, which is all
``trace_reduce.collective_seconds`` looks at."""

import re

from benchmark import trace_reduce

OPCODE = re.compile(r"(?<=\s)(all-reduce|all-gather|reduce-scatter|"
                    r"all-to-all|collective-permute)(-start|-done)?\(")


def _text(event):
    return " " + event.name.partition(" = ")[2]


def collective_seconds(view):
    """(total, exposed) collective seconds inside the window, averaged
    over the devices: exposed is the part during which no other
    operation (loops, calls and conditionals, which only hold others,
    aside, as ``trace_reduce`` knows them) ran on that device."""
    lo, hi = view["lo"], view["hi"]
    totals, exposed = [], []
    for ops in view["trace"].ops.values():
        mine = [e for e in ops if e.end > lo and e.start < hi]
        coll = trace_reduce.union(
            [e for e in mine if OPCODE.search(_text(e))])
        coll = trace_reduce.subtract(
            coll, [(float("-inf"), lo), (hi, float("inf"))])
        rest = trace_reduce.union(
            [e for e in mine if not OPCODE.search(_text(e))
             and not trace_reduce._is_container(e)])
        totals.append(trace_reduce.covered(coll))
        exposed.append(trace_reduce.covered(
            trace_reduce.subtract(coll, rest)))
    n = max(len(totals), 1)
    return sum(totals) / n, sum(exposed) / n


def steps_in_window(view):
    t = view["trace"]
    if not t.devices:
        return 0
    needles = view["run"].traffic["programs"]["step"]
    return sum(1 for e in t.modules[t.devices[0]]
               if trace_reduce.is_match(e, needles)
               and e.start >= view["lo"] and e.end <= view["hi"])


def per_step_ms(view):
    """(total, exposed) collective milliseconds a step, or None where no
    collective ran."""
    steps = steps_in_window(view)
    total, exposed = collective_seconds(view)
    if not steps or total <= 0:
        return None
    return total * 1e3 / steps, exposed * 1e3 / steps


def read(view):
    both = per_step_ms(view)
    return None if both is None else both[0]
