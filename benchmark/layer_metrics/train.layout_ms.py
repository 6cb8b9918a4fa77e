"""Device time a step of the operations that only move data: those
whose instruction is named ``copy...``, ``slice...``, ``reshape...``,
``transpose...`` or ``pad...`` (the name ahead of `` = `` in the
event's HLO text, so ``copy.12``, ``copy-done.3``, ``slice-start.1`` and
a ``pad_maximum_fusion`` count, an operand called ``%copy.12`` in
another operation's text does not), summed on the first chip inside the
traced window and divided by the step program's runs there.

It counts what the rows form of the flash kernels removes (q, k, v and
the heads going into and out of ``[B, H, T, D]``): engaged, the step
program holds no such operation of head shape."""

from benchmark import trace_reduce

PREFIXES = ("copy", "slice", "reshape", "transpose", "pad")


def moves_data(event):
    return event.name.split(" = ", 1)[0].lstrip("%").startswith(PREFIXES)


def read(view):
    t, lo, hi = view["trace"], view["lo"], view["hi"]
    if not t.devices:
        return None
    first = t.devices[0]
    needles = view["run"].traffic["programs"]["step"]
    steps = sum(1 for e in t.modules[first]
                if trace_reduce.is_match(e, needles)
                and e.start >= lo and e.end <= hi)
    if not steps:
        return None
    seconds = sum(e.dur for e in trace_reduce._clip(t.ops[first], lo, hi)
                  if moves_data(e))
    return seconds * 1e3 / steps
