"""Device time a step of the operations that belong to the vocabulary:
those whose HLO text, result or operand, carries a shape with the
configuration's ``vocab_size`` as a dimension (the head's GEMMs and
their logits, the loss's row reductions, the embedding's lookup and
gradient, Adam over both matrices), summed on the first chip inside the
traced window and divided by the step program's runs there.

A shape is what stands in square brackets (``bf16[4096,50257]``); tile
sizes in a layout's braces (``{0:T(512)}``) are not dimensions.
Operations that only hold others (loops, calls) are left out."""

import re

from benchmark import trace_reduce


def has_dimension(text, size):
    """Whether some shape in the HLO text has ``size`` as a dimension."""
    return re.search(rf"\[(?:\d+,)*{size}(?:,\d+)*\]", text) is not None


def read(view):
    t, lo, hi = view["trace"], view["lo"], view["hi"]
    vocab = view["run"].config.get("vocab_size")
    if not t.devices or not vocab:
        return None
    first = t.devices[0]
    needles = view["run"].traffic["programs"]["step"]
    steps = sum(1 for e in t.modules[first]
                if trace_reduce.is_match(e, needles)
                and e.start >= lo and e.end <= hi)
    if not steps:
        return None
    seconds = sum(e.dur for e in trace_reduce._clip(t.ops[first], lo, hi)
                  if has_dimension(e.name, vocab)
                  and not trace_reduce._is_container(e))
    return seconds * 1e3 / steps
