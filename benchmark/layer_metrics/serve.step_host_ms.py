"""The host's own work a step: ``serve/step`` less the
``serve/decode_fetch`` and prefill spans inside it, in which it waits
for the chip; median over the window's steps that decoded.  Beside
``serve.host_gap_ms``, which sees the same from the device."""

from benchmark import program_spans

WAITS = ("serve/decode_fetch", "serve/prefill", "serve/suffix_prefill")


def read(view):
    waited = {}
    for s in program_spans.spans(view):
        if s.name in WAITS:
            step = s.ancestor("serve/step")
            if step is not None:
                waited[id(step)] = waited.get(id(step), 0.0) + s.dur
    return program_spans.median_ms(
        [s.dur - waited[id(s)]
         for s in program_spans.named(view, "serve/step")
         if id(s) in waited])
