"""Mean experts of a layer that receive a copy in a decode step:
``held_hit`` of each ``serve/decode_window`` span (the experts that
received a copy, summed over layers; the decode program counts them on
the device) over the configuration's layers, the mean over the window's
steps.  Of ``moe_num_primary_experts`` (64): with ``n`` lanes routing
evenly, ``64 · (1 - (63/64)^(6 n))``.  What a decode step has to read
of the expert layer is this many experts' matrices.  A program without
the count gives None."""

import statistics

from benchmark import program_spans


def read(view):
    layers = view["run"].config.get("num_hidden_layers")
    hit = program_spans.stat(view, "serve/decode_window", "held_hit")
    if not hit or not layers:
        return None
    return statistics.fmean(hit) / layers
