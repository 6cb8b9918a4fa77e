"""The routed experts of a prefill (full and suffix alike), grouped by
sorting, against their roofline: the least time the chip could take for
the routed work of one prefill, from shapes and the program's own count,
over the device time a prefill spends under role ``experts`` (the
gather into expert order, the three grouped products, the activation
and the weighted sum back; ``benchmark/device_scopes.py``).

The routed work of one prefill, a layer: ``copies`` token-copies, each
through three products of ``2 · hidden · expert width`` FLOPs (gate, up,
down); bytes: every expert's three matrices read once, each copy's
hidden row read and its result written once (bfloat16).  ``copies`` is
what the program counted: ``held_copies`` of the window's
``serve/prefill`` and ``serve/suffix_prefill`` spans, the mean over
layers of the copies that landed on an expert (a prompt's own tokens
times the experts a token; the bucket's padding computes nothing and
counts nothing).  It reads the same work whatever implements the layer:
a form that computes every expert for every token does ``experts /
active`` times the products for the same count.  A program without the
count, or without roles, gives None."""

import statistics

from benchmark import device_scopes, flops, program_spans

ITEM = 2
SPANS = ("serve/prefill", "serve/suffix_prefill")


def layer_work(config, copies):
    """(FLOPs, bytes) of one layer's routed work for ``copies``
    token-copies."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    fl = copies * 3 * 2.0 * d * f
    by = ITEM * (config["moe_num_primary_experts"] * 3.0 * d * f
                 + copies * 2.0 * d)
    return fl, by


def read(view):
    config = view["run"].config
    if "moe_ffn_hidden_size" not in config:
        return None
    copies = [c for name in SPANS
              for c in program_spans.stat(view, name, "held_copies")]
    ms = device_scopes.role_ms(view, "prefill", ("experts",))
    if not copies or not ms:
        return None
    fl, by = layer_work(config, statistics.fmean(copies))
    n = config["num_hidden_layers"]
    share, _bound = flops.roofline_share(n * fl, n * by, ms * 1e-3,
                                         view["run"].peaks)
    return share
