"""The routed experts of a decode step against the memory roofline: the
bytes of the matrices of the experts that received a copy, over the
device's published bytes a second, against the device time a decode
step spends under roles ``router`` and ``experts`` (the router's
product, the top-k, the sort, the three grouped products and the
weighted sum back; ``benchmark/device_scopes.py``).

``held_hit`` of a ``serve/decode_window`` span is what the step's
program counted: the experts that received a copy, summed over layers.
Each is three matrices of ``hidden · expert width`` in bfloat16; an
expert no token chose need not be read, and the copies' own rows are
nothing beside a matrix.  The mean over the window's steps is set
against the mean device time a step.  A layer that reads every expert
whatever the routing reads low here by ``touched / experts``.  A
program without the count, or without roles, gives None."""

import statistics

from benchmark import device_scopes, program_spans

ITEM = 2


def expert_bytes(config):
    """One expert's three matrices."""
    return ITEM * 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def read(view):
    run = view["run"]
    if "moe_ffn_hidden_size" not in run.config:
        return None
    hit = program_spans.stat(view, "serve/decode_window", "held_hit")
    ms = device_scopes.role_ms(view, "decode", ("router", "experts"))
    if not hit or not ms:
        return None
    least = statistics.fmean(hit) * expert_bytes(run.config)
    return 100.0 * least / (run.peaks["hbm_gbps"] * 1e9) / (ms * 1e-3)
