"""The decode program of a model that runs its layers several times
against the memory roofline: the least bytes a sound decode step has to
move, over the device's published bytes a second, against the device
time of the decode program's runs.

Least bytes of one step, from the configuration's shapes and what the
step's ``serve/decode_window`` span counted (``ctx_tokens``, ``passes``,
``batch``), all in bfloat16:

* every block's parameters ``passes`` times: pass ``r + 1`` of the first
  layer needs pass ``r`` of the last, and the blocks' 4.9 GB outlive no
  on-chip memory, so each pass reads them again;
* the final norm, the exit gate and the head once (a step reads a row a
  lane of the embedding);
* the cache: ``ctx_tokens`` (the live lanes' contexts) in every one of
  the ``passes x layers`` cache layers, K and V of ``[G, D]`` a token,
  read; and the live lanes' new entries, one a lane a cache layer,
  written.

Nothing a later program could skip is counted, so the share cannot pass
100 %.  A step is paired with the program run that starts inside its
span, as ``deltanet.decode_hbm_roofline`` pairs them.  A program without
the span's counts gives None."""

from benchmark import program_spans, trace_reduce


def block_weights(config):
    """Parameters of ONE pass over the blocks."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    D = config["head_dim"]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    layer = 2 * d * H * D + 2 * d * G * D + 3 * d * ff + 4 * d
    return config["num_hidden_layers"] * layer


def once_weights(config):
    """The final norm, the exit gate (with its bias) and the head."""
    d = config["hidden_size"]
    return d + d + 1 + d * config["vocab_size"]


def entry_bytes(config):
    """What one token keeps in ONE cache layer: K then V, bfloat16."""
    return 2 * 2 * config["num_key_value_heads"] * config["head_dim"]


def step_bytes(config, ctx_tokens, passes, lanes):
    """``(least bytes of a step, the cache's part of them)``."""
    cache_layers = passes * config["num_hidden_layers"]
    cache = entry_bytes(config) * cache_layers * (ctx_tokens + lanes)
    weights = 2 * (passes * block_weights(config) + once_weights(config))
    return weights + cache, cache


def steps(view):
    """The window's decode steps that carry the loop's counts."""
    return [s for s in program_spans.named(view, "serve/decode_window")
            if {"ctx_tokens", "passes", "batch"} <= set(s.stats)]


def read(view):
    run = view["run"]
    trace = view["trace"]
    spans = steps(view)
    if not spans or not trace.devices:
        return None
    needles = run.traffic["programs"]["decode"]
    runs = sorted((e for e in trace.modules[trace.devices[0]]
                   if trace_reduce.is_match(e, needles)),
                  key=lambda e: e.start)
    least = seconds = 0.0
    j = 0
    for s in spans:                 # spans come sorted by start
        while j < len(runs) and runs[j].start < s.start:
            j += 1
        if j < len(runs) and runs[j].start < s.end:
            least += step_bytes(run.config, s.stats["ctx_tokens"],
                                s.stats["passes"], s.stats["batch"])[0]
            seconds += runs[j].dur
    if not seconds:
        return None
    return 100.0 * least / (run.peaks["hbm_gbps"] * 1e9) / seconds
