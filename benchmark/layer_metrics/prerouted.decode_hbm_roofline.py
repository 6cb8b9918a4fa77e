"""The decode program of the ``smallthinker`` block against the memory
roofline: the least bytes a sound decode step has to read, over the
device's published bytes a second, against the device time of the
decode program's runs.  It is ``serve.decode_hbm_roofline`` with this
configuration's keys: the share of the WHOLE step.

Least bytes of one step, from the configuration's shapes and what the
step's ``serve/decode_window`` span counted (bfloat16, 2 bytes):

* the weights every step reads whole: each layer's q, k, v and output
  projections, its router and its two norms, the final norm and the
  head (the embedding is not counted: a step reads a row a lane of it);
* ``held_hit`` experts (those that received a copy, summed over
  layers), three matrices each: an expert no token chose need not be
  read;
* the cache: ``ctx_tokens`` (the live lanes' contexts) in every full
  layer and ``window_tokens`` (``min(context, window)``) in every
  window layer, K and V of ``[G, D]`` a token.

Nothing a later program could skip is counted, so the share cannot pass
100 %.  A step is paired with the program run that starts inside its
span.  A program without the span's counts gives None."""

from benchmark import program_spans, trace_reduce

ITEM = 2


def fixed_weights(config):
    """Parameters every decode step reads whole (the experts and the
    embedding left out)."""
    d, D = config["hidden_size"], config["head_dim"]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    layer = 2 * d * H * D + 2 * d * G * D \
        + d * config["moe_num_primary_experts"] + 2 * d
    return config["num_hidden_layers"] * layer \
        + d + d * config["vocab_size"]


def step_bytes(config, ctx_tokens, window_tokens, held_hit):
    n = config["num_hidden_layers"]
    windowed = sum(config["sliding_window_layout"][:n])
    entry = 2 * config["num_key_value_heads"] * config["head_dim"]
    expert = 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]
    return ITEM * (fixed_weights(config) + held_hit * expert
                   + entry * (ctx_tokens * (n - windowed)
                              + window_tokens * windowed))


def read(view):
    run = view["run"]
    if "sliding_window_layout" not in run.config:
        return None
    steps = [s for s in program_spans.named(view, "serve/decode_window")
             if {"ctx_tokens", "window_tokens", "held_hit"} <= set(s.stats)]
    trace = view["trace"]
    if not steps or not trace.devices:
        return None
    needles = run.traffic["programs"]["decode"]
    runs = sorted((e for e in trace.modules[trace.devices[0]]
                   if trace_reduce.is_match(e, needles)),
                  key=lambda e: e.start)
    least = seconds = 0.0
    j = 0
    for s in steps:                 # spans come sorted by start
        while j < len(runs) and runs[j].start < s.start:
            j += 1
        if j < len(runs) and runs[j].start < s.end:
            least += step_bytes(run.config, s.stats["ctx_tokens"],
                                s.stats["window_tokens"],
                                s.stats["held_hit"])
            seconds += runs[j].dur
    if not seconds:
        return None
    return 100.0 * least / (run.peaks["hbm_gbps"] * 1e9) / seconds
