"""The decode program against the memory roofline: the least bytes a
sound decode step has to read, over the device's published bytes a
second, against the device time of the decode program's runs.

Least bytes of one step, from the configuration's shapes and what the
step's ``serve/decode_window`` span counted (bfloat16, 2 bytes):

* the weights outside the routed experts that every step reads whole:
  each layer's q, k, v, gate and output projections and norms, the dense
  layer's SwiGLU, each expert layer's router and shared expert, the
  final norm and the head (the embedding is not counted: a step reads a
  row a lane of it);
* ``held_hit`` routed experts (those that received a copy, summed over
  expert layers), three matrices each: an expert no token chose need
  not be read;
* the cache: ``ctx_tokens`` (the live lanes' contexts) in every
  full-attention layer and ``window_tokens`` (``min(context, window)``)
  in every sliding layer, K and V of ``[G, D]`` a token.

Nothing a later program could skip is counted, so the share cannot pass
100 %.  A step is paired with the program run that starts inside its
span.  A program without the span's counts gives None."""

from benchmark import program_spans, trace_reduce

ITEM = 2


def fixed_weights(config):
    """Parameters every decode step reads whole (the routed experts and
    the embedding left out)."""
    d, G, D = (config["hidden_size"], config["num_key_value_heads"],
               config["head_dim"])
    total = d + d * config["vocab_size"]            # final norm, head
    for i in range(config["num_hidden_layers"]):
        H = config["num_attention_heads_per_layer"][i]
        total += 2 * d * H * D + 2 * d * G * D + d * H + 2 * D + 2 * d
        if config["mlp_layer_types"][i] == "dense":
            total += 3 * d * config["intermediate_size"]
        else:
            total += (d + 1) * config["published"]["num_experts"] \
                + 3 * d * config["shared_expert_intermediate_size"]
    return total


def step_bytes(config, ctx_tokens, window_tokens, held_hit):
    n = config["num_hidden_layers"]
    sliding = sum(kind == "sliding_attention"
                  for kind in config["layer_types"][:n])
    entry = 2 * config["num_key_value_heads"] * config["head_dim"]
    expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return ITEM * (fixed_weights(config) + held_hit * expert
                   + entry * (ctx_tokens * (n - sliding)
                              + window_tokens * sliding))


def read(view):
    run = view["run"]
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
