"""The decode program of a GPT-2-shaped model against the memory
roofline: the least bytes a sound decode step has to read, over the
device's published bytes a second, against the device time of the decode
program's runs.

Least bytes of one step, from the configuration's shapes and what the
step's ``serve/decode_window`` span counted (``ctx_tokens``):

* every parameter but the two embeddings, once, in bfloat16 (a step
  reads a row a lane of the token and position tables).  The program
  holds its parameters in float32 and casts what it multiplies; a later
  program would hold them in 2 bytes, so 2 is what is counted;
* the cache: ``ctx_tokens`` (the live lanes' contexts) in every layer, K
  and V of ``n_embd`` values a token in bfloat16.

Nothing a later program could skip is counted, so the share cannot pass
100 %.  A step is paired with the program run that starts inside its
span, as ``serve.decode_hbm_roofline`` pairs them.  A program without
the span's count gives None."""

from benchmark import program_spans, trace_reduce

ITEM = 2


def fixed_weights(config):
    """Parameters every decode step reads whole (the token and position
    embeddings left out): a block's qkv, output and two MLP matrices
    with their biases and its two norms, the final norm, the head (a
    matrix of its own, no bias)."""
    d = config["n_embd"]
    ff = config["n_inner"] or 4 * d
    block = (3 * d * d + 3 * d) + (d * d + d) + (d * ff + ff) \
        + (ff * d + d) + 4 * d
    return config["n_layer"] * block + 2 * d + d * config["vocab_size"]


def entry_bytes(config):
    """What one token keeps in ONE layer: K and V of ``n_embd``."""
    return ITEM * 2 * config["n_embd"]


def step_bytes(config, ctx_tokens):
    return ITEM * fixed_weights(config) \
        + entry_bytes(config) * config["n_layer"] * ctx_tokens


def read(view):
    run = view["run"]
    steps = [s for s in program_spans.named(view, "serve/decode_window")
             if "ctx_tokens" in s.stats]
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
            least += step_bytes(run.config, s.stats["ctx_tokens"])
            seconds += runs[j].dur
    if not seconds:
        return None
    return 100.0 * least / (run.peaks["hbm_gbps"] * 1e9) / seconds
