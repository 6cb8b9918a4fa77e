"""The decode program of a model with recurrent and full-attention
layers against the memory roofline: the least bytes a sound decode step
has to move, over the device's published bytes a second, against the
device time of the decode program's runs.

Least bytes of one step, from the configuration's shapes and what the
step's ``serve/decode_window`` span counted:

* every weight but the embedding, once, in bfloat16 (a step reads a row
  a lane of the embedding);
* the page cache: ``ctx_tokens`` (the live lanes' contexts) in every
  full-attention layer, K and V of ``[G, D]`` a token in bfloat16;
* the state cache: ``state_lanes`` (the live lanes) in every linear
  layer, the lane's slot READ AND WRITTEN: every head's ``dk x dv``
  state and the convolution's last ``taps - 1`` inputs, float32.

Nothing a later program could skip is counted, so the share cannot pass
100 %.  A step is paired with the program run that starts inside its
span, as ``serve.decode_hbm_roofline`` pairs them.  A program without
the span's counts gives None."""

from benchmark import program_spans, trace_reduce


def _layers(config):
    n = config["num_hidden_layers"]
    linear = sum(kind == "linear_attention"
                 for kind in config["layer_types"][:n])
    return linear, n - linear


def fixed_weights(config):
    """Parameters every decode step reads whole (the embedding left
    out)."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    H, dk, dv = (config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    channels = H * (2 * dk + dv)
    heads, G = config["num_attention_heads"], config["num_key_value_heads"]
    D = d // heads
    both = 3 * d * ff + 2 * d                       # SwiGLU, two norms
    linear = d * channels + 2 * d * H * dv + 2 * d * H \
        + channels * config["linear_conv_kernel_dim"] + 2 * H + dv
    full = 2 * d * heads * D + 2 * d * G * D + heads * D + G * D
    n_linear, n_full = _layers(config)
    return n_linear * (linear + both) + n_full * (full + both) \
        + d + d * config["vocab_size"]              # final norm, head


def slot_bytes(config):
    """What one sequence keeps in ONE linear layer: float32."""
    H, dk, dv = (config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    return 4 * (H * dk * dv + (config["linear_conv_kernel_dim"] - 1)
                * H * (2 * dk + dv))


def step_bytes(config, ctx_tokens, state_lanes):
    n_linear, n_full = _layers(config)
    entry = 2 * config["num_key_value_heads"] \
        * (config["hidden_size"] // config["num_attention_heads"])
    return 2 * fixed_weights(config) + 2 * entry * ctx_tokens * n_full \
        + 2 * slot_bytes(config) * state_lanes * n_linear


def read(view):
    run = view["run"]
    steps = [s for s in program_spans.named(view, "serve/decode_window")
             if {"ctx_tokens", "state_lanes"} <= set(s.stats)]
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
                                s.stats["state_lanes"])
            seconds += runs[j].dur
    if not seconds:
        return None
    return 100.0 * least / (run.peaks["hbm_gbps"] * 1e9) / seconds
