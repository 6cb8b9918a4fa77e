"""Builder: ``chainermn_tpu.models.WindowMoELM`` from the published keys
of a ``laguna`` ``config.json``, as one chip's share of an expert-parallel
group: the configuration's ``num_experts`` is what is held here,
``published.num_experts`` what the router scores, ``share.index`` which
of the shares this is.  The per-layer lists are the published ones, read
up to ``num_hidden_layers``.  The parameters are constructed as shapes
only and served in ``param_dtype``."""

from __future__ import annotations

import math


def build(config, max_len=None):
    """The link, its parameters still shapes (nothing drawn or
    allocated)."""
    import jax.numpy as jnp
    from chainermn_tpu.core.link import abstract_init
    from chainermn_tpu.models import WindowMoELM
    n = config["num_hidden_layers"]
    held = config["num_experts"]
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    windows = [config["sliding_window"] if kind == "sliding_attention"
               else None for kind in config["layer_types"][:n]]
    with abstract_init():
        return WindowMoELM(
            n_vocab=config["vocab_size"], d_model=config["hidden_size"],
            layer_heads=config["num_attention_heads_per_layer"][:n],
            layer_windows=windows,
            layer_dense=[kind == "dense"
                         for kind in config["mlp_layer_types"][:n]],
            n_kv=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            d_ff=config["intermediate_size"],
            d_expert=config["moe_intermediate_size"],
            n_experts=config["published"]["num_experts"],
            held=(config["share"]["index"] * held, held),
            k=config["num_experts_per_tok"],
            routed_scale=config["moe_routed_scaling_factor"],
            rope_full=dict(
                theta=full["rope_theta"], factor=full["factor"],
                original_max=full["original_max_position_embeddings"],
                beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
                attention_factor=full["attention_factor"],
                partial=full["partial_rotary_factor"]),
            rope_window=dict(theta=sliding["rope_theta"],
                             partial=sliding["partial_rotary_factor"]),
            eps=config["rms_norm_eps"],
            max_len=max_len or config["max_position_embeddings"],
            param_dtype=jnp.dtype(config["param_dtype"]))


# the depth the source trains: a residual stream's feed-forward updates
# are scaled for it, not for the layers held here
_PUBLISHED_LAYERS = 48
_FFN_OUT = ("mlp/down/W", "shared/down/W", "experts/w_down")


def init_rule(path, shape):
    """Embeddings N(0, 1), matrices LeCun normal (std 1/sqrt(fan_in):
    the gate and the router too, so that both paths count), norm gains
    1, the router's selection bias zeros (the config names none).  The
    feed-forward down-projections (dense, shared and routed) are LeCun
    normal times ``1 / sqrt(2 · published layers)``, the scaled
    initialisation of a residual stream's output projections: one
    routed expert's term (weight 0.25, a SwiGLU of RMS 0.6) is then a
    sixty-fifth of the stream and not a fourteenth, so a top-k choice
    that rounding flips between two near-equal scores moves a token's
    logits by less than rounding itself does (PERF.md section 6, PR
    31).  The attention's output projection keeps its LeCun normal, so
    that what the caches hold counts in the logits."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "gamma":
        return ("ones",)
    if leaf == "router_bias":
        return ("zeros",)
    if "embed" in path:
        return ("normal", 1.0)
    if leaf in ("w_gate", "w_up"):      # [held, out, in]
        return ("normal", 1.0 / math.sqrt(shape[2]))
    # W [out, in], router [experts, in], w_down [held, in, out]
    std = 1.0 / math.sqrt(shape[1])
    if path.endswith(_FFN_OUT):
        std /= math.sqrt(2 * _PUBLISHED_LAYERS)
    return ("normal", std)
