"""Builder: ``chainermn_tpu.models.PreroutedMoELM`` from the published
keys of a ``smallthinker`` ``config.json``: every expert of each layer
held here (``moe_num_primary_experts``), the per-layer lists
(``rope_layout``, ``sliding_window_layout``) the published ones, read up
to ``num_hidden_layers``.  The parameters are constructed as shapes only
and served in ``param_dtype``."""

from __future__ import annotations

import math


def build(config, max_len=None):
    """The link, its parameters still shapes (nothing drawn or
    allocated)."""
    import jax
    import jax.numpy as jnp
    from chainermn_tpu.core.link import abstract_init
    from chainermn_tpu.models import PreroutedMoELM
    n = config["num_hidden_layers"]
    if config["rope_scaling"] is not None:
        raise ValueError("scaled rotary frequencies are not written for "
                         "this model")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the router written is a softmax over the chosen "
                         "logits: moe_primary_router_apply_softmax with "
                         "norm_topk_prob")
    if config["tie_word_embeddings"]:
        raise ValueError("a tied head is not written for this model")
    experts = config["moe_num_primary_experts"]
    with abstract_init():
        return PreroutedMoELM(
            n_vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_kv=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            layer_windows=[config["sliding_window_size"] if windowed
                           else None for windowed in
                           config["sliding_window_layout"][:n]],
            layer_rotary=[bool(r) for r in config["rope_layout"][:n]],
            rope_theta=config["rope_theta"],
            d_expert=config["moe_ffn_hidden_size"], n_experts=experts,
            held=(0, experts),
            k=config["moe_num_active_primary_experts"],
            activation=jax.nn.relu, eps=config["rms_norm_eps"],
            max_len=max_len or config["max_position_embeddings"],
            param_dtype=jnp.dtype(config["param_dtype"]))


# the depth the source trains: a residual stream's feed-forward updates
# are scaled for it, not for the layers held here
_PUBLISHED_LAYERS = 52


def init_rule(path, shape):
    """Embeddings N(0, 1), matrices LeCun normal (std 1/sqrt(fan_in):
    the router too, so that its choice counts), norm gains 1.  The
    experts' down-projections are LeCun normal times ``1 / sqrt(2 ·
    published layers)``, the scaled initialisation of a residual
    stream's output projections, as ``window_moe_lm.init_rule``'s: one
    routed expert's term is then a small part of the stream, so a top-6
    choice that rounding flips between two near-equal logits moves a
    token's logits by less than rounding itself does (PERF.md section 6,
    PR 31).  The attention's output projection keeps its LeCun normal,
    so that what the caches hold counts in the logits."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "gamma":
        return ("ones",)
    if "embed" in path:
        return ("normal", 1.0)
    if leaf in ("w_gate", "w_up"):      # [held, out, in]
        return ("normal", 1.0 / math.sqrt(shape[2]))
    # W [out, in], router [experts, in], w_down [held, in, out]
    std = 1.0 / math.sqrt(shape[1])
    if leaf == "w_down":
        std /= math.sqrt(2 * _PUBLISHED_LAYERS)
    return ("normal", std)
