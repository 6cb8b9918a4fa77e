"""Builder: ``chainermn_tpu.models.LatentMoELM`` from the published keys
of a DeepSeek-V3-shaped ``config.json`` (``kimi_k2``), as one chip's share
of an expert-parallel group: the configuration's ``n_routed_experts`` is
what is held here, ``published.n_routed_experts`` what the router scores,
``share.index`` which of the shares this is.  The parameters are
constructed as shapes only and served in ``param_dtype``."""

from __future__ import annotations

import math


def build(config, max_len=None):
    """The link, its parameters still shapes (nothing drawn or
    allocated: the float32 weights of this cut fit the chip once)."""
    import jax.numpy as jnp
    from chainermn_tpu.core.link import abstract_init
    from chainermn_tpu.models import LatentMoELM
    held = config["n_routed_experts"]
    rs = config["rope_scaling"]
    with abstract_init():
        return LatentMoELM(
            n_vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_layers=config["num_hidden_layers"],
            n_dense=config["first_k_dense_replace"],
            q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], d_ff=config["intermediate_size"],
            d_expert=config["moe_intermediate_size"],
            n_experts=config["published"]["n_routed_experts"],
            held=(config["share"]["index"] * held, held),
            k=config["num_experts_per_tok"],
            routed_scale=config["routed_scaling_factor"],
            rope=dict(theta=config["rope_theta"], factor=rs["factor"],
                      original_max=rs["original_max_position_embeddings"],
                      beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                      mscale=rs["mscale"],
                      mscale_all_dim=rs["mscale_all_dim"]),
            eps=config["rms_norm_eps"],
            max_len=max_len or config["max_position_embeddings"],
            param_dtype=jnp.dtype(config["param_dtype"]))


def init_rule(path, shape):
    """Embeddings N(0, 1), matrices LeCun normal (std 1/sqrt(fan_in)),
    norm gains 1, the router's selection bias N(0, 0.05): small, and not
    zero, so that a path that dropped it would show."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "gamma":
        return ("ones",)
    if leaf == "router_bias":
        return ("normal", 0.05)
    if "embed" in path:
        return ("normal", 1.0)
    if leaf in ("w_gate", "w_up"):      # [held, out, in]
        return ("normal", 1.0 / math.sqrt(shape[2]))
    # W [out, in], router [experts, in], w_down [held, in, out]
    return ("normal", 1.0 / math.sqrt(shape[1]))
