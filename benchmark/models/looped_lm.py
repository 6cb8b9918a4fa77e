"""Builder: ``chainermn_tpu.models.LoopedLM`` from the published keys of
an ``ouro`` ``config.json``: ``num_hidden_layers`` blocks applied
``total_ut_steps`` times.  The parameters are constructed as shapes only
and served in ``param_dtype``."""

from __future__ import annotations

import math


def build(config, max_len=None):
    """The link, its parameters still shapes (nothing drawn or
    allocated)."""
    import jax.numpy as jnp
    from chainermn_tpu.core.link import abstract_init
    from chainermn_tpu.models import LoopedLM
    if config["sliding_window"] is not None or config["use_sliding_window"] \
            or set(config["layer_types"]) != {"full_attention"}:
        raise ValueError("a looped model with window layers is not written")
    if config["rope_scaling"] is not None:
        raise ValueError("scaled rotary frequencies are not written for "
                         "the looped model")
    _built["branches"] = 2 * config["num_hidden_layers"]
    with abstract_init():
        return LoopedLM(
            n_vocab=config["vocab_size"], d_model=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv=config["num_key_value_heads"],
            head_dim=config["head_dim"], d_ff=config["intermediate_size"],
            passes=config["total_ut_steps"],
            rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
            exit_threshold=config["early_exit_threshold"],
            max_len=max_len or config["max_position_embeddings"],
            param_dtype=jnp.dtype(config["param_dtype"]))


# What ``init_rule`` takes from the configuration ``build`` last saw (the
# drivers build a model, then ask the rule of each of its leaves): the
# residual branches that join the stream between two final norms, two a
# layer.  ``total_ut_steps`` does not enter: the final norm closes every
# pass, so the stream a pass grows starts at norm 1 again.
_built = {}
_OUT_NORMS = ("ln2/gamma", "ln4/gamma")


def init_rule(path, shape):
    """Embeddings N(0, 1), matrices LeCun normal (std 1/sqrt(fan_in)),
    the exit gate's bias 0, the gains of the norms on a sublayer's INPUT
    and of the final norm 1, the gains of the norms on a sublayer's
    OUTPUT ``1 / sqrt(2 · num_hidden_layers)`` of the configuration
    built: the scaled initialisation of a residual stream's branches,
    which for this block lives in the output norm's gain (a scaled output
    projection would be normed away).  With those gains at 1 every branch
    is as large as the stream it joins (the stream is normed back to 1 at
    the end of every pass), the seeded model amplifies bfloat16's
    rounding to the size of the logits themselves, and ``correct`` cannot
    tell bfloat16 from float8 (PERF.md section 6, PR 40: 0.81-1.29
    against 5.8-6.9 at 48 layers)."""
    leaf = path.rsplit("/", 1)[-1]
    if path.endswith(_OUT_NORMS):
        return ("full", 1.0 / math.sqrt(_built["branches"]))
    if leaf == "gamma":
        return ("ones",)
    if leaf == "b":
        return ("zeros",)
    if "embed" in path:
        return ("normal", 1.0)
    return ("normal", 1.0 / math.sqrt(shape[1]))      # W [out, in]
