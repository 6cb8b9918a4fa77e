"""Builder: ``chainermn_tpu.models.TransformerLM`` from GPT-2's published
keys (``n_embd``, ``n_layer``, ``n_head``, ``n_positions``,
``vocab_size``), bf16 compute over float32 parameters."""

from __future__ import annotations

import math

from . import _init


def build(config, max_len=None):
    """The link, its parameters still the constructor's placeholders."""
    import jax.numpy as jnp
    from chainermn_tpu.models import TransformerLM
    with _init.host_draws_skipped():
        return TransformerLM(
            n_vocab=config["vocab_size"], d_model=config["n_embd"],
            n_heads=config["n_head"], n_layers=config["n_layer"],
            max_len=max_len or config["n_positions"], seed=0,
            compute_dtype=jnp.bfloat16)


def init_rule(path, shape):
    """The distribution the link's own constructor draws each leaf from:
    embeddings N(0, 1), matrices LeCun normal (std 1/sqrt(fan_in)),
    LayerNorm gains 1, every bias 0."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "gamma":
        return ("ones",)
    if leaf in ("beta", "b"):
        return ("zeros",)
    if "embed" in path:
        return ("normal", 1.0)
    return ("normal", 1.0 / math.sqrt(shape[1]))
