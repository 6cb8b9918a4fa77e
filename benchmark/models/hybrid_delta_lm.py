"""Builder: ``chainermn_tpu.models.HybridDeltaLM`` from the published
keys of an ``olmo_hybrid`` ``config.json``, as one stage of a pipeline:
``layer_types`` is the published list, read up to ``num_hidden_layers``.
The parameters are constructed as shapes only and served in
``param_dtype``."""

from __future__ import annotations

import math


def build(config, max_len=None):
    """The link, its parameters still shapes (nothing drawn or
    allocated)."""
    import jax.numpy as jnp
    from chainermn_tpu.core.link import abstract_init
    from chainermn_tpu.models import HybridDeltaLM
    n = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("key heads grouped under value heads are not "
                         "written for the delta-rule layer")
    with abstract_init():
        return HybridDeltaLM(
            n_vocab=config["vocab_size"], d_model=config["hidden_size"],
            layer_linear=[kind == "linear_attention"
                          for kind in config["layer_types"][:n]],
            linear=dict(n_heads=config["linear_num_value_heads"],
                        dk=config["linear_key_head_dim"],
                        dv=config["linear_value_head_dim"],
                        conv=config["linear_conv_kernel_dim"]),
            full=dict(n_heads=heads,
                      n_kv=config["num_key_value_heads"],
                      head_dim=config["hidden_size"] // heads),
            d_ff=config["intermediate_size"], eps=config["rms_norm_eps"],
            max_len=max_len or config["max_position_embeddings"],
            stride=config["snapshot_stride"],
            param_dtype=jnp.dtype(config["param_dtype"]))


# the depth the source trains: a residual stream's output projections
# are scaled for it, not for the layers held here
_PUBLISHED_LAYERS = 32
_OUT = ("mlp/down/W", "mix/o/W")


def init_rule(path, shape):
    """Embeddings N(0, 1), matrices LeCun normal (std 1/sqrt(fan_in)),
    the convolution's taps N(0, 1/taps), norm gains 1.  Every down- and
    output-projection is LeCun normal times ``1 / sqrt(2 · published
    layers)`` (PERF.md section 6, PR 31).  Gated DeltaNet's reference
    code draws ``A`` uniform in (0, 16) and the time step log-uniform in
    (0.001, 0.1) (as ``DeltaMixer`` itself does); ``benchmark/weights.py``
    makes normals and constants alone, so here ``A_log`` is N(0, 1) (``A``
    log-normal about 1, 0.1 to 10) and ``dt_bias`` the constant that
    gives the middle of that range, 0.01: a head forgets ``A`` hundredths
    of its state a token at a zero input."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "gamma":
        return ("ones",)
    if "embed" in path:
        return ("normal", 1.0)
    if leaf == "A_log":
        return ("normal", 1.0)
    if leaf == "dt_bias":          # the inverse softplus of 0.01
        return ("full", 0.01 + math.log(-math.expm1(-0.01)))
    # W [out, in], conv [channels, taps]
    std = 1.0 / math.sqrt(shape[1])
    if path.endswith(_OUT):
        std /= math.sqrt(2 * _PUBLISHED_LAYERS)
    return ("normal", std)
