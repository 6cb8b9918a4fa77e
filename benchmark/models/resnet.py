"""Builder: ``chainermn_tpu.models.resnet.ResNet`` under ``Classifier``
from the configuration's keys, NHWC, bf16 compute over float32
parameters."""

from __future__ import annotations

import math

from . import _init


def build(config):
    import jax.numpy as jnp
    from chainermn_tpu.models import Classifier
    from chainermn_tpu.models.resnet import ResNet
    with _init.host_draws_skipped():
        model = Classifier(ResNet(list(config["block_counts"]),
                                  n_classes=config["num_classes"],
                                  compute_dtype=jnp.bfloat16, seed=0,
                                  layout="NHWC"))
    _check_widths(model, config)
    return model


def _check_widths(model, config):
    """The class fixes its widths in code: hold them against the
    configuration's, so that neither drifts from the source unseen."""
    shapes = {path: tuple(p.shape) for path, p in model.namedparams()}
    want = {"/predictor/conv1/conv/W": config["stem_channels"]}
    for i, (mid, out) in enumerate(zip(config["stage_mid_channels"],
                                       config["stage_out_channels"])):
        want[f"/predictor/res{i + 2}/0/a/conv/W"] = mid
        want[f"/predictor/res{i + 2}/0/c/conv/W"] = out
    for path, width in want.items():
        if shapes[path][0] != width:
            raise ValueError(f"{path} has {shapes[path][0]} output channels; "
                             f"the configuration says {width}")


LAST_BN_GAIN = 0.25


def init_rule(path, shape):
    """As the link's constructor (convolutions He normal over OIHW
    kernels, the classifier LeCun normal, BN gains 1, every bias and
    shift 0) but for each block's last BN, whose gain starts at
    LAST_BN_GAIN.  With every gain 1 a randomly initialised 50-layer BN
    network amplifies any perturbation: the classifier's gradient then
    lies a tenth of its norm from float32's in bfloat16 and under twice
    that in float8, and no comparison tells the precisions apart.
    Large-batch ImageNet training starts that gain at 0 (Goyal et al.
    2017, arXiv:1706.02677, section 5.1); but at exactly 0 the 48 branch
    convolutions leave the loss and have no gradient, and lower precision
    there could not be seen.  At 0.25 every branch carries signal and
    the classifier's gradient is 0.013 off in bfloat16, 0.12 in float8
    and 0.07 with float8 in the branches alone (my chip runs, PR 23;
    PERF.md section 6)."""
    leaf = path.rsplit("/", 1)[-1]
    if path.endswith("/c/bn/gamma"):
        return ("full", LAST_BN_GAIN)
    if leaf == "gamma":
        return ("ones",)
    if leaf in ("beta", "b"):
        return ("zeros",)
    if len(shape) == 4:
        return ("normal", math.sqrt(2.0 / (shape[1] * shape[2] * shape[3])))
    return ("normal", 1.0 / math.sqrt(shape[1]))
