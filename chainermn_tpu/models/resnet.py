"""ResNet family (the ImageNet vertical — BASELINE config #2).

Reference capability: ``chainer.links.model.vision.resnet ·
ResNet50Layers`` and ChainerMN's ``examples/imagenet/train_imagenet.py``
(SURVEY.md §6: ResNet-50/ImageNet is the reference's headline benchmark).
Freshly designed for TPU rather than transcribed:

* Activations run in a selectable layout: ``layout="NHWC"`` (the TPU
  native channels-last layout — channels map onto the MXU lane dimension,
  so XLA inserts no layout-change transposes between conv/BN/relu) or
  ``"NCHW"`` (the reference layout, kept as the compatibility default).
  Kernels are stored OIHW either way, so checkpoints are layout-portable.
* ``compute_dtype=bfloat16`` runs conv/matmul compute in bf16 (MXU-native)
  with fp32 parameters and fp32 BN statistics — the TPU translation of the
  reference era's fp16 training recipe.
* Identity shortcuts use stride-slicing + channel-pad (option A) or
  projection (option B, the ResNet-50 default), all fusible.
* ``input_norm="imagenet"`` moves input normalization IN-GRAPH: the host
  pipeline ships raw uint8 pixels and the cast + per-channel standardize
  fuses into the first conv on device.  Motivation: the host-side
  float32 cast is what bounds a one-core input pipeline, the uint8
  gather is several times cheaper, and shipping uint8 also cuts
  host→HBM DMA traffic 4×.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..core.link import Chain, ChainList
from ..nn import functions as F
from ..nn import links as L

__all__ = ["ResNet50", "ResNet18", "ResNet101", "BottleneckBlock",
           "BasicBlock", "IMAGENET_MEAN", "IMAGENET_STD",
           "input_norm_consts", "normalize_input"]

# ImageNet channel statistics in 0-1 scale (the standard ImageNet
# normalization the reference's example pipeline applies on HOST per
# image; here the same math runs in-graph over 0-255 inputs)
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def input_norm_consts(input_norm):
    """(scale, bias) folding 0-255→0-1 and channel standardization into
    one multiply-add: y = x·scale + bias ≡ (x/255 − mean)/std.  Returns
    None for ``input_norm=None`` (inputs already normalized floats).
    Shared input-norm infrastructure: every ImageNet model family
    (ResNet here, the classic convnets in ``convnets.py``) consumes
    these two helpers — treat their contract as public."""
    if input_norm is None:
        return None
    if isinstance(input_norm, str):
        if input_norm != "imagenet":
            raise ValueError(
                f"unknown input_norm preset {input_norm!r}; valid: "
                "'imagenet', None, or a (mean, std) pair in 0-1 scale")
        mean, std = IMAGENET_MEAN, IMAGENET_STD
    else:  # (mean, std) pair in 0-1 scale
        mean, std = input_norm
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    return 1.0 / (255.0 * std), -mean / std


def normalize_input(x, consts, layout, compute_dtype):
    """Cast + (optionally) standardize on DEVICE, inside the compiled
    step: constants fold, XLA fuses the multiply-add into the first
    conv's input, and uint8 host→device transfers stay uint8.  The
    multiply-add itself runs in float32 and only the RESULT casts to
    ``compute_dtype`` — matching the host-normalized pipeline's
    precision (one rounding, not a bf16 FMA over bf16-rounded
    constants)."""
    if consts is None:
        return x.astype(compute_dtype) if compute_dtype is not None else x
    scale, bias = consts
    shape = (1, 1, 1, 3) if layout == "NHWC" else (1, 3, 1, 1)
    out = (x.astype(jnp.float32)
           * jnp.asarray(scale, jnp.float32).reshape(shape)
           + jnp.asarray(bias, jnp.float32).reshape(shape))
    return out.astype(compute_dtype) if compute_dtype is not None else out


class ConvBN(Chain):
    def __init__(self, in_ch, out_ch, ksize, stride=1, pad=0, seed=None,
                 layout="NCHW"):
        super().__init__()
        self.stride = stride
        self.pad = pad
        self.layout = layout
        bn_axis = (0, 1, 2) if layout == "NHWC" else None  # None → (0,2,3)
        with self.init_scope():
            self.conv = L.Convolution2D(in_ch, out_ch, ksize, stride=stride,
                                        pad=pad, nobias=True, seed=seed,
                                        layout=layout)
            self.bn = L.BatchNormalization(out_ch, axis=bn_axis)

    def forward(self, x, activate=True):
        # conv compute in the activation dtype (bf16 on the MXU when the
        # model casts); BN keeps the activation dtype end-to-end while its
        # statistics accumulate in fp32 internally (links.py _moments /
        # functions.py _apply_bn) — the elementwise chain conv→BN→relu
        # never round-trips the full tensor through fp32
        W = self.conv.W.array.astype(x.dtype)
        h = F.convolution_2d(x, W, None, self.stride, self.pad,
                             layout=self.layout)
        h = self.bn(h)
        if activate:
            h = F.relu(h)
        return h.astype(x.dtype)


class BottleneckBlock(Chain):
    """1x1 → 3x3 → 1x1 bottleneck with optional projection shortcut."""

    def __init__(self, in_ch, mid_ch, out_ch, stride=1, project=False,
                 seed=0, layout="NCHW"):
        super().__init__()
        self.project = project or in_ch != out_ch or stride != 1
        with self.init_scope():
            self.a = ConvBN(in_ch, mid_ch, 1, seed=seed, layout=layout)
            self.b = ConvBN(mid_ch, mid_ch, 3, stride=stride, pad=1,
                            seed=seed + 1, layout=layout)
            self.c = ConvBN(mid_ch, out_ch, 1, seed=seed + 2, layout=layout)
            if self.project:
                self.shortcut = ConvBN(in_ch, out_ch, 1, stride=stride,
                                       seed=seed + 3, layout=layout)

    def forward(self, x):
        h = self.a(x)
        h = self.b(h)
        h = self.c(h, activate=False)
        s = self.shortcut(x, activate=False) if self.project else x
        return F.relu(h + s)


class BasicBlock(Chain):
    """3x3 → 3x3 block (ResNet-18/34)."""

    def __init__(self, in_ch, out_ch, stride=1, seed=0, layout="NCHW"):
        super().__init__()
        self.project = in_ch != out_ch or stride != 1
        with self.init_scope():
            self.a = ConvBN(in_ch, out_ch, 3, stride=stride, pad=1, seed=seed,
                            layout=layout)
            self.b = ConvBN(out_ch, out_ch, 3, pad=1, seed=seed + 1,
                            layout=layout)
            if self.project:
                self.shortcut = ConvBN(in_ch, out_ch, 1, stride=stride,
                                       seed=seed + 2, layout=layout)

    def forward(self, x):
        h = self.a(x)
        h = self.b(h, activate=False)
        s = self.shortcut(x, activate=False) if self.project else x
        return F.relu(h + s)


class _Stage(ChainList):
    def __init__(self, n_blocks, in_ch, mid_ch, out_ch, stride, seed,
                 layout="NCHW"):
        blocks = [BottleneckBlock(in_ch, mid_ch, out_ch, stride=stride,
                                  project=True, seed=seed, layout=layout)]
        for i in range(1, n_blocks):
            blocks.append(BottleneckBlock(out_ch, mid_ch, out_ch,
                                          seed=seed + 10 * i, layout=layout))
        super().__init__(*blocks)

    def forward(self, x):
        for block in self:
            x = block(x)
        return x


class ResNet(Chain):
    def __init__(self, block_counts, n_classes=1000, compute_dtype=None,
                 seed=42, remat=False, layout="NCHW", input_norm=None):
        super().__init__()
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.layout = layout
        self.input_norm = input_norm
        self._in_consts = input_norm_consts(input_norm)
        with self.init_scope():
            self.conv1 = ConvBN(3, 64, 7, stride=2, pad=3, seed=seed,
                                layout=layout)
            self.res2 = _Stage(block_counts[0], 64, 64, 256, 1, seed + 100,
                               layout=layout)
            self.res3 = _Stage(block_counts[1], 256, 128, 512, 2, seed + 200,
                               layout=layout)
            self.res4 = _Stage(block_counts[2], 512, 256, 1024, 2, seed + 300,
                               layout=layout)
            self.res5 = _Stage(block_counts[3], 1024, 512, 2048, 2, seed + 400,
                               layout=layout)
            self.fc = L.Linear(2048, n_classes, seed=seed + 500)

    def _apply_stage(self, stage, h):
        if not self.remat:
            return stage(h)
        # rematerialize per stage: backward recomputes activations instead
        # of keeping them resident — trades MXU FLOPs for HBM (SURVEY §7
        # hardware note), buying larger per-chip batches.  BN running
        # stats must flow through the checkpoint boundary as explicit
        # inputs/outputs (attribute mutation would leak tracers out of the
        # remat region).
        import jax
        from ..core.link import _persistent_slots
        slots = list(_persistent_slots(stage))

        def run(h, values):
            for (sl, n, _), v in zip(slots, values):
                object.__setattr__(sl, n, v)
                sl._persistent[n] = v
            out = stage(h)
            new = tuple(getattr(sl, n) for sl, n, _ in slots)
            return out, new

        values = tuple(getattr(sl, n) for sl, n, _ in slots)
        out, new = jax.checkpoint(run)(h, values)
        for (sl, n, _), v in zip(slots, new):
            object.__setattr__(sl, n, v)
            sl._persistent[n] = v
        return out

    def forward(self, x):
        x = normalize_input(x, self._in_consts, self.layout,
                             self.compute_dtype)
        h = self.conv1(x)
        h = F.max_pooling_2d(h, 3, stride=2, pad=1, cover_all=False,
                             layout=self.layout)
        h = self._apply_stage(self.res2, h)
        h = self._apply_stage(self.res3, h)
        h = self._apply_stage(self.res4, h)
        h = self._apply_stage(self.res5, h)
        h = F.global_average_pooling_2d(h, layout=self.layout)
        return self.fc(h.astype(jnp.float32))


class ResNet50(ResNet):
    def __init__(self, n_classes=1000, compute_dtype=None, seed=42,
                 remat=False, layout="NCHW", input_norm=None):
        super().__init__([3, 4, 6, 3], n_classes, compute_dtype, seed,
                         remat=remat, layout=layout,
                         input_norm=input_norm)


class ResNet101(ResNet):
    def __init__(self, n_classes=1000, compute_dtype=None, seed=42,
                 remat=False, layout="NCHW", input_norm=None):
        super().__init__([3, 4, 23, 3], n_classes, compute_dtype, seed,
                         remat=remat, layout=layout,
                         input_norm=input_norm)


class ResNet18(Chain):
    def __init__(self, n_classes=1000, compute_dtype=None, seed=42,
                 input_norm=None):
        super().__init__()
        self.compute_dtype = compute_dtype
        self.input_norm = input_norm
        self._in_consts = input_norm_consts(input_norm)
        cfg = [(64, 64, 1), (64, 128, 2), (128, 256, 2), (256, 512, 2)]
        with self.init_scope():
            self.conv1 = ConvBN(3, 64, 7, stride=2, pad=3, seed=seed)
            stages = []
            for i, (in_ch, out_ch, stride) in enumerate(cfg):
                stages.append(BasicBlock(in_ch, out_ch, stride,
                                         seed=seed + 100 * (i + 1)))
                stages.append(BasicBlock(out_ch, out_ch,
                                         seed=seed + 100 * (i + 1) + 50))
            self.body = ChainList(*stages)
            self.fc = L.Linear(512, n_classes, seed=seed + 999)

    def forward(self, x):
        x = normalize_input(x, self._in_consts, "NCHW",
                             self.compute_dtype)
        h = self.conv1(x)
        h = F.max_pooling_2d(h, 3, stride=2, pad=1, cover_all=False)
        for block in self.body:
            h = block(h)
        h = F.global_average_pooling_2d(h)
        return self.fc(h.astype(jnp.float32))
