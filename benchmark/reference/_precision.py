"""The precisions a plain reference can be computed in.

``float32`` is the reference proper: every matrix product at
``highest``.  ``fp8`` is the control, the nearest precision below the
bfloat16 the configurations state, used as the programs use bfloat16:
the operands of every matrix product or convolution and every tensor a
layer hands on are held in float8 (4 exponent and 3 mantissa bits,
scaled to the tensor's largest magnitude; products still accumulate in
float32), with a straight-through gradient.  A sound check must call
the control not correct.

The rounding is ``jax.lax.reduce_precision``.  A pair of ``astype``s to
``float8_e4m3fn`` and back is not one on the chip: the TPU compiler
drops the pair wherever it does not feed a product (an array of a
million normal numbers came back with a relative error of 3e-8 where the
CPU gives 0.026; my chip run, PR 23), which left a control that rounded
the products' operands alone and read nearer to float32 than bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "fp8")


def _fp8(x):
    # four exponent bits in IEEE form reach 240 (e4m3fn, which spends no
    # code on infinities, 448)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                 mantissa_bits=3) * scale
    return x + jax.lax.stop_gradient(q - x)


def operand(x, precision):
    """A matrix-product operand, or a tensor a layer hands on, as
    ``precision`` would hold it."""
    if precision == "float32":
        return x
    if precision == "fp8":
        return _fp8(x)
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def matmul(a, b, precision):
    return operand(jnp.matmul(operand(a, precision), operand(b, precision),
                              precision=jax.lax.Precision.HIGHEST),
                   precision)
