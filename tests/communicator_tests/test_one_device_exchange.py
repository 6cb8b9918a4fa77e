"""A one-device axis exchanges nothing (ISSUE 25).

``lax.pmean`` over an axis of one device is the identity, so the plain
``grad_transform`` of a communicator built over ONE device emits no
pack, no collective and no unpack, whatever ``batch_collectives`` is:
gradients reach the optimizer update as the leaves they are.  What is
part of the mathematics stays (the cast to a non-quantized
``allreduce_grad_dtype`` and back), so the trajectory is bitwise the
packed path's.  Structure is read off the lowered step; values off
three steps of a small MLP.  Multi-device programs are pinned unchanged
by tools/comm_budgets.json (tests/test_comm_budget.py).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import chainermn_tpu as ct
from chainermn_tpu.communicators._memory_utility import (tree_pack,
                                                         tree_unpack)
from chainermn_tpu.core.optimizer import Adam, MomentumSGD
from chainermn_tpu.models import Classifier, MLP

STEPS = 3
#: tiny bound so even the toy MLP would split into several buckets
TINY_BUCKET_MB = 2000 / 2 ** 20
_OPTIMIZERS = {"momentum_sgd": lambda: MomentumSGD(lr=0.1, momentum=0.9),
               "adam": lambda: Adam(alpha=0.01)}


def _data(seed=0, n=32, d=8, k=4):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32)),
            jnp.asarray(rng.randint(0, k, n).astype(np.int32)))


def _model():
    return Classifier(MLP(n_units=16, n_out=4, seed=0))


def _one_device_comm(batch_collectives=True, grad_dtype=None):
    return ct.create_communicator(
        "jax_ici", devices=jax.devices()[:1],
        batch_collectives=batch_collectives,
        bucket_mb=TINY_BUCKET_MB if batch_collectives == "bucketed"
        else None,
        allreduce_grad_dtype=grad_dtype)


def _trajectory(opt, model):
    x, t = _data()
    losses = [np.asarray(opt.update(model, x, t)) for _ in range(STEPS)]
    return losses, [np.asarray(p.array) for p in model.params()]


def _run(make_inner, comm=None):
    """Three steps through the multi-node optimizer over ``comm``, or
    through the wrapped optimizer alone."""
    model = _model()
    if comm is None:
        return _trajectory(make_inner().setup(model), model)
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(make_inner(), comm).setup(model)
    return _trajectory(opt, model) + (opt,)


def _assert_bitwise(a, b):
    for x, y in zip(a[0], b[0]):
        np.testing.assert_array_equal(x, y)
    assert len(a[1]) == len(b[1])
    for x, y in zip(a[1], b[1]):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("grad_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("batch_collectives", [False, True, "bucketed"])
def test_one_device_step_packs_and_exchanges_nothing(batch_collectives,
                                                     grad_dtype):
    """The lowered step holds no collective on a gradient leaf, no
    ``concatenate`` (the pack) and no value of the model's parameter
    count (the bucket); with bf16 compression each leaf is still cast
    there and back."""
    comm = _one_device_comm(batch_collectives, grad_dtype)
    assert comm.size == 1 and not comm.grad_exchange_on_wire
    _, params, opt = _run(_OPTIMIZERS["momentum_sgd"], comm)
    text = opt.actual_optimizer.traced_step().lower().as_text()
    n_params = sum(p.size for p in params)

    # what pmeans remain are the loss's and the observations': scalars
    reduced = re.findall(
        r'"stablehlo\.all_reduce"\(.*?\) -> (tensor<[^>]*>)', text, re.S)
    assert reduced and set(reduced) == {"tensor<f32>"}, reduced
    assert "concatenate" not in text
    sizes = {int(np.prod([int(d) for d in dims.split("x") if d]))
             for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)}
    assert sizes and n_params not in sizes

    def casts(src, dst):
        return len(re.findall(
            rf"stablehlo\.convert .*x{src}>\) -> tensor<[\dx]*{dst}>", text))
    n_casts = len(params) if grad_dtype else 0
    assert casts("f32", "bf16") == n_casts
    assert casts("bf16", "f32") == n_casts


def test_multi_device_step_still_packs_one_bucket():
    """The other side of the choice: over more than one device the flat
    exchange is the packed bucket it was."""
    comm = ct.create_communicator("jax_ici", devices=jax.devices()[:2])
    assert comm.grad_exchange_on_wire
    _, params, opt = _run(_OPTIMIZERS["momentum_sgd"], comm)
    text = opt.actual_optimizer.traced_step().lower().as_text()
    n_params = sum(p.size for p in params)
    assert "concatenate" in text
    assert f"-> tensor<{n_params}xf32>" in text


def test_quantized_one_device_exchange_stays():
    """Quantization is lossy, so it is part of the result even at size
    1: that transform is not this shortcut's."""
    assert _one_device_comm(True, "int8").grad_exchange_on_wire


@pytest.mark.parametrize("batch_collectives", [False, True, "bucketed"])
@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
def test_one_device_equals_wrapped_optimizer_alone(optimizer,
                                                   batch_collectives):
    """Three multi-node steps over one device equal, bitwise, three
    steps of the wrapped optimizer with no communicator at all."""
    make = _OPTIMIZERS[optimizer]
    alone = _run(make)
    wrapped = _run(make, _one_device_comm(batch_collectives))
    _assert_bitwise(wrapped, alone)


def _packed_transform(comm):
    """The packed round trip the one-device exchange used to trace:
    cast, ONE flat bucket, ``pmean``, unpack, cast back."""
    axis, dtype = comm.axis_name, comm.allreduce_grad_dtype

    def transform(grads):
        leaves, treedef = jax.tree.flatten(grads)
        orig_dtypes = [g.dtype for g in leaves]
        if dtype is not None:
            leaves = [g.astype(dtype) for g in leaves]
        flat, spec = tree_pack(list(reversed(leaves)))
        flat = lax.pmean(flat, axis)
        leaves = reversed(tree_unpack(flat, spec))
        leaves = [g.astype(d) for g, d in zip(leaves, orig_dtypes)]
        return jax.tree.unflatten(treedef, leaves)

    return transform


@pytest.mark.parametrize("grad_dtype", [None, "bfloat16"])
@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
def test_one_device_equals_the_packed_round_trip(optimizer, grad_dtype,
                                                 monkeypatch):
    """Bitwise the values of the pack → pmean → unpack it replaces,
    bf16 compression included (the cast is part of the result)."""
    make = _OPTIMIZERS[optimizer]
    direct = _run(make, _one_device_comm(True, grad_dtype))
    comm = _one_device_comm(True, grad_dtype)
    monkeypatch.setattr(comm, "grad_transform",
                        lambda: _packed_transform(comm), raising=True)
    packed = _run(make, comm)
    text = packed[2].actual_optimizer.traced_step().lower().as_text()
    assert "concatenate" in text    # the reference really packs
    _assert_bitwise(direct, packed)
    if grad_dtype:
        # and the compression is observable: not the lossless values
        lossless = _run(make, _one_device_comm(True, None))
        assert any((a != b).any()
                   for a, b in zip(direct[1], lossless[1]))


def test_one_device_update_scan_continues_the_trajectory():
    """``update_scan`` runs the same transform: two fused steps after
    three plain ones equal five steps of the wrapped optimizer alone."""
    make = _OPTIMIZERS["momentum_sgd"]
    model = _model()
    alone = make().setup(model)
    x, t = _data()
    ref = [np.asarray(alone.update(model, x, t)) for _ in range(5)]
    losses, _, opt = _run(make, _one_device_comm(True))
    scan = np.asarray(opt.update_scan(
        opt.target, jnp.stack([x, x]), jnp.stack([t, t])))
    np.testing.assert_array_equal(
        np.asarray(list(losses) + list(scan)), np.asarray(ref))
    jaxpr = str(opt.actual_optimizer.traced_step().jaxpr)
    assert "concatenate" not in jaxpr
