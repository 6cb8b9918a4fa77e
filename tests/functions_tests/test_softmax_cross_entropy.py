"""``F.softmax_cross_entropy`` takes the target's logit inside the row
reduction (a masked sum against an iota) where it used to gather it from
a float32 log-softmax of every logit.  Value and gradient are held to the
form it replaces, written out here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.nn import functions as F

IGNORE = -1


def gathered(x, t, ignore_label=IGNORE, reduce="mean", normalize=True,
             class_weight=None):
    """The loss as it was: ``-take_along_axis(log_softmax(x), t)``."""
    x = x.astype(jnp.float32)
    logp = jax.nn.log_softmax(x, axis=1)
    t_safe = jnp.where(t == ignore_label, 0, t)
    nll = -jnp.take_along_axis(logp, jnp.expand_dims(t_safe, 1),
                               axis=1).squeeze(1)
    if class_weight is not None:
        nll = nll * jnp.asarray(class_weight)[t_safe]
    mask = t != ignore_label
    nll = jnp.where(mask, nll, 0.0)
    if reduce == "no":
        return nll
    count = jnp.maximum(mask.sum(), 1) if normalize else x.shape[0]
    return nll.sum() / count


def _case(shape, dtype, targets="mixed", seed=0):
    """Logits of ``shape`` (classes along axis 1) and targets over the
    other axes: random with some ignored, all ignored, or pinned to one
    class."""
    rng = np.random.default_rng(seed)
    C = shape[1]
    x = jnp.asarray(rng.standard_normal(shape) * 3.0, dtype)
    t_shape = shape[:1] + shape[2:]
    if targets == "mixed":
        t = rng.integers(0, C, t_shape)
        t[rng.random(t_shape) < 0.25] = IGNORE
    elif targets == "ignored":
        t = np.full(t_shape, IGNORE)
    else:
        t = np.full(t_shape, targets)
    return x, jnp.asarray(t, jnp.int32)


SHAPES = [pytest.param((12, 10), id="2d"),
          pytest.param((6, 10, 5), id="nd"),
          pytest.param((4, 1153), id="c1153")]
DTYPES = [pytest.param(jnp.float32, id="f32"),
          pytest.param(jnp.bfloat16, id="bf16")]
OPTIONS = [pytest.param({}, id="mean"),
           pytest.param({"reduce": "no"}, id="reduce_no"),
           pytest.param({"normalize": False}, id="unnormalized"),
           pytest.param({"class_weight": True}, id="class_weight")]


def _options(options, C):
    if options.get("class_weight"):
        w = np.random.default_rng(7).uniform(0.5, 2.0, C).astype(np.float32)
        return {**options, "class_weight": w}
    return options


@pytest.mark.parametrize("options", OPTIONS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_value_is_the_gathered_form(shape, dtype, options):
    x, t = _case(shape, dtype)
    kw = _options(options, shape[1])
    got = F.softmax_cross_entropy(x, t, **kw)
    want = gathered(x, t, **kw)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    # lse - x_t against -(x_t - lse): the last place of float32
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("options", OPTIONS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_gradient_is_the_gathered_form_in_the_logits_dtype(
        shape, dtype, options):
    x, t = _case(shape, dtype, seed=1)
    kw = _options(options, shape[1])

    def total(fn):
        return lambda x: jnp.sum(fn(x, t, **kw))

    got = jax.grad(total(F.softmax_cross_entropy))(x)
    want = jax.grad(total(gathered))(x)
    assert got.dtype == x.dtype and got.shape == x.shape
    # both round softmax - onehot from float32 once; a bfloat16 gradient
    # may differ by one rounding of its 8 bits
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -8
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 1e-2)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_an_all_ignored_batch_is_zero_with_a_zero_gradient(shape, dtype):
    x, t = _case(shape, dtype, targets="ignored")
    loss, grad = jax.value_and_grad(
        lambda x: F.softmax_cross_entropy(x, t))(x)
    assert float(loss) == 0.0
    assert grad.dtype == x.dtype
    assert not np.asarray(grad, np.float32).any()
    assert not np.asarray(F.softmax_cross_entropy(x, t, reduce="no")).any()


@pytest.mark.parametrize("where", ["first", "last"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_a_target_at_either_end_of_the_classes(shape, dtype, where):
    C = shape[1]
    c = 0 if where == "first" else C - 1
    x, t = _case(shape, dtype, targets=c, seed=2)
    got = F.softmax_cross_entropy(x, t, reduce="no")
    x32 = np.asarray(x, np.float64)
    lse = np.log(np.exp(x32).sum(axis=1))
    np.testing.assert_allclose(got, lse - x32[:, c], rtol=1e-5, atol=1e-5)
    # the gradient is softmax - onehot(c): its column c is the negative one
    grad = np.asarray(jax.grad(
        lambda x: jnp.sum(F.softmax_cross_entropy(x, t, reduce="no")))(x),
        np.float64)
    assert (np.take(grad, c, axis=1) < 0).all()
    assert (np.delete(grad, c, axis=1) >= 0).all()
    np.testing.assert_allclose(grad.sum(axis=1), 0.0, atol=0.05)


def test_ignored_rows_leave_the_others_as_they_are():
    x, t = _case((12, 10), jnp.float32)
    keep = np.asarray(t) != IGNORE
    assert keep.any() and not keep.all()
    per_row = F.softmax_cross_entropy(x, t, reduce="no")
    assert not np.asarray(per_row)[~keep].any()
    alone = F.softmax_cross_entropy(x[keep], t[keep], reduce="no")
    np.testing.assert_allclose(np.asarray(per_row)[keep], alone, rtol=1e-6)
    np.testing.assert_allclose(F.softmax_cross_entropy(x, t),
                               np.asarray(alone).mean(), rtol=1e-6)
    np.testing.assert_allclose(
        F.softmax_cross_entropy(x, t, normalize=False),
        np.asarray(alone).sum() / 12, rtol=1e-6)


def test_the_traced_loss_and_its_backward_hold_no_gather():
    """A gather cannot fuse into the producer of its operand: with one in
    the trace XLA writes the float32 log-softmax of every logit for it
    (``tests/test_chip_compile.py`` has the compiled program)."""
    x, t = _case((16, 1153), jnp.bfloat16)
    text = jax.jit(jax.value_and_grad(
        lambda x: F.softmax_cross_entropy(x, t))).lower(x).as_text()
    assert "gather" not in text and "scatter" not in text
    old = jax.jit(jax.value_and_grad(
        lambda x: gathered(x, t))).lower(x).as_text()
    assert "gather" in old
