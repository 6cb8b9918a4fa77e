"""``F.softmax_cross_entropy`` takes the target's logit inside the row
reduction (a masked sum against an iota) where it used to gather it from
a float32 log-softmax of every logit.  Value and gradient are held to the
form it replaces, written out here.  On a TPU, logits large enough go
through a rule of the loss's own (``ops/softmax_cotangent.py``): one read
of the logits gives the row sums and the logits' cotangent as ONE array,
which both backward GEMMs of a head take as a plain operand."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.core.link import bind_state, extract_state
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.nn import functions as F
from chainermn_tpu.ops import softmax_cotangent

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


# -- the rule of the loss's own: one read of the logits, one cotangent array -
#
# On a TPU, logits that ``ops.softmax_cotangent.fits`` go through the
# kernel; ``one_read`` is the public loss as the TPU runs it, the kernel
# interpreted, and is held to the plain form.

ONE_READ_SHAPES = [pytest.param((16, 4096 + 81), id="a_step_and_a_tail"),
                   pytest.param((32, 2 * 4096), id="two_steps_two_blocks"),
                   pytest.param((16, 2 * 4096 + 1), id="a_tail_of_one"),
                   pytest.param((48, 4096 + 384),
                                id="three_blocks_a_tail_of_whole_tiles")]
BF16 = jnp.bfloat16     # the one dtype the kernel takes (float32 loses)


INTERPRETED = functools.partial(softmax_cotangent.weighted_nll,
                                interpret=True)


def one_read(x, t, **kw):
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(softmax_cotangent, "weighted_nll", INTERPRETED)
        return F.softmax_cross_entropy(x, t, **kw)


def _pulled_back(fn, x, t, kw, scaled):
    """Value and the logits' cotangent, under a cotangent of ones or one
    that is not, so that the scaling shows."""
    out, pull = jax.vjp(lambda x: fn(x, t, **kw), x)
    g = jnp.asarray(np.random.default_rng(5).uniform(0.25, 2.0, out.shape),
                    out.dtype) if scaled else jnp.ones_like(out)
    return out, pull(g)[0]


@pytest.mark.parametrize("targets,options", [
    pytest.param("mixed", {}, id="mean"),
    pytest.param("mixed", {"reduce": "no"}, id="reduce_no"),
    pytest.param("mixed", {"normalize": False}, id="unnormalized"),
    pytest.param("mixed", {"class_weight": True}, id="class_weight"),
    pytest.param("ignored", {}, id="all_ignored"),
    pytest.param(0, {}, id="first_class"),
    pytest.param(-1, {}, id="last_class")])
@pytest.mark.parametrize("scaled", [
    pytest.param(False, id="ones"), pytest.param(True, id="scaled")])
@pytest.mark.parametrize("shape", ONE_READ_SHAPES)
def test_the_one_read_rule_is_plain_autodiff(shape, scaled, targets,
                                             options, dtype=BF16):
    if targets == -1:
        targets = shape[1] - 1
    x, t = _case(shape, dtype, targets=targets, seed=3)
    kw = _options(options, shape[1])
    if targets == "mixed":      # rows with an ignored target among the rest
        assert (np.asarray(t) == IGNORE).any()
        assert (np.asarray(t) != IGNORE).any()
    loss, got = jax.jit(lambda: _pulled_back(one_read, x, t, kw, scaled))()
    want_loss, want = jax.jit(lambda: _pulled_back(
        F.softmax_cross_entropy, x, t, kw, scaled))()
    assert loss.dtype == jnp.float32 and loss.shape == want_loss.shape
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6, atol=2e-6)
    assert got.dtype == x.dtype and got.shape == x.shape
    # float32 arithmetic rounded once to the logits' dtype on both sides:
    # one rounding of bfloat16's 8 bits; a cotangent that is not ones
    # multiplies the rule's array in float32, one rounding more (three
    # halves of a place between the two sides)
    tol = 2 ** (-7 if scaled else -8)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 1e-3)
    if targets == "ignored":
        assert float(loss) == 0.0
        assert not np.asarray(got, np.float32).any()


@pytest.mark.parametrize("fn", [
    pytest.param(one_read, id="one_read"),
    pytest.param(F.softmax_cross_entropy, id="plain")])
def test_the_targets_get_no_cotangent(fn):
    x, t = _case((16, 4096), jnp.bfloat16)
    _, pull = jax.vjp(fn, x, t)
    gx, gt = pull(jnp.float32(1.0))
    assert gx.dtype == x.dtype and gx.shape == x.shape
    assert gt.dtype == jax.dtypes.float0 and gt.shape == t.shape


def test_forward_mode_is_what_the_one_read_rule_gives_up():
    x, t = _case((16, 4096), jnp.bfloat16)
    tangent = (jnp.ones_like(x),)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: one_read(x, t), (x,), tangent)
    # off the TPU, and for every other shape, the loss is plain jnp
    _, dot = jax.jvp(lambda x: F.softmax_cross_entropy(x, t), (x,), tangent)
    np.testing.assert_allclose(dot, 0.0, atol=1e-6)     # rows sum to zero
    # as it is with the kernel asked for, for logits it does not take
    x = x.astype(jnp.float32)
    jax.jvp(lambda x: one_read(x, t), (x,), (jnp.ones_like(x),))


@pytest.mark.parametrize("shape,dtype,fits", [
    ((4096, 50257), jnp.bfloat16, True),        # GPT-2-medium's step
    ((16, 4096), jnp.bfloat16, True),
    ((1024, 160000), jnp.bfloat16, True),
    ((4096, 50257), jnp.float32, False),        # slower there than plain
    ((128, 1000), jnp.float32, False),          # ResNet-50
    ((128, 1000), jnp.bfloat16, False),         # too few classes
    ((24, 8192), jnp.bfloat16, False),          # no whole blocks of rows
    ((16, 8192, 5), jnp.bfloat16, False),       # classes not the last axis
    ((16, 8192), jnp.float16, False),
    ((16, 1 << 20), jnp.bfloat16, False)])      # a block past the VMEM
def test_which_logits_the_one_read_rule_takes(shape, dtype, fits):
    assert softmax_cotangent.fits(shape, dtype) is fits


@pytest.mark.parametrize("compute_dtype", [
    pytest.param(jnp.bfloat16, id="bf16"), pytest.param(None, id="f32")])
def test_the_lms_gradients_under_the_one_read_rule(compute_dtype,
                                                   monkeypatch):
    """``jax.grad`` through ``TransformerLM.forward`` at a tiny
    configuration: ``head/W``, ``ln_f`` and every other leaf as the plain
    loss gives them; float32 logits are not the kernel's and keep plain
    autodiff to the bit."""
    vocab = 4096 + 81
    lm = TransformerLM(vocab, d_model=32, n_heads=2, n_layers=1, max_len=16,
                       seed=11, compute_dtype=compute_dtype)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, vocab, (2, 8)), jnp.int32)
    t = jnp.asarray(np.where(rng.random((2, 8)) < 0.2, IGNORE,
                             rng.integers(0, vocab, (2, 8))), jnp.int32)
    state = extract_state(lm)

    def grads():
        def loss(params):
            with bind_state(lm, {"params": params, "state": state["state"]}):
                return lm.forward(x, t)
        return jax.jit(jax.value_and_grad(loss))(state["params"])

    want_loss, want = grads()
    monkeypatch.setattr(softmax_cotangent, "weighted_nll", INTERPRETED)
    loss, got = grads()
    assert {"/head/W", "/ln_f/gamma", "/ln_f/beta"} <= set(got)
    assert set(got) == set(want)
    if compute_dtype is None:
        np.testing.assert_array_equal(loss, want_loss)
        for name in got:
            np.testing.assert_array_equal(got[name], want[name], name)
        return
    # rows first, the CPU's GEMM sums a bfloat16 logit in another order
    np.testing.assert_allclose(loss, want_loss, rtol=2e-4)
    for name in got:
        scale = float(np.abs(np.asarray(want[name])).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2 ** -7,
                                   atol=2 ** -7 * scale, err_msg=name)


@pytest.mark.parametrize("stray", [
    pytest.param(4096 + 81, id="past_the_classes"),
    pytest.param(-7, id="below_zero")])
def test_a_target_that_is_no_class_gives_its_row_nothing(stray, dtype=BF16):
    """The kernel addresses a row's entry at its target, so a target that
    is neither ``ignore_label`` nor a class is kept off it: no loss and
    no gradient for its row, and the other rows as they are (the plain
    form picks no logit for such a row)."""
    shape = (16, 4096 + 81)
    x, t = _case(shape, dtype, seed=4)
    strays = np.asarray(t).copy()
    strays[[2, 9]] = stray
    ignored = np.asarray(t).copy()
    ignored[[2, 9]] = IGNORE

    def rows(t):
        return _pulled_back(one_read, x, jnp.asarray(t), {"reduce": "no"},
                            scaled=True)

    (loss, got), (want_loss, want) = rows(strays), rows(ignored)
    np.testing.assert_array_equal(loss, want_loss)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert not np.asarray(loss)[[2, 9]].any()
    assert not np.asarray(got, np.float32)[[2, 9]].any()
    # the stray rows still count in the mean's normaliser, as they always did
    count = lambda t: (np.asarray(t) != IGNORE).sum()
    np.testing.assert_allclose(
        one_read(x, jnp.asarray(strays)) * count(strays),
        one_read(x, jnp.asarray(ignored)) * count(ignored), rtol=1e-6)


def test_without_a_gradient_nothing_of_the_logits_shape_is_written():
    """Evaluation: the kernel is the rule's forward pass under
    differentiation alone."""
    x, t = _case((16, 4096), jnp.bfloat16)
    value = jax.make_jaxpr(lambda x: one_read(x, t))(x)
    assert "pallas_call" not in str(value)
    both = jax.make_jaxpr(jax.value_and_grad(lambda x: one_read(x, t)))(x)
    assert "pallas_call" in str(both)
    np.testing.assert_allclose(
        one_read(x, t), jax.value_and_grad(lambda x: one_read(x, t))(x)[0],
        rtol=2e-6)


def test_both_gemms_of_a_head_take_the_kernels_one_cotangent_array():
    """The trace of a head and the loss, backwards: the logits' cotangent
    is the second result of the ONE ``pallas_call``
    (``_softmax_cotangent_kernel``), and the head's two backward
    ``dot_general``s (dx, dW) take it, through nothing but the float32
    product with the rows' cotangent and its casts, which XLA drops
    under a mean.  No exponential is taken outside the kernel.  ``tests/test_chip_compile.py``
    holds the program the TPU's compiler makes of GPT-2-medium's
    vocabulary to the same."""
    N, V, D = 16, 4096, 32

    def loss(h, W, t):
        return one_read(h @ W.T, t)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        jnp.zeros((N, D), jnp.bfloat16), jnp.zeros((V, D), jnp.bfloat16),
        jnp.zeros((N,), jnp.int32)).jaxpr
    kernels = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    assert kernels[0].params["name"] == "_softmax_cotangent_kernel"
    cotangent = kernels[0].outvars[1]
    assert cotangent.aval.shape == (N, V)
    assert cotangent.aval.dtype == jnp.bfloat16
    for step in ("convert_element_type", "mul", "convert_element_type"):
        (scaled,) = [e for e in jaxpr.eqns if cotangent in e.invars]
        assert scaled.primitive.name == step
        (cotangent,) = scaled.outvars
    assert cotangent.aval.dtype == jnp.bfloat16
    gemms = [e for e in jaxpr.eqns if cotangent in e.invars]
    assert sorted((e.primitive.name, e.outvars[0].aval.shape)
                  for e in gemms) == [("dot_general", (N, D)),
                                      ("dot_general", (V, D))]
    assert not [e for e in jaxpr.eqns if e.primitive.name in ("exp", "exp2")]
