"""Model zoo tests: shapes, training steps, model-parallel equivalence."""

import numpy as np
import pytest

# multi-minute compile-heavy suite (ResNets, model-parallel seq2seq):
# slow-marked so tier-1 stays inside its wall-clock budget
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp

import chainermn_tpu as ct
from chainermn_tpu import F
from chainermn_tpu.core.optimizer import Adam, SGD
from chainermn_tpu.models import (Classifier, DCGANUpdater, Discriminator,
                                  Generator, MLP, ModelParallelSeq2seq,
                                  ResNet18, ResNet50, Seq2seq,
                                  make_synthetic_translation_data)


def test_mlp_classifier_trains():
    model = Classifier(MLP(n_units=32, n_out=5, seed=0))
    opt = Adam().setup(model)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (16, 20)).astype(np.float32))
    t = jnp.asarray(rng.randint(0, 5, 16).astype(np.int32))
    losses = [float(opt.update(model, x, t)) for _ in range(10)]
    assert losses[-1] < losses[0]


def test_resnet50_forward_shape():
    model = ResNet50(n_classes=10)
    x = jnp.zeros((2, 3, 64, 64), jnp.float32)
    y = model(x)
    assert y.shape == (2, 10)
    assert model.count_params() > 23_000_000  # ResNet-50 scale


def test_resnet50_bf16_compute():
    model = ResNet50(n_classes=10, compute_dtype=jnp.bfloat16)
    x = jnp.zeros((2, 3, 64, 64), jnp.float32)
    y = model(x)
    assert y.dtype == jnp.float32  # logits back in f32
    assert np.isfinite(np.asarray(y)).all()


def test_resnet50_uint8_input_norm_matches_host_normalized():
    """input_norm='imagenet' over raw uint8 pixels must equal the same
    weights fed host-normalized float32 ((x/255 - mean)/std) — the
    in-graph path exists so the pipeline can ship uint8 and cast on
    device."""
    from chainermn_tpu.models.resnet import IMAGENET_MEAN, IMAGENET_STD

    rng = np.random.RandomState(0)
    x8 = rng.randint(0, 256, (2, 3, 64, 64)).astype(np.uint8)
    mean = np.asarray(IMAGENET_MEAN, np.float32).reshape(1, 3, 1, 1)
    std = np.asarray(IMAGENET_STD, np.float32).reshape(1, 3, 1, 1)
    xf = (x8.astype(np.float32) / 255.0 - mean) / std

    m_u8 = ResNet50(n_classes=10, seed=0, input_norm="imagenet")
    m_f = ResNet50(n_classes=10, seed=0)
    y_u8 = np.asarray(m_u8(jnp.asarray(x8)))
    y_f = np.asarray(m_f(jnp.asarray(xf)))
    np.testing.assert_allclose(y_u8, y_f, rtol=2e-4, atol=2e-4)
    # NHWC layout flavor keeps the same math
    m_u8n = ResNet50(n_classes=10, seed=0, input_norm="imagenet",
                     layout="NHWC")
    y_u8n = np.asarray(m_u8n(jnp.asarray(
        np.transpose(x8, (0, 2, 3, 1)))))
    np.testing.assert_allclose(y_u8n, y_f, rtol=2e-4, atol=2e-4)
    # bf16 flavor: the in-graph normalize runs in f32 and casts only the
    # result, so it must track the host-normalized bf16 model within
    # bf16 rounding (not merely stay finite)
    m_b = ResNet50(n_classes=10, seed=0, input_norm="imagenet",
                   compute_dtype=jnp.bfloat16)
    m_bf = ResNet50(n_classes=10, seed=0, compute_dtype=jnp.bfloat16)
    y_b = np.asarray(m_b(jnp.asarray(x8)))
    y_bf = np.asarray(m_bf(jnp.asarray(xf)))
    np.testing.assert_allclose(y_b, y_bf, rtol=5e-2, atol=5e-2)
    # misspelled preset fails loudly at construction
    with pytest.raises(ValueError, match="input_norm preset"):
        ResNet50(n_classes=10, input_norm="ImageNet")


def test_classic_convnets_input_norm_matches_host_normalized():
    """input_norm='imagenet' on the classic ImageNet archs equals the
    same weights fed host-normalized float32 (NIN: deterministic
    forward, no dropout on the conv path)."""
    from chainermn_tpu.models import NIN
    from chainermn_tpu.models.resnet import IMAGENET_MEAN, IMAGENET_STD

    rng = np.random.RandomState(0)
    x8 = rng.randint(0, 256, (2, 3, 64, 64)).astype(np.uint8)
    mean = np.asarray(IMAGENET_MEAN, np.float32).reshape(1, 3, 1, 1)
    std = np.asarray(IMAGENET_STD, np.float32).reshape(1, 3, 1, 1)
    xf = (x8.astype(np.float32) / 255.0 - mean) / std
    m_u8 = NIN(n_classes=10, seed=0, input_norm="imagenet")
    m_f = NIN(n_classes=10, seed=0)
    np.testing.assert_allclose(np.asarray(m_u8(jnp.asarray(x8))),
                               np.asarray(m_f(jnp.asarray(xf))),
                               rtol=2e-4, atol=2e-4)


def test_resnet18_trains_on_synthetic_cifar():
    model = Classifier(ResNet18(n_classes=10, seed=0))
    opt = Adam().setup(model)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(0, 1, (8, 3, 32, 32)).astype(np.float32))
    t = jnp.asarray(rng.randint(0, 10, 8).astype(np.int32))
    l0 = float(opt.update(model, x, t))
    for _ in range(5):
        l = float(opt.update(model, x, t))
    assert l < l0


def test_seq2seq_loss_and_translate():
    xs, ys_in, ys_out = make_synthetic_translation_data(n=32, max_len=8)
    model = Seq2seq(40, 40, 32, seed=0)
    opt = Adam().setup(model)
    l0 = float(opt.update(model, jnp.asarray(xs), jnp.asarray(ys_in),
                          jnp.asarray(ys_out)))
    for _ in range(15):
        l = float(opt.update(model, jnp.asarray(xs), jnp.asarray(ys_in),
                             jnp.asarray(ys_out)))
    assert l < l0
    out = model.translate(jnp.asarray(xs[:4]), bos_id=0, eos_id=1,
                          max_length=8)
    assert out.shape == (4, 8)


def test_model_parallel_seq2seq_matches_single_process():
    """Enc/dec split across stage ranks == single-process seq2seq (golden
    rule, BASELINE config #4)."""
    comm = ct.create_communicator("jax_ici", axis_name="s2s_stage")
    xs, ys_in, ys_out = make_synthetic_translation_data(n=8, max_len=6)
    xs, ys_in, ys_out = (jnp.asarray(xs), jnp.asarray(ys_in),
                        jnp.asarray(ys_out))
    mp = ModelParallelSeq2seq(comm, 40, 40, 16, seed=5)
    ref = Seq2seq(40, 40, 16, seed=5)
    loss_mp = mp(xs, ys_in, ys_out)
    loss_ref = ref(xs, ys_in, ys_out)
    np.testing.assert_allclose(float(loss_mp), float(loss_ref),
                               rtol=1e-4)


def test_model_parallel_seq2seq_trains():
    comm = ct.create_communicator("jax_ici", axis_name="s2s_stage2")
    xs, ys_in, ys_out = make_synthetic_translation_data(n=16, max_len=6)
    xs, ys_in, ys_out = (jnp.asarray(xs), jnp.asarray(ys_in),
                        jnp.asarray(ys_out))
    model = ModelParallelSeq2seq(comm, 40, 40, 16, seed=3)
    opt = SGD(lr=0.5).setup(model)
    l0 = float(opt.update(model, xs, ys_in, ys_out))
    for _ in range(10):
        l = float(opt.update(model, xs, ys_in, ys_out))
    assert l < l0


def test_dcgan_updater_steps():
    gen, dis = Generator(n_hidden=16, ch=32, seed=0), Discriminator(ch=32,
                                                                    seed=1)
    opt_gen = Adam(alpha=1e-3).setup(gen)
    opt_dis = Adam(alpha=1e-3).setup(dis)
    rng = np.random.RandomState(0)
    data = rng.normal(0, 0.5, (16, 3, 32, 32)).astype(np.float32)
    from chainermn_tpu.dataset import SerialIterator
    it = SerialIterator(data, 8, shuffle=False)
    updater = DCGANUpdater(it, opt_gen, opt_dis)
    w_gen0 = np.asarray(gen.l0.W.array).copy()
    w_dis0 = np.asarray(dis.l4.W.array).copy()
    updater.update()
    updater.update()
    assert not np.allclose(np.asarray(gen.l0.W.array), w_gen0)
    assert not np.allclose(np.asarray(dis.l4.W.array), w_dis0)


def test_dcgan_data_parallel():
    comm = ct.create_communicator("jax_ici")
    gen, dis = Generator(n_hidden=16, ch=32, seed=0), Discriminator(ch=32,
                                                                    seed=1)
    opt_gen = ct.create_multi_node_optimizer(Adam(alpha=1e-3), comm).setup(gen)
    opt_dis = ct.create_multi_node_optimizer(Adam(alpha=1e-3), comm).setup(dis)
    rng = np.random.RandomState(0)
    data = rng.normal(0, 0.5, (32, 3, 32, 32)).astype(np.float32)
    from chainermn_tpu.dataset import SerialIterator
    it = SerialIterator(data, 16, shuffle=False)
    updater = DCGANUpdater(it, opt_gen, opt_dis)
    updater.update()
    assert np.isfinite(np.asarray(gen.l0.W.array)).all()


def test_resnet_remat_matches_no_remat():
    """jax.checkpoint stages: identical loss/grads, lower activation
    memory; BN stats thread through the remat boundary."""
    from chainermn_tpu.core.optimizer import SGD
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.normal(0, 1, (4, 3, 64, 64)).astype(np.float32))
    t = jnp.asarray(rng.randint(0, 10, 4).astype(np.int32))
    losses = {}
    stats = {}
    for remat in (False, True):
        m = Classifier(ResNet50(n_classes=10, remat=remat, seed=0))
        opt = SGD(lr=0.01).setup(m)
        losses[remat] = [float(opt.update(m, x, t)) for _ in range(2)]
        stats[remat] = np.asarray(m.predictor.res2[0].a.bn.avg_mean)
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    np.testing.assert_allclose(stats[True], stats[False], rtol=1e-5)
    assert np.abs(stats[True]).sum() > 0  # BN stats actually updated


def test_classic_convnets_forward_and_train():
    from chainermn_tpu.models import AlexNet, NIN, VGG16, GoogLeNet
    rng = np.random.RandomState(0)
    # small spatial input keeps CPU time sane; archs handle any size ≥ their
    # stride pyramid via lazy/GAP heads (VGG/Alex use lazy fc6)
    for cls, size in ((NIN, 67), (GoogLeNet, 64)):
        m = cls(n_classes=7, seed=0)
        x = jnp.asarray(rng.normal(0, 1, (2, 3, size, size))
                        .astype(np.float32))
        y = m(x)
        assert y.shape == (2, 7), cls.__name__
        assert np.isfinite(np.asarray(y)).all()
    # AlexNet/VGG16 train one step on tiny inputs
    from chainermn_tpu.core.optimizer import SGD
    for cls, size in ((AlexNet, 67), (VGG16, 64)):
        m = Classifier(cls(n_classes=5, seed=0))
        opt = SGD(lr=0.01).setup(m)
        x = jnp.asarray(rng.normal(0, 1, (2, 3, size, size))
                        .astype(np.float32))
        t = jnp.asarray(rng.randint(0, 5, 2).astype(np.int32))
        loss = opt.update(m, x, t)
        assert np.isfinite(float(loss)), cls.__name__


def test_googlenet_aux_heads():
    from chainermn_tpu.models import GoogLeNet
    from chainermn_tpu.core.optimizer import SGD
    m = GoogLeNet(n_classes=7, seed=0)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, 64, 64)).astype(np.float32))
    t = jnp.asarray(rng.randint(0, 7, 2).astype(np.int32))
    main, a1, a2 = m.forward_with_aux(x)
    assert main.shape == a1.shape == a2.shape == (2, 7)
    opt = SGD(lr=0.01).setup(m)
    loss = opt.update(m.loss, x, t)
    assert np.isfinite(float(loss))
    # eval mode: loss excludes aux terms
    with ct.using_config("train", False):
        eval_loss = m.loss(x, t)
        main_only = F.softmax_cross_entropy(m(x), t)
    np.testing.assert_allclose(float(eval_loss), float(main_only),
                               rtol=1e-5)
