"""Multi-node BN vs single-process BN on the concatenated batch.

Mirrors reference ``links_tests/test_batch_normalization.py``
(SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import chainermn_tpu as ct
from chainermn_tpu import L
from chainermn_tpu.core.link import apply_state, extract_state
from chainermn_tpu.links import (MultiNodeBatchNormalization,
                                 create_mnbn_model)

COMM = None


def setup_module(module):
    global COMM
    COMM = ct.create_communicator("jax_ici")


def test_mnbn_matches_global_batch_bn():
    size = COMM.size
    bn_global = L.BatchNormalization(3)
    mnbn = MultiNodeBatchNormalization(3, COMM)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(2, 3, (size * 4, 3)).astype(np.float32))

    y_global, _ = apply_state(bn_global, extract_state(bn_global), x)

    state = extract_state(mnbn)

    def body(params, pstate, x):
        out, new = apply_state(mnbn, {"params": params, "state": pstate}, x)
        return out, new["state"]

    from jax import shard_map
    mapped = shard_map(body, mesh=COMM.mesh,
                       in_specs=(P(), P(), P(COMM.axis_name)),
                       out_specs=(P(COMM.axis_name), P()),
                       check_vma=False)
    y_mn, new_state = jax.jit(mapped)(state["params"], state["state"], x)
    np.testing.assert_allclose(np.asarray(y_mn), np.asarray(y_global),
                               rtol=1e-4, atol=1e-5)
    # running stats updated toward the global moments
    np.testing.assert_allclose(np.asarray(new_state["/avg_mean"]),
                               0.1 * np.asarray(x).mean(axis=0), rtol=1e-3)


def test_mnbn_gradients_match_global_bn():
    size = COMM.size
    bn_global = L.BatchNormalization(3)
    mnbn = MultiNodeBatchNormalization(3, COMM)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(1, 2, (size * 2, 3)).astype(np.float32))

    sg = extract_state(bn_global)

    def loss_global(p):
        out, _ = apply_state(bn_global, {"params": p, "state": sg["state"]}, x)
        return jnp.sum(out ** 3)

    g_ref = jax.grad(loss_global)(sg["params"])

    sm = extract_state(mnbn)

    def body(params, pstate, x):
        # per-rank local loss; total gradient = psum of per-rank grads
        # (the multi-node optimizer's treatment) — cross-rank dependencies
        # through the pmean'd moments are handled by AD transposition
        def loss(p):
            out, _ = apply_state(mnbn, {"params": p, "state": pstate}, x)
            return jnp.sum(out ** 3)
        grads = jax.grad(loss)(params)
        return jax.tree.map(lambda g: jax.lax.psum(g, COMM.axis_name), grads)

    from jax import shard_map
    mapped = shard_map(body, mesh=COMM.mesh,
                       in_specs=(P(), P(), P(COMM.axis_name)),
                       out_specs=P(),
                       check_vma=False)
    g_mn = jax.jit(mapped)(sm["params"], sm["state"], x)
    for k in g_ref:
        np.testing.assert_allclose(np.asarray(g_mn[k]), np.asarray(g_ref[k]),
                                   rtol=1e-3, atol=1e-4)


def test_create_mnbn_model_rewrites_recursively():
    class Net(ct.Chain):
        def __init__(self):
            super().__init__()
            with self.init_scope():
                self.conv = L.Convolution2D(3, 8, 3, seed=0)
                self.bn = L.BatchNormalization(8)
                self.inner = ct.Sequential(L.Linear(8, 4, seed=1),
                                           L.BatchNormalization(4))

    net = Net()
    net.bn.gamma.array = jnp.full((8,), 2.0)
    mn = create_mnbn_model(net, COMM)
    assert isinstance(mn.bn, MultiNodeBatchNormalization)
    assert isinstance(mn.inner[1], MultiNodeBatchNormalization)
    assert not isinstance(mn.conv, MultiNodeBatchNormalization)
    np.testing.assert_allclose(np.asarray(mn.bn.gamma.array), 2.0)
    # original untouched
    assert not isinstance(net.bn, MultiNodeBatchNormalization)
    # params enumerate under the same paths
    assert [n for n, _ in mn.namedparams()] == [n for n, _ in net.namedparams()]


def test_bn_running_var_unbiased():
    """Running variance accumulates the unbiased batch variance
    (× m/(m-1)), matching the reference's adjustment (ADVICE r1)."""
    bn = L.BatchNormalization(2, decay=0.5)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.normal(0, 2, (6, 2)).astype(np.float32))
    bn(x)
    m = x.shape[0]
    expected = 0.5 * 1.0 + 0.5 * np.asarray(x).var(axis=0) * m / (m - 1)
    np.testing.assert_allclose(np.asarray(bn.avg_var), expected, rtol=1e-5)
