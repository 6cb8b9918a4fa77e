"""Expert-parallel Switch MoE: routing correctness + training, plus the
two-stage (ici × dcn) dispatch property suite (ISSUE 12): every token
crosses the two hops exactly once (two-stage == flat bit-for-bit, round
trip == identity), on-host tokens never touch the slow fabric (they stay
bit-exact under a quantized DCN crossing), routing is deterministic
across ranks, capacity overflow is reported honestly (``dropped_frac``),
and the quantized dispatch gates on convergence parity (the 5%
final-loss band) on the MoE transformer vertical while the lossless
two-stage path is bit-parity with the flat reference."""

import warnings

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import chainermn_tpu as ct
from chainermn_tpu.parallel import switch_moe
from chainermn_tpu.parallel import moe as moe_mod

COMM = None
COMM_H = None


def setup_module(module):
    global COMM, COMM_H
    COMM = ct.create_communicator("jax_ici", axis_name="ep")
    COMM_H = ct.create_communicator("hierarchical", inter_size=2)


def _weights(D=8, H=16, seed=0):
    rng = np.random.RandomState(seed)
    E = COMM.size
    router = rng.normal(0, 0.5, (D, E)).astype(np.float32)
    w_in = rng.normal(0, 0.3, (E, D, H)).astype(np.float32)
    b_in = np.zeros((E, H), np.float32)
    w_out = rng.normal(0, 0.3, (E, H, D)).astype(np.float32)
    b_out = np.zeros((E, D), np.float32)
    return map(jnp.asarray, (router, w_in, b_in, w_out, b_out))


def test_moe_forward_matches_dense_routing():
    """With generous capacity, MoE output == per-token expert MLP."""
    D, H = 8, 16
    router, w_in, b_in, w_out, b_out = _weights(D, H)
    E = COMM.size
    T_local = 4
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(0, 1, (E * T_local, D)).astype(np.float32))

    def body(x, router, w_in, b_in, w_out, b_out):
        out, aux = switch_moe(COMM, x, router, w_in[0], b_in[0],
                              w_out[0], b_out[0], capacity_factor=float(E))
        return out, aux["aux_loss"].reshape(1)

    out, aux = COMM.run_spmd(
        body, x, router, w_in, b_in, w_out, b_out,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=(P("ep"), P("ep")))

    # dense reference: every token through its argmax expert
    xn = np.asarray(x)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xn) @ router, axis=-1))
    idx = probs.argmax(-1)
    expect = np.zeros_like(xn)
    for t in range(xn.shape[0]):
        e = idx[t]
        h = np.asarray(jax.nn.gelu(jnp.asarray(
            xn[t] @ np.asarray(w_in)[e] + np.asarray(b_in)[e])))
        expect[t] = (h @ np.asarray(w_out)[e] + np.asarray(b_out)[e]) \
            * probs[t, e]
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4,
                               atol=2e-5)


def test_moe_trains():
    D, H = 8, 16
    router, w_in, b_in, w_out, b_out = _weights(D, H, seed=2)
    E = COMM.size
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(0, 1, (E * 8, D)).astype(np.float32))
    target = jnp.asarray(rng.normal(0, 1, (E * 8, D)).astype(np.float32))

    def body(params, x, target):
        router, w_in, b_in, w_out, b_out = params

        def loss(params):
            router, w_in, b_in, w_out, b_out = params
            out, aux = switch_moe(COMM, x, router, w_in[0], b_in[0],
                                  w_out[0], b_out[0], capacity_factor=2.0)
            return jnp.mean((out - target) ** 2) + 0.01 * aux["aux_loss"]

        l, g = jax.value_and_grad(loss)(params)
        return l.reshape(1), g

    spec = (P(), P("ep"), P("ep"), P("ep"), P("ep"))
    params = (router, w_in, b_in, w_out, b_out)
    for _ in range(12):
        l, g = COMM.run_spmd(
            body, params, x, target,
            in_specs=(spec, P("ep"), P("ep")),
            out_specs=(P("ep"), spec))
        params = jax.tree.map(lambda p, gg: p - 0.5 * gg, params, g)
        if '_l0' not in dir():
            _l0 = float(np.asarray(l)[0])
    assert float(np.asarray(l)[0]) < _l0


def test_topk_moe_matches_dense_topk():
    """k=2 routing at generous capacity == dense top-2 mixture."""
    from chainermn_tpu.parallel import moe_dispatch_combine_topk
    D, H = 8, 16
    router, w_in, b_in, w_out, b_out = _weights(D, H, seed=4)
    E = COMM.size
    T_local = 4
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(0, 1, (E * T_local, D)).astype(np.float32))

    def body(x, router, w_in, b_in, w_out, b_out):
        def expert(h):
            return jax.nn.gelu(h @ w_in[0] + b_in[0]) @ w_out[0] + b_out[0]
        out, aux = moe_dispatch_combine_topk(
            COMM, x, x @ router, expert, k=2, capacity_factor=float(E))
        return out

    out = COMM.run_spmd(
        body, x, router, w_in, b_in, w_out, b_out,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
        out_specs=P("ep"))

    xn = np.asarray(x)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xn) @ router, axis=-1))
    topk = np.argsort(-probs, axis=1)[:, :2]
    expect = np.zeros_like(xn)
    for t in range(xn.shape[0]):
        g = probs[t, topk[t]]
        g = g / g.sum()
        for j, e in enumerate(topk[t]):
            h = np.asarray(jax.nn.gelu(jnp.asarray(
                xn[t] @ np.asarray(w_in)[e] + np.asarray(b_in)[e])))
            expect[t] += g[j] * (h @ np.asarray(w_out)[e]
                                 + np.asarray(b_out)[e])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=3e-4,
                               atol=3e-5)


# -- two-stage dispatch over the ici × dcn hierarchy (ISSUE 12) --------------

def _stacked_exchange(comm, base, ops):
    """Run a list of ``(two_stage, combine)`` exchange legs over the
    stacked ``[size*E, C, D]`` sentinel, chaining each leg on the
    PREVIOUS leg's output when ``chain`` is set."""
    axes = comm.axis_name

    def body(buf):
        outs = []
        cur = buf
        for two_stage, combine, chain in ops:
            src = cur if chain else buf
            cur = moe_mod._exchange(comm, src, two_stage, combine=combine)
            outs.append(cur)
        return tuple(outs)

    return comm.run_spmd(body, jnp.asarray(base), in_specs=(P(axes),),
                         out_specs=tuple(P(axes) for _ in ops))


def test_two_stage_exchange_every_token_exactly_once():
    """The routing-plan conservation property: the two-stage exchange is
    the SAME permutation as the flat single-axis all_to_all (every
    unique sentinel value lands exactly once, at the flat reference's
    position — nothing duplicated, dropped, or misrouted across the two
    hops), and the combine exchange is its exact inverse (round trip ==
    identity)."""
    E, C, D = COMM_H.size, 4, 2
    base = np.arange(E * E * C * D, dtype=np.float32) \
        .reshape(E * E, C, D)
    flat, two, back = _stacked_exchange(
        COMM_H, base, [(False, False, False), (True, False, False),
                       (True, True, True)])
    np.testing.assert_array_equal(np.asarray(two), np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(back), base)


def test_two_stage_exchange_deterministic_across_ranks():
    """Determinism: the exchange is a pure function of the buffer — a
    freshly constructed communicator over the same devices reproduces
    it bitwise (the cross-rank contract: every rank traces the same
    plan from the same arguments)."""
    E, C, D = COMM_H.size, 3, 2
    rng = np.random.RandomState(7)
    base = rng.normal(0, 1, (E * E, C, D)).astype(np.float32)
    (a,) = _stacked_exchange(COMM_H, base, [(True, False, False)])
    comm2 = ct.create_communicator("hierarchical", inter_size=2)
    (b,) = _stacked_exchange(comm2, base, [(True, False, False)])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_on_host_tokens_never_cross_dcn():
    """The behavioral pin of "on-host tokens never touch the slow
    fabric": under an int8 DCN crossing, blocks whose SOURCE host is
    the receiving host are bit-exact vs the lossless exchange (they
    never met the codebook), while off-host blocks demonstrably
    quantized."""
    comm_q = ct.create_communicator("hierarchical", inter_size=2,
                                    allreduce_grad_dtype={"dcn": "int8"})
    E, C, D = comm_q.size, 4, 3
    intra = comm_q.ici_size
    rng = np.random.RandomState(3)
    base = rng.normal(0, 1, (E * E, C, D)).astype(np.float32)
    (lossless,) = _stacked_exchange(COMM_H, base, [(True, False, False)])
    (quant,) = _stacked_exchange(comm_q, base, [(True, False, False)])
    lossless, quant = np.asarray(lossless), np.asarray(quant)
    changed_off_host = 0
    for r in range(E):
        block = slice(r * E, (r + 1) * E)  # rank r's [E, C, D] result
        lo, qo = lossless[block], quant[block]
        for src in range(E):
            if src // intra == r // intra:   # same-host source block
                np.testing.assert_array_equal(
                    qo[src], lo[src],
                    err_msg=f"on-host block {src}->{r} was quantized")
            elif (qo[src] != lo[src]).any():
                changed_off_host += 1
    assert changed_off_host > 0, \
        "no off-host block changed: the int8 crossing is not engaging"


def test_topk_two_stage_matches_flat_bitwise():
    """The GShard top-k path shares the exchange: two-stage lossless ==
    flat single-axis, bit for bit."""
    from chainermn_tpu.parallel import moe_dispatch_combine_topk
    E = COMM_H.size
    T, D = 8, 8
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.normal(0, 1, (E * T, D)).astype(np.float32))
    router = jnp.asarray(rng.normal(0, 0.5, (D, E)).astype(np.float32))
    axes = COMM_H.axis_name

    def body(x, router):
        def run(two_stage):
            out, _ = moe_dispatch_combine_topk(
                COMM_H, x, x @ router, lambda h: h * 2.0 + 1.0, k=2,
                capacity_factor=2.0, two_stage=two_stage)
            return out
        return run(False), run(True)

    flat, two = COMM_H.run_spmd(body, x, router,
                                in_specs=(P(axes), P()),
                                out_specs=(P(axes), P(axes)))
    np.testing.assert_array_equal(np.asarray(two), np.asarray(flat))


def test_dropped_frac_reports_capacity_overflow():
    """The capacity-honesty satellite: ``dropped_frac`` equals the
    dense-reference count of tokens beyond each expert's queue, and the
    load-balancing statistics (``frac``/``mean_prob``) are reported
    next to it with ``aux_loss`` their exact contraction."""
    from chainermn_tpu.parallel import moe_dispatch_combine
    E = COMM.size
    T, D = 16, 8
    capacity_factor = 0.5
    capacity = max(1, int(capacity_factor * T / E))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(0, 1, (E * T, D)).astype(np.float32))
    router = jnp.asarray(rng.normal(0, 0.5, (D, E)).astype(np.float32))

    def body(x, router):
        out, aux = moe_dispatch_combine(
            COMM, x, x @ router, lambda h: h, 
            capacity_factor=capacity_factor)
        return (out, aux["dropped_frac"].reshape(1),
                aux["frac"], aux["mean_prob"], aux["aux_loss"].reshape(1))

    out, dropped, frac, mean_prob, aux_loss = COMM.run_spmd(
        body, x, router, in_specs=(P("ep"), P()),
        out_specs=(P("ep"), P("ep"), P("ep"), P("ep"), P("ep")))
    dropped = np.asarray(dropped)
    frac = np.asarray(frac).reshape(E, E)
    mean_prob = np.asarray(mean_prob).reshape(E, E)
    aux_loss = np.asarray(aux_loss)

    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    idx = probs.argmax(-1).reshape(E, T)  # [rank, local token]
    for r in range(E):
        counts = np.zeros(E, dtype=int)
        kept = 0
        for e in idx[r]:
            if counts[e] < capacity:
                kept += 1
            counts[e] += 1
        assert dropped[r] == pytest.approx(1.0 - kept / T, abs=1e-6), r
        np.testing.assert_allclose(
            aux_loss[r], E * np.sum(frac[r] * mean_prob[r]), rtol=1e-6)
    assert (dropped > 0).any(), \
        "capacity_factor=0.5 dropped nothing: the test is vacuous"


def test_two_stage_on_flat_comm_is_loud():
    """Guard rail: requesting the two-stage exchange on a one-fabric
    communicator is a construction-site error, never a silent flat
    run."""
    from chainermn_tpu.parallel import moe_dispatch_combine
    x = jnp.zeros((8, 4))
    with pytest.raises(ValueError, match="two_stage"):
        COMM.run_spmd(
            lambda x: moe_dispatch_combine(
                COMM, x, jnp.zeros((x.shape[0], COMM.size)),
                lambda h: h, two_stage=True)[0],
            x, in_specs=(P("ep"),), out_specs=P("ep"))


def test_hierarchy_flat_hatch_drops_two_stage_with_warning(monkeypatch):
    """The CHAINERMN_TPU_HIERARCHY=flat hatch drops two-stage routing
    with the one-time warning pattern PR 11 established for striping —
    precisely: only a communicator the hatch actually DEGRADED (a
    requested hierarchy collapsed to one axis) warns; a comm that was
    never hierarchical keeps the loud two_stage=True error and never
    warns, whatever the environment says.  The dropped run IS the flat
    dispatch, bit for bit."""
    from chainermn_tpu.parallel import moe_dispatch_combine
    monkeypatch.setenv("CHAINERMN_TPU_HIERARCHY", "flat")
    monkeypatch.setattr(ct.communicators, "_WARNED_FLAT_TWO_STAGE",
                        set())
    # a requested hierarchy, collapsed by the hatch to one flat axis
    hatch_comm = ct.create_communicator("hierarchical", inter_size=2,
                                        axis_name="moe_hatch")
    assert hatch_comm.hierarchy is None
    E = hatch_comm.size
    rng = np.random.RandomState(13)
    x = jnp.asarray(rng.normal(0, 1, (E * 4, 8)).astype(np.float32))
    router = jnp.asarray(rng.normal(0, 0.5, (8, E)).astype(np.float32))

    def run(comm, two_stage):
        axes = comm.axis_name

        def body(x, router):
            out, _ = moe_dispatch_combine(
                comm, x, x @ router, lambda h: h * 2.0,
                capacity_factor=2.0, two_stage=two_stage)
            return out
        return comm.run_spmd(body, x, router, in_specs=(P(axes), P()),
                             out_specs=P(axes))

    with pytest.warns(UserWarning, match="two-stage MoE routing"):
        dropped = run(hatch_comm, True)
    # one-time: a second resolution does not warn again
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        again = run(hatch_comm, True)
    # a NEVER-hierarchical comm stays loud even with the hatch set
    with pytest.raises(ValueError, match="two_stage"):
        run(COMM, True)
    flat = run(hatch_comm, False)
    np.testing.assert_array_equal(np.asarray(dropped), np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(flat))


def _train_moe_vertical(dispatch_dtype=None, two_stage=None, steps=25):
    """Train the MoE transformer vertical (``MoETransformerLM``,
    scaled tier-1 small) through the multi-node optimizer on the
    simulated 2-host split.  ``dispatch_dtype`` compresses ONLY the
    token dispatch's DCN crossing (a separate ep communicator binding
    the same (dcn, ici) axes) while the gradient exchange stays
    lossless — the gradient wire is PR 7's already-gated story, and
    folding it in would attribute its noise to the dispatch."""
    from chainermn_tpu.core.optimizer import Adam
    from chainermn_tpu.models import MoETransformerLM
    comm = ct.create_communicator("hierarchical", inter_size=2)
    ep = comm if dispatch_dtype is None else ct.create_communicator(
        "hierarchical", inter_size=2,
        allreduce_grad_dtype=dispatch_dtype)
    model = MoETransformerLM(n_vocab=64, ep_comm=ep, d_model=32,
                             n_heads=2, n_layers=2, max_len=16, seed=0,
                             two_stage=two_stage)
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        Adam(alpha=3e-3), comm).setup(model)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 64, (8, 16)).astype(np.int32))
    t = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))
    return [float(opt.update(model, x, t)) for _ in range(steps)]


def test_moe_vertical_convergence_parity():
    """The acceptance gates on the MoE transformer vertical: the
    lossless two-stage dispatch trains the SAME trajectory as the
    explicit flat single-axis dispatch on the same communicator (the
    exchange itself is bit-equal — pinned by the dispatch-level tests
    above and the golden-equality gate in test_exchange_equivalence —
    so the only admissible trajectory difference is XLA reassociating
    f32 math around the differing collective structure: the same
    reduction-order tolerance the hierarchical gradient exchange
    gets), and the int8 DCN crossing sits inside the committed 5%
    final-loss band of the lossless run (the EF-style
    convergence-parity discipline — the codebook rounds, so
    bit-exactness is not the claim)."""
    lossless = _train_moe_vertical(two_stage=True)
    flat = _train_moe_vertical(two_stage=False)
    np.testing.assert_allclose(
        lossless, flat, rtol=1e-5, atol=1e-7,
        err_msg="two-stage lossless dispatch drifted from the flat "
                "reference beyond reduction-order noise")
    assert lossless[-1] < lossless[0], "the vertical does not learn"
    for wire in ({"dcn": "int8"}, {"dcn": "bfloat16"}):
        quant = _train_moe_vertical(dispatch_dtype=wire)
        assert np.isfinite(quant).all()
        assert abs(quant[-1] - lossless[-1]) <= 0.05 * lossless[-1], (
            f"{wire} dispatch final loss {quant[-1]} outside the 5% "
            f"band of lossless {lossless[-1]}")
