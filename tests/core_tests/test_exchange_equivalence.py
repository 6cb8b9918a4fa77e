"""Golden equality of every gradient-exchange variant (ISSUE 5 + 6).

The exchange structure — per-leaf psums, one flat bucket, K size-bounded
buckets, reduce-scatter + shard update + all-gather, or the two-level
hierarchical (ici × dcn) composition of either — changes the SCHEDULE
of the DP step, never its math.  Golden rule (SURVEY §4): each
variant's trajectory must EQUAL the single-device run on the merged
batch; the allreduce packings must be BITWISE equal to each other
(pmean is elementwise), and the reduce-scatter / hierarchical updates
must match to f32 reduction-order noise (chained per-hop sums reorder
the additions).  Composition axes from the ISSUE grids: {donation,
double buffering, compressed dtype} × the exchanges; the hierarchical
legs run on a SIMULATED 2-host split (``inter_size=2`` → dcn 2 × ici
4) of the 8-device CPU mesh.

Compile budget: every run here is a small MLP step (~1 s CPU compile);
the grid is kept to ~a dozen compiles so the suite stays tier-1-cheap.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chainermn_tpu as ct
from chainermn_tpu.core.optimizer import SGD, MomentumSGD
from chainermn_tpu.models import Classifier, MLP

STEPS = 3
#: tiny bound so even the toy MLP splits into several buckets
TINY_BUCKET_MB = 2000 / 2 ** 20

_BC = {"per_leaf": False, "flat": True, "bucketed": "bucketed",
       "hierarchical_bucketed": "bucketed",
       "striped_bucketed": "bucketed"}
#: exchange names that run on the two-level communicator (simulated
#: 2-host split); *_rs routes through the sharded-update step; the
#: striped names (ISSUE 11) run the multi-path exchange at ratio 0.5 —
#: both fabrics carry half of every bucket, the most adversarial split
#: for the equality grid
_HIER = ("hierarchical", "hierarchical_bucketed", "hierarchical_rs",
         "striped", "striped_bucketed", "striped_rs")
_STRIPED = ("striped", "striped_bucketed", "striped_rs")
STRIPE_RATIO = 0.5
_RS = ("reduce_scatter", "hierarchical_rs", "striped_rs")


def _data(seed=0, n=32, d=8, k=4):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32)),
            jnp.asarray(rng.randint(0, k, n).astype(np.int32)))


def _model():
    return Classifier(MLP(n_units=16, n_out=4, seed=0))


def _run(exchange, double_buffering=False, donate=True, grad_dtype=None,
         steps=STEPS, opt_cls=MomentumSGD, devices=None, **opt_kw):
    """Trajectory (losses, params) of one exchange variant.

    ``exchange``: per_leaf | flat | bucketed (communicator flavors of
    the allreduce) | reduce_scatter (the optimizer-level step variant)
    | hierarchical / hierarchical_bucketed / hierarchical_rs (the same
    structures on the two-level communicator, simulated 2-host split).
    """
    opt_kw = opt_kw or dict(lr=0.1, momentum=0.9)
    comm = ct.create_communicator(
        "hierarchical" if exchange in _HIER else "jax_ici",
        devices=devices,
        inter_size=2 if exchange in _HIER else None,
        batch_collectives=_BC.get(exchange, True),
        bucket_mb=TINY_BUCKET_MB if "bucketed" in exchange else None,
        stripe_ratio=STRIPE_RATIO if exchange in _STRIPED else None,
        allreduce_grad_dtype=grad_dtype)
    model = _model()
    comm.bcast_data(model)
    inner = opt_cls(**opt_kw)
    inner.donate_params = donate
    opt = ct.create_multi_node_optimizer(
        inner, comm, double_buffering=double_buffering,
        exchange="reduce_scatter" if exchange in _RS
        else "allreduce").setup(model)
    x, t = _data()
    losses = [float(opt.update(model, x, t)) for _ in range(steps)]
    return losses, [np.asarray(p.array) for p in model.params()], opt


def _golden(steps=STEPS, opt_cls=MomentumSGD, **opt_kw):
    """Single-device trajectory on the merged batch (the golden rule's
    reference point — no communicator at all)."""
    opt_kw = opt_kw or dict(lr=0.1, momentum=0.9)
    model = _model()
    opt = opt_cls(**opt_kw).setup(model)
    x, t = _data()
    losses = [float(opt.update(model, x, t)) for _ in range(steps)]
    return losses, [np.asarray(p.array) for p in model.params()]


@pytest.fixture(scope="module")
def golden():
    return _golden()


@pytest.mark.parametrize("exchange",
                         ["per_leaf", "flat", "bucketed",
                          "reduce_scatter", "hierarchical",
                          "hierarchical_bucketed", "hierarchical_rs",
                          "striped", "striped_bucketed", "striped_rs"])
def test_exchange_matches_single_device_golden(exchange, golden):
    """Acceptance bar: all exchange variants — including the two-level
    hierarchical AND multi-path striped ones on the simulated 2-host
    mesh — golden-equal to the single-device trajectory on the CPU
    mesh."""
    glosses, gparams = golden
    losses, params, _ = _run(exchange)
    np.testing.assert_allclose(losses, glosses, rtol=1e-5, atol=1e-7,
                               err_msg=f"{exchange} losses diverged")
    for a, g in zip(params, gparams):
        np.testing.assert_allclose(a, g, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{exchange} params diverged")


@pytest.mark.parametrize("n_devices", [8, 1])
def test_allreduce_packings_bitwise_equal(n_devices):
    """per-leaf == flat == bucketed BITWISE: packing changes the
    schedule, not the math (pmean is elementwise).  Over ONE device
    all three are the same program: nothing is packed or exchanged."""
    devices = jax.devices()[:n_devices]
    ref = _run("per_leaf", devices=devices)
    for exchange in ("flat", "bucketed"):
        losses, params, _ = _run(exchange, devices=devices)
        assert losses == ref[0], f"{exchange} losses differ bitwise"
        for a, b in zip(params, ref[1]):
            np.testing.assert_array_equal(a, b)


def test_double_buffering_grid_equal():
    """Double buffering × {flat, bucketed, reduce_scatter,
    hierarchical, hierarchical_rs}: the one-step-stale semantics are
    exchange-independent (first update applies zeros, update t applies
    grads of t-1) — including the reduce-scatter variants, whose stale
    buffer is the sharded chunk (on the hierarchical mesh: the
    1/(ici·dcn) chunk in the fast-hop-major layout)."""
    ref = _run("flat", double_buffering=True, steps=4)
    # stale application is observable: step 2's loss equals step 1's
    assert ref[0][0] == ref[0][1]
    for exchange in ("bucketed", "reduce_scatter", "hierarchical",
                     "hierarchical_rs", "striped", "striped_rs"):
        losses, params, _ = _run(exchange, double_buffering=True, steps=4)
        np.testing.assert_allclose(losses, ref[0], rtol=1e-5, atol=1e-7,
                                   err_msg=f"db×{exchange} diverged")
        for a, b in zip(params, ref[1]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("exchange", ["reduce_scatter", "hierarchical",
                                      "striped", "striped_rs"])
def test_donation_off_matches_donation_on(exchange):
    """The donation axis of the grid, on the sharded-update and
    two-level steps: buffer aliasing must not change the trajectory."""
    on = _run(exchange, donate=True)
    off = _run(exchange, donate=False)
    np.testing.assert_allclose(on[0], off[0], rtol=1e-6, atol=1e-8)
    for a, b in zip(on[1], off[1]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_compressed_dtype_composes():
    """bf16 gradient compression per bucket: bucketed and flat compress
    identically (bitwise — same cast, same elementwise mean), and the
    compressed reduce-scatter step stays finite and learns.  bf16 is
    NOT golden-exact vs f32 by design, so no golden assert here."""
    flat = _run("flat", grad_dtype="bfloat16")
    bucketed = _run("bucketed", grad_dtype="bfloat16")
    assert flat[0] == bucketed[0]
    for a, b in zip(flat[1], bucketed[1]):
        np.testing.assert_array_equal(a, b)
    rs_losses, _, _ = _run("reduce_scatter", grad_dtype="bfloat16",
                           steps=5)
    assert np.isfinite(rs_losses).all() and rs_losses[-1] < rs_losses[0]
    # hierarchical × bf16 (BOTH hops compressed): chained per-hop sums
    # reorder bf16 roundings, so equality to the flat bf16 leg is
    # approximate at bf16 precision — and the run must learn
    h_losses, _, _ = _run("hierarchical", grad_dtype="bfloat16", steps=5)
    np.testing.assert_allclose(h_losses[:3], flat[0], rtol=5e-3,
                               err_msg="hier×bf16 far from flat×bf16")
    assert np.isfinite(h_losses).all() and h_losses[-1] < h_losses[0]


def test_per_hop_dtype_stays_close_to_lossless():
    """allreduce_grad_dtype={'dcn': 'bfloat16'} (lossless ICI +
    compressed DCN — the knob that halves only the slow hop's bytes):
    trajectory stays within bf16 rounding of the f32 hierarchical run
    and learns."""
    f32 = _run("hierarchical", steps=5)
    dcn = _run("hierarchical", grad_dtype={"dcn": "bfloat16"}, steps=5)
    np.testing.assert_allclose(dcn[0], f32[0], rtol=5e-3,
                               err_msg="dcn-bf16 far from lossless")
    assert dcn[0][-1] < dcn[0][0]


def test_striped_bf16_composes():
    """Compressed-dtype axes × striping: a scalar bf16 dtype (both
    hops, both paths) stays within bf16 rounding of the flat bf16
    trajectory and learns; the per-hop {'dcn': bf16} variant (only the
    DCN-fabric crossings of BOTH paths compressed) stays within bf16
    rounding of the lossless striped run."""
    flat = _run("flat", grad_dtype="bfloat16", steps=5)
    s_bf16 = _run("striped", grad_dtype="bfloat16", steps=5)
    np.testing.assert_allclose(s_bf16[0], flat[0], rtol=5e-3,
                               err_msg="striped×bf16 far from flat×bf16")
    assert np.isfinite(s_bf16[0]).all() and s_bf16[0][-1] < s_bf16[0][0]
    f32 = _run("striped", steps=5)
    dcn = _run("striped", grad_dtype={"dcn": "bfloat16"}, steps=5)
    np.testing.assert_allclose(dcn[0], f32[0], rtol=5e-3,
                               err_msg="striped dcn-bf16 far from lossless")
    assert dcn[0][-1] < dcn[0][0]


def test_striped_dcn_only_stale_degenerates():
    """The DCN-slice-only double-buffering variant (ISSUE 11,
    ``double_buffering="dcn"``): per-path staleness interpolates
    between the fresh and fully-stale trajectories, pinned at the
    degenerate ratios — ratio 1 (everything on the DCN path) equals
    FULL double buffering bitwise-close, and the mid-ratio run is a
    genuine third trajectory that still learns."""
    def run_ratio(ratio, db, steps=4):
        comm = ct.create_communicator("hierarchical", inter_size=2,
                                      batch_collectives=True,
                                      stripe_ratio=ratio)
        model = _model()
        comm.bcast_data(model)
        opt = ct.create_multi_node_optimizer(
            MomentumSGD(lr=0.1, momentum=0.9), comm,
            double_buffering=db).setup(model)
        x, t = _data()
        return [float(opt.update(model, x, t)) for _ in range(steps)], opt

    full, _ = run_ratio(1.0, True)
    dcn_only, _ = run_ratio(1.0, "dcn")
    np.testing.assert_allclose(dcn_only, full, rtol=1e-6, atol=1e-7,
                               err_msg="ratio-1 dcn-stale != full stale")
    mid, opt = run_ratio(0.5, "dcn")
    fresh, _ = run_ratio(0.5, False)
    assert np.isfinite(mid).all() and mid[-1] < mid[0]
    # genuinely between the two: not the fresh trajectory, not the full
    # one-step-stale one (the ICI half is fresh, the DCN half stale)
    assert mid != fresh
    assert mid != full
    # footprint claim: the stale buffer is the DCN slices only
    assert opt._stale_grads.shape[0] == \
        opt.communicator.grad_dcn_stale_len_for(opt.target)


def test_striped_dcn_only_stale_resume_bit_exact(tmp_path):
    """The DCN-slice stale buffer is OBSERVABLE state like every other
    stale buffer: same-size serialize → restore → continue is
    bit-exact."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    _, _, opt = _run("striped", double_buffering="dcn", steps=2)
    assert opt._stale_grads is not None
    save_npz(path, opt)
    cont_ref = [float(opt.update(opt.target, x, t)) for _ in range(2)]

    _, _, fresh = _run("striped", double_buffering="dcn", steps=1)
    load_npz(path, fresh)
    cont = [float(fresh.update(fresh.target, x, t)) for _ in range(2)]
    np.testing.assert_allclose(cont, cont_ref, rtol=0, atol=0)


def test_double_buffered_striped_rs_resume_bit_exact(tmp_path):
    """The striped sharded update's stale PAIR (fast- and slow-hop-
    major chunks) round-trips through the flat-vector serialization
    bit-exactly, like the single-layout chunk does."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    _, _, opt = _run("striped_rs", double_buffering=True, steps=2)
    save_npz(path, opt)
    cont_ref = [float(opt.update(opt.target, x, t)) for _ in range(2)]

    _, _, fresh = _run("striped_rs", double_buffering=True, steps=1)
    load_npz(path, fresh)
    cont = [float(fresh.update(fresh.target, x, t)) for _ in range(2)]
    np.testing.assert_allclose(cont, cont_ref, rtol=0, atol=0)


def test_striped_rs_quantized_wire_rejected():
    """The slow-hop-major chain has no quantized psum_scatter shape:
    int8 × striped × reduce_scatter is a LOUD construction error, not
    a silently lossless run."""
    comm = ct.create_communicator("hierarchical", inter_size=2,
                                  stripe_ratio=0.5,
                                  allreduce_grad_dtype={"dcn": "int8"})
    with pytest.raises(ValueError, match="striped"):
        ct.create_multi_node_optimizer(
            MomentumSGD(lr=0.1), comm, exchange="reduce_scatter")


def test_hierarchical_rs_grad_not_populated():
    """The sharded-update contract holds on the two-level step too:
    the full mean gradient never materializes."""
    _, _, opt = _run("hierarchical_rs")
    assert all(p.grad is None for p in opt.target.params())


def test_striped_rs_grad_not_populated():
    """Same contract on the striped pair-layout step."""
    _, _, opt = _run("striped_rs")
    assert all(p.grad is None for p in opt.target.params())


def test_hierarchical_update_scan_continues_trajectory():
    """hierarchical × fused K-step dispatch: the scan continues the
    SAME trajectory as the golden run's steps 4-5 (both the allreduce
    and the sharded-update hierarchical steps drive the scan maker)."""
    glosses, _ = _golden(steps=5)
    for exchange in ("hierarchical", "hierarchical_rs", "striped",
                     "striped_rs"):
        losses, _, opt = _run(exchange, steps=3)
        x, t = _data()
        scan_losses = np.asarray(opt.update_scan(
            opt.target, jnp.stack([x, x]), jnp.stack([t, t])))
        np.testing.assert_allclose(list(losses) + list(scan_losses),
                                   glosses, rtol=1e-5, atol=1e-7,
                                   err_msg=f"{exchange} scan diverged")


def test_double_buffered_hierarchical_rs_resume_bit_exact(tmp_path):
    """Serialize → restore → continue is bit-exact for the
    hierarchical reduce-scatter double-buffering pair: the stale chunk
    (fast-hop-major layout) round-trips through the flat-vector
    serialization exactly like the one-axis layout does."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    losses_a, _, opt = _run("hierarchical_rs", double_buffering=True,
                            steps=2)
    save_npz(path, opt)
    cont_ref = [float(opt.update(opt.target, x, t)) for _ in range(2)]

    _, _, fresh = _run("hierarchical_rs", double_buffering=True, steps=1)
    load_npz(path, fresh)
    cont = [float(fresh.update(fresh.target, x, t)) for _ in range(2)]
    np.testing.assert_allclose(cont, cont_ref, rtol=0, atol=0)


def test_moe_two_stage_dispatch_golden_equal_flat():
    """ISSUE 12: the two-stage (ici → dcn) MoE token dispatch on the
    simulated 2×4 split is GOLDEN-EQUAL — bit for bit — to the flat
    single-axis dispatch: the two stages compose to the exact same
    permutation as the joint-axis all_to_all, so routing, capacity
    drops, expert compute, and combine weights all coincide.  Checked
    at the full dispatch+combine level (a real expert MLP), against
    BOTH flat references: the explicit ``two_stage=False`` escape on
    the same hierarchical communicator AND a genuinely flat one-axis
    communicator over the same devices."""
    from jax.sharding import PartitionSpec as P
    from chainermn_tpu.parallel import switch_moe

    hier = ct.create_communicator("hierarchical", inter_size=2)
    flat = ct.create_communicator("jax_ici", axis_name="moe_flat_ref")
    E = hier.size
    D, H, T = 8, 16, 8
    rng = np.random.RandomState(17)
    x = jnp.asarray(rng.normal(0, 1, (E * T, D)).astype(np.float32))
    router = jnp.asarray(rng.normal(0, 0.5, (D, E)).astype(np.float32))
    w_in = jnp.asarray(rng.normal(0, 0.3, (D, H)).astype(np.float32))
    w_out = jnp.asarray(rng.normal(0, 0.3, (H, D)).astype(np.float32))
    b_in = jnp.zeros((H,), jnp.float32)
    b_out = jnp.zeros((D,), jnp.float32)

    def run(comm, two_stage):
        def body(x, router, w_in, b_in, w_out, b_out):
            out, aux = switch_moe(comm, x, router, w_in, b_in, w_out,
                                  b_out, capacity_factor=1.0,
                                  two_stage=two_stage)
            return out, aux["dropped_frac"].reshape(1)
        axes = comm.axis_name
        return comm.run_spmd(
            body, x, router, w_in, b_in, w_out, b_out,
            in_specs=(P(axes), P(), P(), P(), P(), P()),
            out_specs=(P(axes), P(axes)))

    out_two, drop_two = run(hier, True)
    out_hflat, drop_hflat = run(hier, False)
    out_flat, drop_flat = run(flat, None)
    np.testing.assert_array_equal(np.asarray(out_two),
                                  np.asarray(out_hflat))
    np.testing.assert_array_equal(np.asarray(out_two),
                                  np.asarray(out_flat))
    np.testing.assert_array_equal(np.asarray(drop_two),
                                  np.asarray(drop_flat))


def test_reduce_scatter_grad_not_populated():
    """The documented sharded-update contract holds for the plain-DP
    reduce-scatter step too: the full mean gradient never materializes,
    so Parameter.grad stays None."""
    _, _, opt = _run("reduce_scatter")
    assert all(p.grad is None for p in opt.target.params())


def test_reduce_scatter_update_scan_continues_trajectory(golden):
    """exchange="reduce_scatter" × fused K-step dispatch: the scan
    continues the SAME trajectory as the golden run's steps 4-5."""
    glosses, _ = _golden(steps=5)
    losses, _, opt = _run("reduce_scatter", steps=3)
    x, t = _data()
    scan_losses = np.asarray(opt.update_scan(
        opt.target, jnp.stack([x, x]), jnp.stack([t, t])))
    np.testing.assert_allclose(list(losses) + list(scan_losses), glosses,
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("exchange,db", [("hierarchical", False),
                                         ("hierarchical_rs", False),
                                         ("hierarchical_rs", True),
                                         ("striped", False),
                                         ("striped", "dcn")])
def test_quantized_residual_resume_bit_exact(tmp_path, exchange, db):
    """The error-feedback residual is OBSERVABLE state (ISSUE 8): a
    same-size serialize → restore → continue is bit-exact — the
    telescoping sum (applied + residual == true) survives the
    checkpoint — on the allreduce, sharded-update, and
    double-buffered×rs quantized paths."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    _, _, opt = _run(exchange, double_buffering=db,
                     grad_dtype={"dcn": "int8"}, steps=2)
    assert opt._residual is not None
    save_npz(path, opt)
    cont_ref = [float(opt.update(opt.target, x, t)) for _ in range(2)]

    _, _, fresh = _run(exchange, double_buffering=db,
                       grad_dtype={"dcn": "int8"}, steps=1)
    load_npz(path, fresh)
    assert fresh._residual is not None
    cont = [float(fresh.update(fresh.target, x, t)) for _ in range(2)]
    np.testing.assert_allclose(cont, cont_ref, rtol=0, atol=0)


def test_quantized_residual_pre_feature_snapshot_zero_seeds(tmp_path):
    """A snapshot saved WITHOUT error feedback (no ef_residual section)
    loads onto an EF run with fresh zero-seed semantics — no crash, no
    stale residual invented."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()
    _, _, plain = _run("hierarchical", steps=2)  # lossless: no residual
    save_npz(path, plain)
    _, _, ef = _run("hierarchical", grad_dtype={"dcn": "int8"}, steps=2)
    assert ef._residual is not None
    load_npz(path, ef)
    assert ef._residual is None  # zero-seeds on the next update
    assert np.isfinite(float(ef.update(ef.target, x, t)))


def _run_sized(exchange, n_devices, double_buffering=False,
               grad_dtype=None, steps=2):
    """Like :func:`_run` but over an explicit device-count world — the
    changed-communicator-size resume grid (ISSUE 10 satellite).  The
    hierarchical legs keep the forced dcn=2 split, so 8 devices = 2×4
    and 4 devices = 2×2: a genuinely different chunk partition."""
    comm = ct.create_communicator(
        "hierarchical" if exchange in _HIER else "jax_ici",
        devices=jax.devices()[:n_devices],
        inter_size=2 if exchange in _HIER else None,
        batch_collectives=_BC.get(exchange, True),
        allreduce_grad_dtype=grad_dtype)
    model = _model()
    comm.bcast_data(model)
    inner = MomentumSGD(lr=0.1, momentum=0.9)
    opt = ct.create_multi_node_optimizer(
        inner, comm, double_buffering=double_buffering,
        exchange="reduce_scatter"
        if exchange in ("reduce_scatter", "hierarchical_rs")
        else "allreduce").setup(model)
    x, t = _data()
    losses = [float(opt.update(model, x, t)) for _ in range(steps)]
    return losses, opt


def test_size_changed_resume_reseeds_ef_residual(tmp_path):
    """ISSUE 10 satellite: the re-seed-zeros contract for the
    error-feedback ``_residual`` was documented but only SAME-size
    resume was pinned.  Changed size: a snapshot from the 2×4 world
    loads into a 2×2 world — params carry over, the residual (per-
    DEVICE quantization error, meaningless under a new partition)
    re-seeds zeros, and training continues finite."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    _, opt8 = _run_sized("hierarchical", 8, grad_dtype={"dcn": "int8"})
    assert opt8._residual is not None
    save_npz(path, opt8)
    saved_params = [np.asarray(p.array) for p in opt8.target.params()]

    _, opt4 = _run_sized("hierarchical", 4, grad_dtype={"dcn": "int8"})
    assert opt4._residual is not None
    load_npz(path, opt4)
    # params resumed from the snapshot bit-exact (size-independent)...
    for a, b in zip(opt4.target.params(), saved_params):
        np.testing.assert_array_equal(np.asarray(a.array), b)
    # ...the residual re-seeded (zero on the next update), explicitly
    # EXCLUDED from the bit-exact contract
    assert opt4._residual is None
    assert np.isfinite(float(opt4.update(opt4.target, x, t)))


def test_size_changed_resume_reseeds_sharded_ef_residual(tmp_path):
    """Same pin for the sharded-update (hierarchical_rs) residual: its
    length follows the flat chunk layout, so a changed world size can
    never reuse it — zero-seed, while the flat opt-state re-pads to
    the new multiple (the PR 5 brick)."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    _, opt8 = _run_sized("hierarchical_rs", 8,
                         grad_dtype={"dcn": "int8"})
    assert opt8._residual is not None
    save_npz(path, opt8)

    _, opt4 = _run_sized("hierarchical_rs", 4,
                         grad_dtype={"dcn": "int8"})
    load_npz(path, opt4)
    assert opt4._residual is None  # re-seeded
    _, n, n_pad = opt4._zero_layout
    assert n_pad % 4 == 0
    # the flat opt-state slices to the true length and re-pads to the
    # NEW world's multiple — the compiled step runs on it directly
    assert np.isfinite(float(opt4.update(opt4.target, x, t)))


def test_size_changed_resume_repads_stale_chunk(tmp_path):
    """The double-buffer stale CHUNK has the complementary contract: it
    is GLOBAL content (the flat one-step-stale mean gradient), so a
    size-changed resume slices/re-pads it instead of zero-seeding —
    the first resumed update still applies the saved step's gradient."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    _, opt8 = _run_sized("reduce_scatter", 8, double_buffering=True)
    assert opt8._stale_grads is not None
    saved = np.asarray(opt8._stale_grads)
    save_npz(path, opt8)

    _, opt4 = _run_sized("reduce_scatter", 4, double_buffering=True)
    load_npz(path, opt4)
    assert opt4._stale_grads is not None
    _, n, n_pad4 = opt4._zero_layout
    restored = np.asarray(opt4._stale_grads)
    assert restored.shape[0] == n_pad4
    np.testing.assert_array_equal(restored[:n], saved[:n])
    assert np.isfinite(float(opt4.update(opt4.target, x, t)))


def test_double_buffered_reduce_scatter_resume_bit_exact(tmp_path):
    """Serialize → restore → continue must be bit-exact for the
    reduce-scatter double-buffering pair: the stale CHUNK is observable
    state (without it a resumed run would apply zeros on its first
    update)."""
    from chainermn_tpu.serializers import load_npz, save_npz
    path = str(tmp_path / "snap.npz")
    x, t = _data()

    losses_a, _, opt = _run("reduce_scatter", double_buffering=True,
                            steps=2)
    save_npz(path, opt)
    cont_ref = [float(opt.update(opt.target, x, t)) for _ in range(2)]

    _, _, fresh = _run("reduce_scatter", double_buffering=True, steps=1)
    load_npz(path, fresh)
    cont = [float(fresh.update(fresh.target, x, t)) for _ in range(2)]
    np.testing.assert_allclose(cont, cont_ref, rtol=0, atol=0)
