"""Striped multi-path exchange: the plan's cross-rank contract
(ISSUE 11).

The two slices' collectives only line up across ranks because every
rank traces the IDENTICAL split from the identical ``(n_elems, ratio)``
inputs — these tests pin the properties that contract rests on (every
element in exactly one slice, contiguity, the committed ratio honored,
degenerate collapse, cross-process determinism), the generalized
striped ``hop_schedule`` ordering, the per-path byte identities, and
the knob plumbing.  Numeric equivalence of the striped exchange lives
in tests/core_tests/test_exchange_equivalence.py; the traced per-path
structure is gated by tests/test_comm_budget.py.
"""

import numpy as np
import pytest

import chainermn_tpu as ct
from chainermn_tpu.communicators._memory_utility import (
    DEFAULT_STRIPE_RATIO, exchanged_bytes, hop_schedule, stripe_plan,
    striped_exchanged_bytes)


def test_every_element_in_exactly_one_slice():
    rng = np.random.RandomState(0)
    for _ in range(50):
        n = int(rng.randint(0, 1 << 20))
        ratio = float(rng.uniform(0, 1))
        n_i, n_d = stripe_plan(n, ratio)
        assert n_i >= 0 and n_d >= 0
        assert n_i + n_d == n, (n, ratio)


def test_ratio_respected():
    """The DCN share is the committed ratio rounded to whole elements
    — never off by more than the rounding of one element."""
    rng = np.random.RandomState(1)
    for _ in range(50):
        n = int(rng.randint(1, 1 << 20))
        ratio = float(rng.uniform(0, 1))
        _, n_d = stripe_plan(n, ratio)
        assert n_d == int(round(ratio * n))
        assert abs(n_d - ratio * n) <= 0.5


def test_degenerate_ratios_collapse_to_single_path():
    """ratio 0 == the strict hierarchical plan (everything on the
    fast-hop-major path); ratio 1 routes the whole payload over the
    slow-hop-major path — the one-fabric flat shape with DCN as the
    bulk wire."""
    for n in (0, 1, 17, 4096):
        assert stripe_plan(n, 0.0) == (n, 0)
        assert stripe_plan(n, 1.0) == (0, n)


def test_cross_process_determinism():
    """Pure function of the inputs: two traces (two ranks) produce the
    identical split — including at awkward float ratios."""
    for n in (7, 1000, 999999):
        for ratio in (0.1, 0.25, 1 / 3, 0.5, 0.75):
            assert stripe_plan(n, ratio) == stripe_plan(n, ratio)


def test_stripe_plan_rejects_bad_inputs():
    with pytest.raises(ValueError, match="ratio"):
        stripe_plan(10, -0.1)
    with pytest.raises(ValueError, match="ratio"):
        stripe_plan(10, 1.1)
    with pytest.raises(ValueError, match="n_elems"):
        stripe_plan(-1, 0.5)


def test_striped_hop_schedule_ordering():
    """The striped schedule's contract: per path dataflow order holds,
    the slow path's op leads each phase, and EVERY scatter/exchange op
    of both paths precedes ANY bucket's gather epilogue (the
    concurrency window the census hop_ordered gate validates)."""
    assert hop_schedule(0, mode="striped") == []
    for k in (1, 2, 5):
        sched = hop_schedule(k, mode="striped")
        assert len(sched) == 6 * k
        pos = {pair: i for i, pair in enumerate(sched)}
        for b in range(k):
            # per-path dataflow
            assert pos[("dcn_path_scatter", b)] \
                < pos[("dcn_path_exchange", b)] \
                < pos[("dcn_path_gather", b)]
            assert pos[("ici_path_scatter", b)] \
                < pos[("ici_path_exchange", b)] \
                < pos[("ici_path_gather", b)]
            # slow path leads each phase of its bucket
            assert pos[("dcn_path_scatter", b)] \
                < pos[("ici_path_scatter", b)]
            assert pos[("dcn_path_gather", b)] \
                < pos[("ici_path_gather", b)]
        last_phase1 = max(pos[(op, b)] for b in range(k)
                          for op in ("dcn_path_scatter", "ici_path_scatter",
                                     "dcn_path_exchange",
                                     "ici_path_exchange"))
        first_gather = min(pos[(op, b)] for b in range(k)
                           for op in ("dcn_path_gather",
                                      "ici_path_gather"))
        assert last_phase1 < first_gather
    with pytest.raises(ValueError, match="mode"):
        hop_schedule(1, mode="diagonal")


def test_striped_bytes_conservation_and_share():
    """The per-path accounting's two identities, exact on cleanly
    dividing splits: path totals sum to the flat allreduce figure over
    intra×inter ranks, and the DCN path's share IS the ratio."""
    for n, intra, inter, ratio in ((3200, 4, 2, 0.25),
                                   (3200, 4, 2, 0.5),
                                   (1 << 20, 8, 4, 0.75)):
        paths = striped_exchanged_bytes(n, intra, inter, ratio)
        total = paths["ici_path"]["total"] + paths["dcn_path"]["total"]
        assert total == exchanged_bytes(n, intra * inter, "psum"), \
            (n, intra, inter, ratio)
        assert paths["dcn_path"]["total"] / total == ratio
        # fabric split inside each path: the ICI path's bulk rides ici,
        # the DCN path's bulk rides dcn
        assert paths["ici_path"]["ici"] > paths["ici_path"]["dcn"] \
            or ratio == 1.0
        assert paths["dcn_path"]["dcn"] > paths["dcn_path"]["ici"]


def test_striped_bytes_degenerate_ratios():
    flat = exchanged_bytes(3200, 8, "psum")
    r0 = striped_exchanged_bytes(3200, 4, 2, 0.0)
    assert r0["dcn_path"]["total"] == 0
    assert r0["ici_path"]["total"] == flat
    r1 = striped_exchanged_bytes(3200, 4, 2, 1.0)
    assert r1["ici_path"]["total"] == 0
    assert r1["dcn_path"]["total"] == flat


def test_striped_bytes_dcn_dtype_halves_only_dcn_fabric():
    f32 = striped_exchanged_bytes(3200, 4, 2, 0.5)
    bf16 = striped_exchanged_bytes(3200, 4, 2, 0.5, dcn_itemsize=2)
    # ICI-fabric crossings untouched on both paths
    assert bf16["ici_path"]["ici"] == f32["ici_path"]["ici"]
    assert bf16["dcn_path"]["ici"] == f32["dcn_path"]["ici"]
    # DCN-fabric crossings halve on both paths
    assert bf16["ici_path"]["dcn"] * 2 == f32["ici_path"]["dcn"]
    assert bf16["dcn_path"]["dcn"] * 2 == f32["dcn_path"]["dcn"]


# -- knob plumbing -----------------------------------------------------------

def test_communicator_stripe_knobs():
    comm = ct.create_communicator("hierarchical", inter_size=2,
                                  stripe_ratio=0.25)
    assert comm.striped and comm.stripe_ratio == 0.25
    assert comm.topology == "striped"
    # ratio 0 is the strict hierarchical schedule
    comm = ct.create_communicator("hierarchical", inter_size=2,
                                  stripe_ratio=0.0)
    assert not comm.striped and comm.topology == "hierarchical"
    with pytest.raises(ValueError, match="stripe_ratio"):
        ct.create_communicator("hierarchical", inter_size=2,
                               stripe_ratio=1.5)
    # a flat mesh has one fabric: nothing to stripe
    with pytest.raises(ValueError, match="stripe_ratio"):
        ct.create_communicator("jax_ici", stripe_ratio=0.5)


def test_stripe_ratio_env_knob(monkeypatch):
    monkeypatch.setenv("CHAINERMN_TPU_STRIPE_RATIO", "0.5")
    comm = ct.create_communicator("hierarchical", inter_size=2)
    assert comm.striped and comm.stripe_ratio == 0.5
    # explicit argument wins over the env
    comm = ct.create_communicator("hierarchical", inter_size=2,
                                  stripe_ratio=0.25)
    assert comm.stripe_ratio == 0.25
    # a flat communicator never reads the knob (nothing to stripe —
    # a stray env var must not break the flat flavors)
    flat = ct.create_communicator("jax_ici")
    assert not flat.striped and flat.stripe_ratio == 0.0


def test_hierarchy_flat_hatch_drops_striping(monkeypatch):
    """CHAINERMN_TPU_HIERARCHY=flat degrades a striped communicator to
    the flat single-path exchange — loudly, never silently."""
    monkeypatch.setenv("CHAINERMN_TPU_HIERARCHY", "flat")
    from chainermn_tpu import communicators as C
    monkeypatch.setattr(C, "_WARNED_FLAT_STRIPES", set())
    with pytest.warns(UserWarning, match="stripe_ratio"):
        comm = ct.create_communicator("hierarchical", inter_size=2,
                                      stripe_ratio=0.25)
    assert comm.hierarchy is None and not comm.striped
    assert comm.topology == "flat"


def test_striped_pair_is_spelled_directly():
    """``striped`` / ``striped_rs`` (tools/comm_budgets.json's names)
    are the hierarchical communicator with a nonzero ratio under the
    allreduce / reduce-scatter step: (communicator name,
    batch_collectives, exchange=), with no table between."""
    from chainermn_tpu.core.optimizer import Adam
    assert DEFAULT_STRIPE_RATIO == 0.25
    for exchange in ("allreduce", "reduce_scatter"):
        comm = ct.create_communicator(
            "hierarchical", inter_size=2, batch_collectives=True,
            stripe_ratio=DEFAULT_STRIPE_RATIO)
        opt = ct.create_multi_node_optimizer(Adam(), comm,
                                             exchange=exchange)
        assert comm.topology == "striped"
        assert opt.exchange == exchange


def test_grad_dcn_stale_len_matches_plan():
    """The DCN-slice stale buffer's length is the sum of the buckets'
    DCN-path slices — the stripe_ratio fraction of the gradient, the
    footprint claim of the dcn-only double-buffering variant."""
    from chainermn_tpu.models import MLP
    comm = ct.create_communicator("hierarchical", inter_size=2,
                                  stripe_ratio=0.5)
    model = MLP(n_units=16, n_out=4, seed=0)
    # materialize params
    import jax.numpy as jnp
    model(jnp.zeros((2, 8), jnp.float32))
    shapes, dtypes = comm.grad_leaf_specs(model)
    from chainermn_tpu.communicators._memory_utility import stripe_plan
    expect = sum(
        stripe_plan(sum(int(np.prod(shapes[i])) for i in idx), 0.5)[1]
        for idx in comm.grad_buckets(shapes, dtypes))
    assert comm.grad_dcn_stale_len_for(model) == expect
    assert expect > 0
    flat = ct.create_communicator("jax_ici")
    assert flat.grad_dcn_stale_len_for(model) == 0
