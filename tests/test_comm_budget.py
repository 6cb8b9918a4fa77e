"""Gradient-exchange budget gate (ISSUE 5: the comm structure can't rot).

Mirrors tests/test_flash_budget.py: tools/comm_budgets.json commits the
DP step's collective structure and this gate holds every future PR to
it.  Two layers:

* STRUCTURE (backend-neutral, checked here on the simulated CPU mesh):
  a jaxpr census of the REAL compiled step per exchange config —
  per-leaf/flat/bucketed psum counts, the reduce-scatter step's
  reduce_scatter+all_gather replacing the full-gradient allreduce, and
  the exchanged-bytes accounting (gradient bytes exactly halved).
  ISSUE 6 adds the hierarchical (ici × dcn) configs on a simulated
  2-host split: per-hop collective counts resolved from eqn axis
  names, the DCN gradient payload pinned at exactly 1/intra_size, the
  slow-hop-first emission order, and per-hop dtype compression.
  Verified against the traced program, not against documentation.
* NUMBERS (measured on chip by the bucket sweep /
  exposed-comm A/B): dormant while ``sweep.status`` is
  ``pending_on_chip``; arms when rows are stamped ``measured``.

The census traces all five committed configs over ONE shared vertical
(model built once per process — see comm_census._Vertical), so the
whole gate costs seconds, not minutes, of tier-1 time.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import comm_census  # noqa: E402


@pytest.fixture(scope="module")
def budgets():
    with open(comm_census.BUDGETS_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def live(budgets):
    """The live census of every committed config, traced once."""
    import jax
    assert len(jax.devices()) == budgets["vertical"]["n_devices"], \
        "census devices != committed vertical (conftest pins 8)"
    return {name: comm_census.config_row(name)
            for name in comm_census.CONFIGS}


def test_budget_schema(budgets):
    assert set(budgets["structure"]) == set(comm_census.CONFIGS)
    assert budgets["grad_elems_floor"] == comm_census.GRAD_ELEMS_FLOOR
    v = budgets["vertical"]
    assert {k: v[k] for k in comm_census.VERTICAL} == comm_census.VERTICAL
    assert budgets["sweep"]["status"] in ("pending_on_chip", "measured")
    # ISSUE 12: the MoE dispatch census is a sibling section
    assert set(budgets["moe"]["structure"]) == set(comm_census.MOE_CONFIGS)
    mv = budgets["moe"]["vertical"]
    assert {k: mv[k] for k in comm_census.MOE_VERTICAL} == \
        comm_census.MOE_VERTICAL


def test_structure_census_matches_committed(budgets, live):
    """The machine check: the committed structure is what the step
    TRACES today, config by config.  A PR that changes bucketing, the
    packing, or the reduce-scatter wiring must regenerate the budgets
    (tools/comm_census.py --write-budgets) and own the diff."""
    for name, row in live.items():
        committed = dict(budgets["structure"][name])
        committed.pop("config", None)
        assert row == committed, (
            f"{name}: exchange structure drifted.\n traced    {row}\n "
            f"committed {committed}\nRegenerate tools/comm_budgets.json "
            "via `python tools/comm_census.py --write-budgets` if the "
            "change is intentional.")


def test_flat_is_one_collective(live):
    assert live["flat"]["grad_collectives"] == {"psum": 1}


def test_per_leaf_is_one_collective_per_param(live):
    vert = comm_census._Vertical.get()
    assert live["per_leaf"]["grad_collectives"]["psum"] == \
        sum(1 for _ in vert.model.params())


def test_bucketed_emits_multiple_bounded_buckets(budgets, live):
    """The acceptance bar: K>1 collectives at the DEFAULT bucket size on
    the transformer vertical, every bucket within the bound (a single
    oversize leaf may exceed it alone — the embed/head matrices here
    do, by design of the plan)."""
    from chainermn_tpu.communicators._memory_utility import DEFAULT_BUCKET_MB
    row = live["bucketed"]
    k = row["grad_collectives"]["psum"]
    assert k > 1, "bucketed exchange collapsed to one collective"
    import jax.numpy as jnp
    import numpy as np
    bound = DEFAULT_BUCKET_MB * 2 ** 20
    itemsize = jnp.dtype(row["grad_dtype"] or "float32").itemsize
    sizes = [e * itemsize for e in row["grad_collective_elems"]["psum"]]
    vert = comm_census._Vertical.get()
    max_leaf = max(itemsize * int(np.prod(p.shape))
                   for p in vert.model.params())
    for s in sizes:
        assert s <= max(bound, max_leaf)
    # all leaves land in buckets: bucket elems sum to the param count
    assert sum(row["grad_collective_elems"]["psum"]) == vert.n_params


def test_compression_composes_with_bucketing(live):
    """bf16 buckets carry bf16 payloads: exchanged gradient bytes halve
    vs the f32 bucketed config."""
    assert live["bucketed_bf16"]["exchanged_gradient_bytes_per_replica"] \
        * 2 == live["bucketed"]["exchanged_gradient_bytes_per_replica"]


def test_reduce_scatter_replaces_allreduce_and_halves_gradient_bytes(live):
    """The tentpole relation, machine-checked: the reduce-scatter DP
    step's census shows NO full-gradient psum — one reduce_scatter (the
    gradient's single wire crossing) + one all_gather (the params
    rebuild) — and per-replica exchanged GRADIENT bytes are exactly
    half the flat allreduce's."""
    rs = live["reduce_scatter"]
    assert rs["grad_collectives"] == {"reduce_scatter": 1, "all_gather": 1}
    flat = live["flat"]
    assert rs["exchanged_gradient_bytes_per_replica"] * 2 == \
        flat["exchanged_gradient_bytes_per_replica"]
    # the params all-gather is accounted separately, never hidden
    assert rs["exchanged_param_bytes_per_replica"] > 0


def test_hierarchical_per_hop_structure(live):
    """The ISSUE 6 tentpole, machine-checked: the hierarchical step is
    intra-host reduce_scatter over ICI → chunk allreduce over DCN →
    intra-host all_gather over ICI — per-hop counts resolved from the
    eqns' own axis names, never a full-axis gradient collective."""
    row = live["hierarchical"]
    assert row["topology"] == "hierarchical"
    assert row["intra_size"] == 4 and row["inter_size"] == 2
    assert row["per_hop"]["ici"]["collectives"] == \
        {"reduce_scatter": 1, "all_gather": 1}
    assert row["per_hop"]["dcn"]["collectives"] == {"psum": 1}
    # no hop label beyond ici/dcn: a residual full-axis collective
    # would surface as a "both"/"world" key here
    assert set(row["per_hop"]) == {"ici", "dcn"}


def test_hierarchical_dcn_payload_ratio_pinned(budgets, live):
    """Acceptance bar: DCN only ever carries 1/intra_size of the
    gradient — pinned from the traced operand sizes on every
    hierarchical config."""
    for name, row in live.items():
        if row.get("topology") != "hierarchical":
            continue
        assert row["dcn_grad_payload_ratio"] == \
            pytest.approx(1.0 / row["intra_size"], abs=0), name
        assert budgets["structure"][name]["dcn_grad_payload_ratio"] == \
            row["dcn_grad_payload_ratio"]


def test_hierarchical_slow_hop_first_schedule(live):
    """hop_schedule's ordering promise survives tracing: every DCN
    collective is emitted before ANY fast-hop all_gather (the slow hop
    starts first; ICI rebuilds overlap the remaining DCN traffic)."""
    for name, row in live.items():
        if row.get("topology") == "hierarchical":
            assert row["hop_ordered"], name


def test_hierarchical_buckets_compose_with_topology(live):
    """PR 5's bucket planner composes with the two-level exchange: K
    buckets at the default bound → K reduce_scatters, K DCN allreduces,
    K all_gathers — same K as the flat-topology bucketed config."""
    k = live["bucketed"]["grad_collectives"]["psum"]
    row = live["hierarchical_bucketed"]
    assert row["grad_collectives"] == \
        {"reduce_scatter": k, "psum": k, "all_gather": k}


def test_hierarchical_total_bytes_match_flat_ring(live):
    """The ring identity: the hierarchy relocates bytes onto the fast
    wires without adding any — hop totals sum to the flat allreduce's
    per-replica figure (2n(N-1)/N over N = intra × inter)."""
    assert live["hierarchical"]["exchanged_gradient_bytes_per_replica"] \
        == live["flat"]["exchanged_gradient_bytes_per_replica"]


def test_per_hop_dtype_halves_only_dcn(live):
    """allreduce_grad_dtype={'dcn': 'bfloat16'}: the DCN crossing
    halves, ICI stays lossless byte-for-byte."""
    f32 = live["hierarchical"]["per_hop"]
    bf16 = live["hierarchical_dcn_bf16"]["per_hop"]
    assert bf16["ici"]["exchanged_grad_bytes"] == \
        f32["ici"]["exchanged_grad_bytes"]
    assert bf16["dcn"]["exchanged_grad_bytes"] * 2 == \
        f32["dcn"]["exchanged_grad_bytes"]


def test_hierarchical_rs_shards_both_hops(live):
    """exchange='reduce_scatter' × hierarchical: the gradient crosses
    each hop ONCE (rs over ici on the full buffer, rs over dcn on the
    1/intra chunk), the params rebuild all-gathers both hops, and the
    gradient bytes match the flat reduce-scatter exchange (half the
    allreduce) while the DCN share is 1/intra of that."""
    row = live["hierarchical_rs"]
    assert row["per_hop"]["ici"]["collectives"] == \
        {"reduce_scatter": 1, "all_gather": 1}
    assert row["per_hop"]["dcn"]["collectives"] == \
        {"reduce_scatter": 1, "all_gather": 1}
    assert row["exchanged_gradient_bytes_per_replica"] == \
        live["reduce_scatter"]["exchanged_gradient_bytes_per_replica"]
    assert row["exchanged_param_bytes_per_replica"] == \
        live["reduce_scatter"]["exchanged_param_bytes_per_replica"]


def test_quantized_dcn_crossing_at_wire_dtype(live):
    """ISSUE 8 acceptance, machine-checked from the trace: the quantized
    configs' DCN gradient crossing rides the QUANTIZED wire dtype (the
    packed buffer's itemsize, never the gradient dtype), via
    quantize → all_gather (allreduce exchange) / all_to_all (sharded
    update) → dequantize-sum — no full-precision gradient psum ever
    touches DCN — while ICI stays lossless byte-for-byte."""
    f32 = live["hierarchical"]["per_hop"]
    for name, wire in (("hierarchical_int8", "int8"),
                       ("hierarchical_fp8", "float8_e4m3fn")):
        row = live[name]
        assert row["quantized_wire"] == wire, name
        assert row["per_hop"]["dcn"]["collectives"] == {"all_gather": 1}
        assert row["per_hop"]["dcn"]["wire_dtypes"] == [wire], name
        # ICI hop untouched: same collectives, same lossless bytes
        assert row["per_hop"]["ici"] == f32["ici"], name
    rs = live["hierarchical_rs_int8"]
    assert rs["per_hop"]["dcn"]["collectives"] == \
        {"all_to_all": 1, "all_gather": 1}
    # the all_to_all gradient segments are int8; the f32 entry is the
    # params-rebuild all_gather, accounted as param bytes
    assert rs["per_hop"]["dcn"]["wire_dtypes"] == ["float32", "int8"]
    assert rs["per_hop"]["ici"] == live["hierarchical_rs"]["per_hop"]["ici"]


def test_quantized_dcn_payload_pinned_at_quantized_fraction(budgets, live):
    """The acceptance bar: the DCN gradient-payload BYTE ratio of every
    quantized config is the quantized fraction of the lossless one —
    int8/fp8 are 1-byte wires, so exactly 1/4 of the f32 crossing
    (and 1/(4·ici) of the full gradient)."""
    lossless = live["hierarchical"]["dcn_payload_bytes_ratio"]
    for name in ("hierarchical_int8", "hierarchical_fp8",
                 "hierarchical_rs_int8"):
        row = live[name]
        # element payload unchanged (still the 1/ici chunk) ...
        assert row["dcn_grad_payload_ratio"] == \
            pytest.approx(1.0 / row["intra_size"], abs=0), name
        # ... byte payload at the quantized fraction: 1/4 of f32
        assert row["dcn_payload_bytes_ratio"] == \
            pytest.approx(lossless / 4, abs=0), name
        assert row["dcn_payload_bytes_ratio"] <= lossless / 4, name
        assert budgets["structure"][name]["dcn_payload_bytes_ratio"] == \
            row["dcn_payload_bytes_ratio"], name


def test_quantized_keeps_slow_hop_first_order(live):
    """The quantized DCN ops (all_gather of codewords / all_to_all of
    segments) keep hop_schedule's promise: every DCN collective is
    emitted before ANY fast-hop all_gather."""
    for name in ("hierarchical_int8", "hierarchical_fp8",
                 "hierarchical_rs_int8"):
        assert live[name]["hop_ordered"], name


def test_quantized_wire_halves_dcn_bytes_vs_bf16(live):
    """The headline relation at the committed 2-host split: int8 DCN
    grad bytes are half the bf16 crossing and a quarter of the f32 one
    (all_gather of 1-byte codewords at inter=2 == psum of 1-byte
    payload would-be bytes)."""
    f32 = live["hierarchical"]["per_hop"]["dcn"]["exchanged_grad_bytes"]
    bf16 = live["hierarchical_dcn_bf16"]["per_hop"]["dcn"][
        "exchanged_grad_bytes"]
    int8 = live["hierarchical_int8"]["per_hop"]["dcn"][
        "exchanged_grad_bytes"]
    assert bf16 * 2 == f32
    assert int8 * 4 == f32
    assert int8 * 2 == bf16


def test_striped_both_fabrics_carry_bulk(live):
    """The ISSUE 11 tentpole, machine-checked: the striped exchange
    puts a bulk reduce_scatter AND a bulk all_gather on BOTH fabrics
    in one step — the ICI path's rs/ag over ici with its chunk psum
    over dcn, and the transposed DCN path's rs/ag over dcn with its
    chunk psum over ici.  The strict hierarchy's idle-slow-fabric
    window is structurally gone."""
    row = live["striped"]
    assert row["topology"] == "striped"
    assert row["stripe_ratio"] == comm_census.STRIPE_RATIO
    for hop in ("ici", "dcn"):
        assert row["per_hop"][hop]["collectives"] == \
            {"reduce_scatter": 1, "psum": 1, "all_gather": 1}, hop
    assert set(row["per_hop"]) == {"ici", "dcn"}


def test_striped_byte_conservation_identity(budgets, live):
    """Acceptance bar: ici_path + dcn_path bytes of a striped bucket ==
    the flat allreduce bytes of the same payload — striping relocates
    bytes across fabrics, it adds NONE.  Pinned EXACT: the committed
    ratio splits the vertical into slices that divide both rings, so
    no pad slack hides a regression."""
    flat = live["flat"]["exchanged_gradient_bytes_per_replica"]
    for name in ("striped", "striped_bucketed"):
        per_path = live[name]["per_path_bytes"]
        assert set(per_path) == {"ici", "dcn"}, name
        assert per_path["ici"] + per_path["dcn"] == flat, name
        assert budgets["structure"][name]["per_path_bytes"] == per_path


def test_striped_dcn_share_is_committed_ratio(live):
    """Acceptance bar: the DCN path's byte share IS the committed split
    ratio, exactly — per-path totals are proportional to slice sizes
    under the ring identity, so the wire division the schedule promises
    falls out of the traced operand sizes."""
    for name in ("striped", "striped_bucketed"):
        row = live[name]
        per_path = row["per_path_bytes"]
        total = per_path["ici"] + per_path["dcn"]
        assert per_path["dcn"] / total == row["stripe_ratio"], name


def test_striped_buckets_compose_with_striping(live):
    """PR 5's bucket planner composes with the multi-path schedule: K
    buckets → K collectives per (path, op) — same K as the flat-
    topology bucketed config — with the per-path byte identities
    holding across the whole plan."""
    k = live["bucketed"]["grad_collectives"]["psum"]
    row = live["striped_bucketed"]
    for hop in ("ici", "dcn"):
        assert row["per_hop"][hop]["collectives"] == \
            {"reduce_scatter": k, "psum": k, "all_gather": k}


def test_striped_concurrent_eligible_order(live):
    """The generalized hop_ordered gate (ISSUE 11 satellite): every
    scatter/crossing op of BOTH paths precedes every rebuild
    all_gather — the striped configs are budget-gated, not exempted,
    and the old single-path slow-hop-first property still holds for
    the hierarchical configs under the same generalized check."""
    for name, row in live.items():
        if row.get("topology") in ("hierarchical", "striped"):
            assert row["hop_ordered"], name


def test_striped_dcn_bf16_compresses_only_dcn_fabric(live):
    """Per-hop dtype × striping: the DCN FABRIC's crossings (the ICI
    path's chunk psum, the DCN path's bulk rs + ag) halve; the ICI
    fabric is byte-identical — the DCN path's chunk upcasts to f32
    before its fast-hop allreduce, so lossless-over-ICI survives the
    transposed schedule."""
    f32 = live["striped"]["per_hop"]
    bf16 = live["striped_dcn_bf16"]["per_hop"]
    assert bf16["ici"]["exchanged_grad_bytes"] == \
        f32["ici"]["exchanged_grad_bytes"]
    assert bf16["dcn"]["exchanged_grad_bytes"] * 2 == \
        f32["dcn"]["exchanged_grad_bytes"]


def test_striped_rs_shards_both_paths(live):
    """exchange='reduce_scatter' × striped: each path's slice chains
    psum_scatter over BOTH axes (2 rs per hop) and the params rebuild
    all-gathers both chains in reverse (2 ag per hop); gradient bytes
    equal the flat reduce-scatter exchange (half the allreduce — the
    conservation identity's rs form) and the params rebuild matches
    it byte for byte."""
    row = live["striped_rs"]
    for hop in ("ici", "dcn"):
        assert row["per_hop"][hop]["collectives"] == \
            {"reduce_scatter": 2, "all_gather": 2}, hop
    assert row["exchanged_gradient_bytes_per_replica"] == \
        live["reduce_scatter"]["exchanged_gradient_bytes_per_replica"]
    assert row["exchanged_param_bytes_per_replica"] == \
        live["reduce_scatter"]["exchanged_param_bytes_per_replica"]


def test_unknown_collective_prim_is_hard_census_error():
    """A collective the pricing does not understand must raise, never
    silently skip or misprice (the satellite's contract)."""
    import chainermn_tpu as ct
    comm = ct.create_communicator("jax_ici")
    with pytest.raises(ValueError, match="cannot price"):
        comm_census.row_wire_bytes(
            {"prim": "ppermute", "elems": 1024, "dtype": "float32",
             "axes": ["mn_world"]}, comm)


# -- MoE dispatch census (ISSUE 12) ------------------------------------------

@pytest.fixture(scope="module")
def moe_live():
    """The live MoE dispatch census of every committed config."""
    return {name: comm_census.moe_config_row(name)
            for name in comm_census.MOE_CONFIGS}


def test_moe_structure_census_matches_committed(budgets, moe_live):
    """The machine check for the MoE section: what `parallel.moe`
    traces today is what tools/comm_budgets.json commits, config by
    config — a PR that changes the dispatch shape must regenerate the
    budgets and own the diff."""
    for name, row in moe_live.items():
        committed = dict(budgets["moe"]["structure"][name])
        committed.pop("config", None)
        assert row == committed, (
            f"{name}: MoE dispatch structure drifted.\n traced    {row}\n"
            f" committed {committed}\nRegenerate tools/comm_budgets.json "
            "via `python tools/comm_census.py --write-budgets` if the "
            "change is intentional.")


def test_moe_two_stage_per_hop_structure(moe_live):
    """The ISSUE 12 tentpole, machine-checked: the two-stage dispatch
    is an all_to_all over ICI and an all_to_all over DCN (each hop
    crossed once per direction — 2 with the combine return trip), hop
    labels resolved from the eqns' own axis names; the flat reference
    is ONE joint-axis collective each way; and no config emits any
    other dispatch-sized collective."""
    for name, row in moe_live.items():
        assert row["intra_size"] == 4 and row["inter_size"] == 2, name
        assert row["non_dispatch_collectives"] == 0, name
    two = moe_live["moe_two_stage"]
    assert set(two["per_hop"]) == {"ici", "dcn"}
    for hop in ("ici", "dcn"):
        assert two["per_hop"][hop]["collectives"] == {"all_to_all": 2}
    flat = moe_live["moe_flat"]
    assert set(flat["per_hop"]) == {"dcn+ici"}
    assert flat["per_hop"]["dcn+ici"]["collectives"] == {"all_to_all": 2}


def test_moe_off_host_dispatch_ratio_pinned(budgets, moe_live):
    """Acceptance bar: `off_host_dispatch_ratio` is pinned EXACT per
    committed config — (inter-1)/inter of the capacity buffer belongs
    to off-host experts on the 2-host split — and the two-stage
    configs' DCN dispatch bytes, pinned FROM THE TRACE at wire dtype,
    carry exactly that share of the f32 round trip when lossless, half
    under bf16, a quarter under int8."""
    for name, row in moe_live.items():
        assert row["off_host_dispatch_ratio"] == 0.5, name
        assert budgets["moe"]["structure"][name][
            "off_host_dispatch_ratio"] == 0.5, name
    assert moe_live["moe_two_stage"]["dcn_dispatch_bytes_ratio"] == 0.5
    assert moe_live["moe_two_stage_bf16"]["dcn_dispatch_bytes_ratio"] \
        == 0.25
    assert moe_live["moe_two_stage_int8"]["dcn_dispatch_bytes_ratio"] \
        == 0.125


def test_moe_dcn_crossing_at_wire_dtype(moe_live):
    """The compressed DCN crossing rides the WIRE dtype (the packed
    buffer that actually crosses — int8 codewords with the per-segment
    scale all_to_all below the census floor), while ICI stays lossless
    byte-for-byte across every two-stage config."""
    lossless = moe_live["moe_two_stage"]["per_hop"]
    for name, wire in (("moe_two_stage_bf16", "bfloat16"),
                       ("moe_two_stage_int8", "int8")):
        row = moe_live[name]
        assert row["dcn_wire_dtype"] == wire, name
        assert row["per_hop"]["dcn"]["wire_dtypes"] == [wire], name
        assert row["per_hop"]["ici"] == lossless["ici"], name
    f32 = lossless["dcn"]["exchanged_dispatch_bytes"]
    bf16 = moe_live["moe_two_stage_bf16"]["per_hop"]["dcn"][
        "exchanged_dispatch_bytes"]
    int8 = moe_live["moe_two_stage_int8"]["per_hop"]["dcn"][
        "exchanged_dispatch_bytes"]
    assert bf16 * 2 == f32 and int8 * 4 == f32


def test_moe_pricing_surface_matches_census(moe_live):
    """`_memory_utility.moe_dispatch_exchanged_bytes` — the pricing of
    the MoE dispatch — agrees with the traced census byte-for-byte, so
    the formula and the committed budgets cannot drift apart."""
    from chainermn_tpu.communicators._memory_utility import \
        moe_dispatch_exchanged_bytes
    row = moe_live["moe_two_stage"]
    n_bytes = row["dispatch_elems"] * 4
    hops = moe_dispatch_exchanged_bytes(n_bytes, row["intra_size"],
                                        row["inter_size"])
    assert hops["ici"] == \
        row["per_hop"]["ici"]["exchanged_dispatch_bytes"]
    assert hops["dcn"] == \
        row["per_hop"]["dcn"]["exchanged_dispatch_bytes"]
    int8 = moe_live["moe_two_stage_int8"]
    hops8 = moe_dispatch_exchanged_bytes(
        n_bytes, row["intra_size"], row["inter_size"],
        dcn_n_bytes=int8["dispatch_elems"])
    assert hops8["dcn"] == \
        int8["per_hop"]["dcn"]["exchanged_dispatch_bytes"]
    flat = moe_live["moe_flat"]
    world = moe_dispatch_exchanged_bytes(n_bytes, row["intra_size"],
                                         row["inter_size"],
                                         two_stage=False)
    assert world["world"] == \
        flat["per_hop"]["dcn+ici"]["exchanged_dispatch_bytes"]


def test_measured_sweep_meets_tolerance_when_present(budgets):
    sweep = budgets["sweep"]
    if sweep["status"] != "measured":
        return  # pending_on_chip: the numeric half is dormant
    rows = sweep.get("rows", [])
    flat = [r for r in rows if r.get("exchange") == "flat"]
    bucketed = [r for r in rows if r.get("exchange") == "bucketed"]
    assert flat and bucketed, "measured sweep lacks flat/bucketed rows"
    tol = 1.0 - sweep.get("regression_tolerance_pct", 2.0) / 100.0
    best_flat = max(r["value"] for r in flat)
    best_bucketed = max(r["value"] for r in bucketed)
    assert best_bucketed >= tol * best_flat, (
        f"bucketed flagship {best_bucketed} fell more than the "
        f"tolerated margin below flat {best_flat} — record the "
        "refutation in PERF.md before re-committing")
