"""Serving budget gate (ISSUE 9: the serving structure can't rot).

Mirrors tests/test_flash_budget.py: tools/serving_budgets.json commits
the serving engine's compiled-program contract and this gate holds
every future PR to it.  Two layers:

* STRUCTURE (backend-neutral, checked here on CPU): the decode step
  reads the KV cache through the block table — exactly one gather per
  pool per layer, NO full-T attention (zero dot_generals carrying a
  [T, T] score matrix — a dense re-prefill per token is the regression
  this exists to catch), zero backward kernels; prefill reuses the
  fused flash FORWARD (one Pallas kernel per layer, zero bwd kernels).
  Verified against the traced programs, not documentation.
* TARGETS: dormant while ``status`` is ``pending_on_chip``; once
  measured on the chip, the committed tokens/sec + p99 latency arm.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import serving_census  # noqa: E402


def _budgets():
    return serving_census.load_budgets()


def test_budget_schema():
    b = _budgets()
    assert set(b["structure"]) == {"decode", "prefill", "prefix_prefill",
                                   "disagg_decode_slice",
                                   "transfer_insert", "spec_verify",
                                   "chunked_prefill"}
    g = b["geometry"]
    # the full-T detector's soundness precondition: T strictly exceeds
    # every feature dimension of the census vertical, so two T-sized
    # output dims can only be a score matrix
    assert g["prefill_T"] > max(4 * g["d_model"], g["n_vocab"])
    assert b["targets"]["status"] in ("pending_on_chip", "measured")


def test_decode_structure_gate():
    """The decode hot loop's contract, machine-checked: gather-backed
    cache reads (one per pool per layer), page-scatter writes, NO
    full-T attention, no Pallas bwd kernels.  A PR that reshapes the
    decode step fails here and must either fix it or consciously
    re-commit the structure (python tools/serving_census.py
    --write-budgets)."""
    b = _budgets()
    census = serving_census.decode_census("paged")
    assert census == b["structure"]["decode"], (
        f"decode structure drifted: traced {census}, committed "
        f"{b['structure']['decode']}")
    L = b["geometry"]["n_layers"]
    assert census["pool_gathers"] == 2 * L      # one per pool per layer
    assert census["pool_scatters"] == 2 * L     # one page write per pool
    assert census["full_t_score_dots"] == 0     # no dense re-prefill
    assert census["bwd_kernels"] == 0


def test_prefill_structure_gate():
    """Prefill must keep riding the PR 4 flash forward: one Pallas
    forward kernel per layer, zero backward kernels (no grad is ever
    traced on the serving path), zero [T, T] score dots at the XLA
    level."""
    b = _budgets()
    census = serving_census.prefill_census()
    assert census == b["structure"]["prefill"], (
        f"prefill structure drifted: traced {census}, committed "
        f"{b['structure']['prefill']}")
    L = b["geometry"]["n_layers"]
    assert census["flash_fwd_kernels"] == L
    assert census["bwd_kernels"] == 0
    assert census["full_t_score_dots"] == 0


def test_dense_hatch_structure():
    """The CHAINERMN_TPU_PAGED_ATTN=dense escape hatch still reads the
    cache through the block table (same gather count) and still never
    forms a [T, T] score — it differs in softmax shape only, so the
    trajectory-equality contract (tests/serving_tests) is structural
    too."""
    census = serving_census.decode_census("dense")
    b = _budgets()
    L = b["geometry"]["n_layers"]
    assert census["pool_gathers"] == 2 * L
    assert census["full_t_score_dots"] == 0
    assert census["attn_mode"] == "dense"


def test_full_t_detector_is_alive():
    """The no-full-T gate is only as good as its detector: a dense
    (non-flash) prefill of the same vertical MUST trip it — if this
    fails, the detector has gone blind and the decode/prefill zeros
    above are vacuous."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import prefill_program

    model, state, (k_pool, v_pool), N, _ = serving_census._vertical()
    g = serving_census.GEOMETRY
    tokens = jnp.zeros((1, g["prefill_T"]), jnp.int32)
    # NO interpret forcing: the CPU fallback materializes dense scores
    jaxpr = jax.make_jaxpr(
        lambda s, k, v, t, tl, b: prefill_program(
            model, s, k, v, t, tl, b))(
        state, k_pool, v_pool, tokens, jnp.int32(g["prefill_T"]),
        jnp.zeros(N, jnp.int32))
    facts = serving_census._census_facts(
        jaxpr.jaxpr, tuple(k_pool.shape), g["prefill_T"])
    assert facts["full_t_score_dots"] >= g["n_layers"]


def test_prefix_prefill_structure_gate():
    """The round-14 prefix-hit contract, machine-checked: the suffix
    prefill reads the shared prefix THROUGH the block table (one gather
    per pool per layer), scatters only the suffix (one offset write per
    pool per layer), and runs ZERO flash kernels — recomputing the
    matched prefix with a full flash pass is the regression this gate
    exists to catch.  No [T, T] score dot either: the score is
    suffix-bucket × context, which is the FLOP saving itself."""
    b = _budgets()
    census = serving_census.prefix_prefill_census()
    assert census == b["structure"]["prefix_prefill"], (
        f"prefix_prefill structure drifted: traced {census}, committed "
        f"{b['structure']['prefix_prefill']}")
    L = b["geometry"]["n_layers"]
    assert census["flash_fwd_kernels"] == 0   # ZERO flash over shared pages
    assert census["pool_gathers"] == 2 * L    # prefix read via the table
    assert census["pool_scatters"] == 2 * L   # suffix written, offset
    assert census["full_t_score_dots"] == 0
    assert census["bwd_kernels"] == 0
    # detector soundness for the suffix score: one dim (context) may
    # reach T, the suffix bucket must stay strictly below it
    g = b["geometry"]
    assert g["prefix_suffix_T"] < g["max_context"]


def test_disagg_decode_slice_gate():
    """Disaggregation's decode-slice contract: the only compute program
    on the HBM-bound slice is the decode step — zero prefill (flash)
    kernels, zero full-T dots, zero bwd kernels.  Pinned against the
    live decode trace so it cannot drift from the single-mesh decode
    either (the trajectory-identity hatch is structural too)."""
    b = _budgets()
    census = serving_census.disagg_decode_slice_census()
    assert census == b["structure"]["disagg_decode_slice"]
    assert census == b["structure"]["decode"]   # same program, one mesh
    assert census["flash_fwd_kernels"] == 0     # no prefill on the slice
    assert census["full_t_score_dots"] == 0
    assert census["bwd_kernels"] == 0


def test_transfer_insert_gate():
    """The page ship lands as ONE drop-fenced full-pool scatter — data
    movement only: no gathers, no kernels, no score dots.  A transfer
    that recomputes (or reads back) on arrival fails here."""
    b = _budgets()
    census = serving_census.transfer_insert_census()
    assert census == b["structure"]["transfer_insert"]
    assert census["pool_scatters"] == 1
    assert census["pool_gathers"] == 0
    assert census["flash_fwd_kernels"] == 0
    assert census["bwd_kernels"] == 0


def test_spec_verify_gate():
    """The round-20 speculative-verify contract, machine-checked: ONE
    dispatch scores spec_k + 1 positions per lane
    (``queries_per_dispatch`` — the dispatch-per-token reduction is
    structural, not a tuning claim), the K extra queries ride the SAME
    one-gather-per-pool-per-layer cache reads the single-query step
    pays, K/V land as one drop-fenced span scatter per pool per layer,
    and NO [T, T] score dot forms — a verify that degenerates into a
    per-token dense re-prefill is the regression this gate exists to
    catch."""
    b = _budgets()
    census = serving_census.spec_verify_census()
    assert census == b["structure"]["spec_verify"], (
        f"spec_verify structure drifted: traced {census}, committed "
        f"{b['structure']['spec_verify']}")
    g = b["geometry"]
    L = g["n_layers"]
    assert census["queries_per_dispatch"] == g["spec_k"] + 1
    assert census["pool_gathers"] == 2 * L    # same reads as decode
    assert census["pool_scatters"] == 2 * L   # one span write per pool
    assert census["full_t_score_dots"] == 0   # never a dense re-prefill
    assert census["flash_fwd_kernels"] == 0
    assert census["bwd_kernels"] == 0
    # detector soundness for the [B, H, K1, ctx] score: the span stays
    # a small constant, strictly below the context dimension
    assert g["spec_k"] + 1 < g["max_context"]


def test_chunked_prefill_gate():
    """The round-20 chunk contract: one mid-prompt chunk is an offset
    suffix-prefill — one gather per pool per layer (written context
    read through the block table), one offset scatter per pool per
    layer, zero flash kernels over already-written pages, and zero
    [T, T] dots: chunking a long prompt never re-materializes the
    monolithic score matrix, so per-chunk cost is budget-bounded by
    construction."""
    b = _budgets()
    census = serving_census.chunked_prefill_census()
    assert census == b["structure"]["chunked_prefill"], (
        f"chunked_prefill structure drifted: traced {census}, committed "
        f"{b['structure']['chunked_prefill']}")
    g = b["geometry"]
    L = g["n_layers"]
    assert census["pool_gathers"] == 2 * L
    assert census["pool_scatters"] == 2 * L
    assert census["full_t_score_dots"] == 0
    assert census["flash_fwd_kernels"] == 0
    assert census["bwd_kernels"] == 0
    # chunk geometry soundness: page-multiple (the admission contract)
    # and strictly below the full-T threshold (detector stays sound)
    assert g["chunk_T"] % g["page_size"] == 0
    assert g["chunk_T"] < g["max_context"]


def test_targets_armed_when_measured():
    b = _budgets()
    t = b["targets"]
    if t["status"] != "measured":
        # dormant: the numeric half waits for serving rows from a
        # chip run; the schema relation is still enforced
        assert t["tokens_per_sec"] is None
        return
    assert t["tokens_per_sec"] > 0
    assert t["p99_token_latency_ms"] > 0


def test_census_tool_cli_smoke():
    """One-command reproducibility: the census CLI prints one row per
    phase and --write-budgets round-trips the committed structure
    (trace property — allowed off-chip, unlike flash/hbm numbers)."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "serving_census.py")],
        env=env, capture_output=True, text=True, timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert {r["phase"] for r in rows} == {
        "decode", "prefill", "prefix_prefill", "disagg_decode_slice",
        "transfer_insert", "spec_verify", "chunked_prefill"}
    committed = _budgets()["structure"]
    for r in rows:
        facts = {k: v for k, v in r.items() if k not in ("probe", "phase")}
        assert facts == committed[r["phase"]]
