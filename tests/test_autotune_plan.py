"""Self-tuning plan artifact gate (ISSUE 19: the derivation can't rot).

Mirrors tests/test_comm_budget.py's sweep pattern:
tools/autotune_plan.json commits HOW exchange plans are derived and —
once a run on the real fabric stamps it — WHAT
plan the first real fabric measurements implied.  Two layers:

* DERIVATION (backend-neutral, always on): the artifact's recorded
  formula / bucket rule / fallback constants must match the planner's
  own (``communicators._autotune`` + ``_memory_utility``), so the
  committed record tracks the code.  While ``status`` is
  ``pending_on_chip`` every numeric field is REFUSED off-chip and must
  stay null — a CPU-sim micro-bench number here would masquerade as
  fabric data.
* NUMBERS (armed when status flips to ``measured``): the committed
  plan must re-derive BIT-IDENTICALLY (same fingerprint) from the
  stamped measurements — the artifact can never disagree with what the
  planner says those measurements imply.
"""

import json
import os

ARTIFACT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "autotune_plan.json")


def _artifact():
    with open(ARTIFACT) as f:
        return json.load(f)


def test_artifact_schema():
    art = _artifact()
    assert art["status"] in ("pending_on_chip", "measured")
    for key in ("derivation", "plan", "measurements",
                "steps_per_sec_delta_vs_hand", "regression_tolerance_pct",
                "plan_version"):
        assert key in art, f"missing committed key {key!r}"
    assert art["regression_tolerance_pct"] > 0


def test_derivation_constants_track_the_planner():
    """The committed derivation record IS the planner's constants —
    a PR that changes the formula, the bucket rule, the overhead
    budget, or a fallback must re-commit the artifact and own the
    diff."""
    from chainermn_tpu.communicators import _autotune
    from chainermn_tpu.communicators._memory_utility import (
        DEFAULT_BUCKET_MB, DEFAULT_STRIPE_RATIO)
    art = _artifact()
    d = art["derivation"]
    assert art["plan_version"] == _autotune.PLAN_VERSION
    assert d["overhead_frac"] == _autotune.OVERHEAD_FRAC
    assert d["fallbacks"]["stripe_ratio"] == DEFAULT_STRIPE_RATIO
    assert d["fallbacks"]["bucket_mb"] == DEFAULT_BUCKET_MB
    # the recorded rule strings are exactly what derive_exchange_plan
    # writes into every plan's derivation block
    probe = _autotune.derive_exchange_plan(
        {"source": "startup", "hops": {"world": {"size": 2, "gbps": 1.0,
                                                 "lat_us": 100.0}}},
        {"axis": "probe", "kind": "flat", "size": 2,
         "exchange": "allreduce"})
    assert d["formula"] == probe["derivation"]["formula"]
    assert d["bucket_rule"] == probe["derivation"]["bucket_rule"]


def test_pending_refuses_numbers_off_chip():
    art = _artifact()
    if art["status"] != "pending_on_chip":
        return
    for key in ("plan", "measurements", "steps_per_sec_delta_vs_hand"):
        assert art[key] is None, (
            f"{key} is stamped while status is pending_on_chip — "
            f"numeric fields are refused off-chip; only the recovery "
            f"queue's FIRST-CHIP-CONTACT item 11 may stamp them "
            f"(and must flip status -> measured)")


def test_measured_plan_rederives_bit_identically():
    """Armed by item 11: the committed plan must be EXACTLY what the
    planner derives from the committed measurements — same fingerprint,
    byte for byte."""
    from chainermn_tpu.communicators._autotune import (derive_exchange_plan,
                                                       plan_fingerprint)
    art = _artifact()
    if art["status"] != "measured":
        return
    plan, measurements = art["plan"], art["measurements"]
    assert plan is not None and measurements is not None, \
        "status is measured but plan/measurements are unstamped"
    assert art["steps_per_sec_delta_vs_hand"] is not None
    rederived = derive_exchange_plan(measurements, plan["topology"])
    assert rederived["fingerprint"] == plan["fingerprint"], (
        "committed plan no longer re-derives from its own measurements "
        "(planner rules changed?): bump PLAN_VERSION and re-stamp from "
        "a chip run before re-committing")
    assert plan_fingerprint(plan) == plan["fingerprint"], \
        "committed plan body was edited without updating its fingerprint"
