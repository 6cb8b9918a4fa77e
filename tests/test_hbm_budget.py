"""Byte-budget regression gate (ISSUE 3: "accounting that can't rot").

The committed budgets in tools/hbm_budgets.json are XLA HloCostAnalysis
``bytes accessed`` over the LOWERED (backend-neutral) flagship train
step — a property of the program the framework emits, identical on every
backend.  A future PR that inflates the step's byte bill past the
~2% headroom fails here and must either fix the regression or
consciously re-commit the budget.  Fast: lowering only, no backend
codegen, no execution.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import hbm_census  # noqa: E402


def _measure(bs, size):
    return hbm_census.measure_hbm_bytes(bs, size, "NHWC", donate=True,
                                        do_compile=False)


def test_small_proxy_within_budget():
    budgets = hbm_census.load_hbm_budgets()
    key = hbm_census.hbm_budget_key(4, 64, "NHWC")
    assert key in budgets, "commit a budget row for the proxy config"
    row = _measure(4, 64)
    assert row["bytes_accessed"] > 0
    assert row["bytes_accessed"] <= budgets[key]["budget_bytes_accessed"], (
        f"byte budget regression: {row['bytes_accessed']} > "
        f"{budgets[key]['budget_bytes_accessed']} — the step program now "
        "moves more bytes than the committed budget; fix the regression "
        "or re-commit tools/hbm_budgets.json with justification "
        f"(category table: {row['bytes_by_category']})")


def test_flagship_within_budget_and_reduced_vs_pre_pr():
    budgets = hbm_census.load_hbm_budgets()
    key = hbm_census.hbm_budget_key(64, 224, "NHWC")
    entry = budgets.get(key)
    assert entry, "commit a budget row for the flagship config"
    row = _measure(64, 224)
    assert row["bytes_accessed"] <= entry["budget_bytes_accessed"], (
        f"flagship byte budget regression: {row['bytes_accessed']} > "
        f"{entry['budget_bytes_accessed']} "
        f"(category table: {row['bytes_by_category']})")
    # the acceptance bar this PR committed to: ≥10% below the pre-PR bill
    pre = entry["pre_pr_bytes_accessed"]
    assert row["bytes_accessed"] <= 0.9 * pre, (
        f"flagship bytes {row['bytes_accessed']} no longer ≥10% below the "
        f"pre-PR bill {pre}")
    # the select-and-scatter maxpool backward must stay gone
    assert row["bytes_by_category"].get("pooling_bwd", 0) == 0


def test_category_parser_on_known_program():
    import jax.numpy as jnp
    from jax import lax

    def f(x, w):
        y = lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                     dimension_numbers=("NCHW", "OIHW",
                                                        "NCHW"))
        y = jnp.maximum(y, 0)
        return lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 2, 2),
                                 (1, 1, 2, 2), [(0, 0)] * 4).sum()

    x = jnp.ones((1, 2, 8, 8), jnp.float32)
    w = jnp.ones((2, 2, 3, 3), jnp.float32)
    text = jax.jit(f).lower(x, w).as_text()
    cats = hbm_census.stablehlo_bytes_by_category(text)
    # conv: x + w + y accesses
    conv_expected = (1 * 2 * 8 * 8 + 2 * 2 * 3 * 3 + 1 * 2 * 8 * 8) * 4
    assert cats["conv"] == conv_expected
    # reduce_window (multi-line region op): y + init + pooled accesses
    pool_expected = (2 * 8 * 8 + 1 + 2 * 4 * 4) * 4
    assert cats["pooling"] == pool_expected
    assert cats["elementwise"] > 0


def test_grad_program_categorizes_select_and_scatter(monkeypatch):
    import chainermn_tpu.nn.functions as F
    import jax.numpy as jnp

    monkeypatch.setattr(F, "_MAXPOOL_VJP", "xla")
    grad = jax.grad(lambda a: jnp.sum(F.max_pooling_2d(a, 2, 2, 0)))
    text = jax.jit(grad).lower(jnp.ones((1, 1, 8, 8), jnp.float32)).as_text()
    cats = hbm_census.stablehlo_bytes_by_category(text)
    assert cats.get("pooling_bwd", 0) > 0, \
        "select_and_scatter should be attributed to pooling_bwd"


def test_census_cli_smoke():
    """One-command reproducibility: the census CLI prints the proxy
    configuration's row beside its committed budget, and the row is the
    one the gate above measures."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "hbm_census.py"),
         "--bs", "4", "--size", "64"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    (row,) = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert row["config"] == hbm_census.hbm_budget_key(4, 64, "NHWC")
    assert row["within_budget"] is True
    assert row["budget_bytes_accessed"] == hbm_census.load_hbm_budgets()[
        row["config"]]["budget_bytes_accessed"]
    assert sum(row["bytes_by_category"].values()) > 0
