"""Flash-backward budget gate (ISSUE 4: the kernel win can't rot).

Mirrors tests/test_hbm_budget.py: tools/flash_budgets.json commits the
flash-attention backward's contract and this gate holds every future PR
to it.  Two layers:

* STRUCTURE (backend-neutral, checked here on CPU): the backward
  lowers to exactly one Pallas kernel that spends exactly one exp on a
  tile it walks — the recompute-once property the fusion exists for.
  Verified against the traced program, not against documentation.
* NUMBERS (measured on chip by `make sweep-flash`): when the committed
  sweep section says ``measured``, the T=8192 fwd+bwd TFLOP/s must
  meet the committed target (≥2× the r5 two-kernel baseline);
  while it says ``pending_on_chip`` the numeric half is dormant but the
  schema/target relation is still enforced.
"""

import importlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import flash_sweep  # noqa: E402

fa = importlib.import_module("chainermn_tpu.ops.flash_attention")


def _budgets():
    with open(flash_sweep.BUDGETS_PATH) as f:
        return json.load(f)


def test_budget_schema_and_target_relation():
    b = _budgets()
    assert b["baseline"]["fwd_bwd_tflops_T8192"] == 31.8  # the r5 datum
    # the acceptance bar ISSUE 4 committed to: >= 2x that baseline
    assert b["target_fwd_bwd_tflops_T8192"] >= \
        2.0 * b["baseline"]["fwd_bwd_tflops_T8192"]
    assert set(b["structure"]) == {"bwd_kernels"}
    for entry in b["causal_block_table"].values():
        for blocks in entry.values():
            assert len(blocks) == 2
            assert all(x > 0 and x % 8 == 0 for x in blocks)
    assert b["sweep"]["status"] in ("pending_on_chip", "measured")


def _recorded_causal_table(b):
    return {tuple(int(x) for x in key.split("x")):
            {leg: tuple(blocks) for leg, blocks in entry.items()}
            for key, entry in b["causal_block_table"].items()}


@pytest.mark.parametrize("recorded,literal", [
    (_recorded_causal_table, "_CAUSAL_BLOCK_TABLE")])
def test_bwd_block_table_matches_kernel_literal(recorded, literal):
    """The kernels read the literal table in ops/flash_attention.py;
    the budgets file records it — they must not desync (the sweep tool
    prints a reminder to paste winners into the literal)."""
    assert recorded(_budgets()) == getattr(fa, literal)


@pytest.mark.parametrize("leg,kernel", [
    ("fwd", "_flash_kernel_lse"), ("bwd", "_flash_bwd_fused_kernel")])
def test_committed_causal_tiles_come_from_the_recorded_sweep(leg, kernel):
    """PR 28's chip sweep at [4, 16, 1024, 64] causal bfloat16 is
    recorded whole (every block_q, block_k over {128..1024}), and the
    committed tiles are within 3 % of the fastest row of their leg."""
    b = _budgets()
    rows = b["cell_sweep_T1024_D64"]["rows"]
    assert {(r["block_q"], r["block_k"]) for r in rows} == {
        (bq, bk) for bq in (128, 256, 512, 1024)
        for bk in (128, 256, 512, 1024)}
    by_blocks = {(r["block_q"], r["block_k"]): r["kernel_ms"][kernel]
                 for r in rows}
    committed = tuple(b["causal_block_table"]["1024x64"][leg])
    assert by_blocks[committed] <= 1.03 * min(by_blocks.values())


@pytest.mark.parametrize("leg,kernel", [
    ("fwd", "_flash_kernel_lse"), ("bwd", "_flash_bwd_fused_kernel")])
def test_rows_form_keeps_the_committed_tiles_and_separation(leg, kernel):
    """PR 30's chip sweep of the rows form (the qkv GEMM's own
    ``[4, 1024, 3072]``, two heads a 128-lane block) is recorded whole:
    the tiles the table holds are within 3 % of the fastest row there
    too, so the table needs no key for the form, and the committed way
    to part a block's heads (``mask``) was the faster of the two tried
    at those tiles."""
    b = _budgets()
    rows_form = b["rows_form_T1024_D64"]
    assert {(r["block_q"], r["block_k"]) for r in rows_form["rows"]} == {
        (bq, bk) for bq in (128, 256, 512, 1024)
        for bk in (128, 256, 512, 1024)}
    by_blocks = {(r["block_q"], r["block_k"]): r["kernel_ms"][kernel]
                 for r in rows_form["rows"]}
    committed = tuple(b["causal_block_table"]["1024x64"][leg])
    assert by_blocks[committed] <= 1.03 * min(by_blocks.values())
    tried = {r["separation"]: r["kernel_ms"][kernel]
             for r in rows_form["head_separations"]["rows"]
             if (r["block_q"], r["block_k"]) == committed}
    assert set(tried) == {"heads_first", "lanes", "mask"}
    assert tried["mask"] < tried["lanes"]


@pytest.mark.parametrize("form", flash_sweep.FORMS)
def test_fused_structure_gate(form):
    """Recompute-once, machine-checked: the backward is ONE pallas
    kernel with ONE exp a tile walked (two loop bodies, one exp each).
    A PR that splits the pass again or adds a second exp(s - lse)
    recompute fails here and must either fix it or consciously
    re-commit the structure section.  In the rows form, where a block
    holds two heads, the counts a head are the same."""
    b = _budgets()
    census = flash_sweep.bwd_kernel_census(fa, form=form)
    assert census == b["structure"]["bwd_kernels"], (
        f"backward structure drifted: traced {census}, committed "
        f"{b['structure']['bwd_kernels']}")


def test_measured_numbers_meet_target_when_present():
    b = _budgets()
    if b["sweep"]["status"] != "measured":
        return  # pending_on_chip: the numeric half is dormant
    results = b["sweep"]["results"]
    assert "8192" in results, "sweep measured but no T=8192 row"
    got = results["8192"]["fwd_bwd_tflops"]
    assert got >= b["target_fwd_bwd_tflops_T8192"], (
        f"committed T=8192 fwd+bwd {got} TFLOP/s below the "
        f"{b['target_fwd_bwd_tflops_T8192']} target — record the "
        "refutation in PERF.md before re-committing a lower target")


def test_sweep_tool_cpu_smoke(tmp_path):
    """The one-command reproducibility claim: the sweep tool runs its
    interpret-mode smoke end to end and refuses --write-budgets off
    chip (budgets are measured artifacts)."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "flash_sweep.py"),
         "--T", "64", "--blocks", "32:32", "--reps", "1"],
        env=env, capture_output=True, text=True, timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines()]
    timed = [r for r in rows if "fwd_bwd_ms" in r]
    assert [(r["T"], r["block_q"], r["block_k"]) for r in timed] \
        == [(64, 32, 32)]
    assert all(r["interpreted"] for r in timed)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "flash_sweep.py"),
         "--T", "64", "--blocks", "32:32", "--reps", "1",
         "--write-budgets"],
        env=env, capture_output=True, text=True, timeout=600, cwd=root)
    assert out.returncode == 2
    assert "refused" in out.stdout
