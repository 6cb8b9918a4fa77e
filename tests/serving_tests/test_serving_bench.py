"""Serving bench-mode harness tests (ISSUE 9 satellites).

The CPU rehearsal is CLAMPED and LABELED (``cpu_smoke: true``,
seconds-scale) so it can never read as a perf datum, and its measured
window never retraces.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_cpu_smoke_is_clamped_labeled_and_retrace_free():
    """End-to-end subprocess: the serving bench on the CPU backend
    emits one final row that is (a) labeled cpu_smoke, (b) clamped to
    the smoke load even when the env asks for more, (c) retrace-free in
    its measured window, and (d) carries the full metric surface
    (tokens/sec + p50/p99 + occupancy)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_MODEL="serving",
               BENCH_SERVE_REQUESTS="64",      # clamps to 12
               BENCH_SERVE_QPS="200",          # fast arrivals: no idle
               BENCH_SERVE_TENANTS="3")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=420, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_engine_throughput"
    assert row["cpu_smoke"] is True
    assert row["requests"] == 12               # the clamp
    assert row["tenants"] == 3                 # knobs respected
    assert row["qps"] == 200.0
    assert row["value"] and row["value"] > 0
    assert row["window_retraces"] == 0
    assert row["completed"] == 12
    for key in ("p50_token_latency_ms", "p99_token_latency_ms",
                "page_occupancy_mean", "page_occupancy_max",
                "attn_mode", "page_dtype", "prefix_hit_rate",
                "prefix_matched_tokens", "effective_capacity_x",
                "forks", "disagg", "transferred_page_bytes", "tp"):
        assert key in row, key
    # round-16 fleet columns are present on EVERY serving row with the
    # single-engine defaults backfilled (ISSUE 15 satellite: row
    # consumers never key-miss on fleet-less rows)
    assert row["replicas"] == 1
    assert row["reroutes"] == 0
    assert row["weight_sync_s"] == 0.0
    # the chat-shaped load (per-tenant shared system prompts, the
    # default) must actually HIT: measured sharing economics, not
    # zero-filled columns (the ISSUE 13 acceptance pin)
    assert row["prefix_hit_rate"] > 0
    assert row["effective_capacity_x"] > 1.0
    assert row["disagg"] is False and row["tp"] == 1


@pytest.mark.slow
def test_cpu_smoke_spec_and_chunk_leg():
    """End-to-end subprocess (slow tier), ISSUE 20 leg: BENCH_SERVE_SPEC_K=4 +
    BENCH_SERVE_CHUNK=64 on the CPU smoke — the chunk threshold clamps
    to 16 so the smoke's long prompts actually chunk, speculation and
    chunking are BOTH exercised (non-zero spec_steps /
    chunked_admissions), the row carries the full round-20 metric
    surface, the measured window stays retrace-free with the verify and
    chunk grids in the warmup set."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_MODEL="serving",
               BENCH_SERVE_REQUESTS="64",      # clamps to 12
               BENCH_SERVE_QPS="200",
               BENCH_SERVE_TENANTS="3",
               BENCH_SERVE_SPEC_K="4",
               BENCH_SERVE_CHUNK="64")         # clamps to 16
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=420, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_engine_throughput"
    assert row["cpu_smoke"] is True
    assert row["spec_k"] == 4
    assert row["chunk_tokens"] == 16           # the smoke clamp (64 -> 16)
    # speculation ran: dispatches counted, and every dispatch emitted
    # at least its pending token (== 1.0 exactly at zero accepts)
    assert row["spec_steps"] > 0
    assert row["accepted_tokens_per_dispatch"] >= 1.0
    assert 0.0 <= row["spec_acceptance_rate"] <= 1.0
    assert row["draft_overhead"] == 0.0        # n-gram draft: no dispatches
    # chunking ran: the smoke's long prompts admitted in chunks
    assert row["chunked_admissions"] > 0
    assert row["chunk_prefills"] > row["chunked_admissions"]
    assert row["completed"] == 12
    assert row["value"] and row["value"] > 0
    assert row["window_retraces"] == 0         # verify+chunk grids warmed


def test_cpu_smoke_fleet_kill_reroutes_with_zero_drops():
    """End-to-end subprocess, fleet leg (ISSUE 15): 2 replicas behind
    the router, the highest killed at decode step 3 — the row carries
    replicas/reroutes/weight_sync_s with the kill actually fired (zero
    dropped requests: completed == requests) and stays labeled
    cpu_smoke."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_MODEL="serving",
               BENCH_SERVE_REQUESTS="64",      # clamps to 12
               BENCH_SERVE_QPS="200",
               BENCH_SERVE_TENANTS="3",
               BENCH_SERVE_REPLICAS="2",
               BENCH_FLEET_KILL_AT="3")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=420, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "serving_engine_throughput"
    assert row["cpu_smoke"] is True
    assert row["replicas"] == 2
    assert row["fleet_kill_at"] == 3
    # the kill fired under load: in-flight sequences rerouted, none
    # dropped, and a cold replica joined via the tree sync
    assert row["reroutes"] > 0
    assert row["weight_sync_s"] > 0.0
    assert row["completed"] == row["requests"] == 12
    assert row["value"] and row["value"] > 0
    # the initial replicas' measured window stays retrace-free (the
    # joiner's cold compiles are the join's cost, not the window's)
    assert row["window_retraces"] == 0
