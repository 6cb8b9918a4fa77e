"""The reduction from a profiler trace to per-layer numbers: interval
arithmetic on made-up events, and the whole path on a small trace
recorded on a TPU v5e (``data/tiny_train.xplane.pb``, with the result line,
the configuration and the job of the run that made it beside it: the
train driver at a tiny GPT-2 shape whose attention still goes through
the Pallas flash kernels, traced for a fraction of a second)."""

import json
import os

import pytest

from benchmark import harness, trace_reduce, tracing
from benchmark.trace_reduce import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tiny_train.xplane.pb")


def _trace(ops, modules=(), spans=(), devices=1):
    return Trace({f"/device:TPU:{d}": list(modules) for d in range(devices)},
                 {f"/device:TPU:{d}": list(ops) for d in range(devices)},
                 list(spans))


def test_union_and_subtract():
    evs = [Event("a", 0.0, 1.0), Event("b", 0.5, 1.0), Event("c", 3.0, 1.0)]
    u = trace_reduce.union(evs)
    assert u == [(0.0, 1.5), (3.0, 4.0)]
    assert trace_reduce.covered(u) == pytest.approx(2.5)
    assert trace_reduce.subtract([(0.0, 5.0)], u) == [(1.5, 3.0), (4.0, 5.0)]
    assert trace_reduce.subtract([(0.0, 1.0)], [(0.0, 1.0)]) == []


def test_busy_is_the_union_of_operations_averaged_over_devices():
    ops = [Event("%fusion.1 = f32[8]", 1.0, 1.0),
           Event("%fusion.2 = f32[8]", 1.5, 1.0),
           Event("%while.3 = (f32[8])", 1.0, 3.0)]
    t = _trace(ops, devices=2)
    assert trace_reduce.busy(t, 0.0, 10.0) == pytest.approx(3.0)
    assert trace_reduce.busy(t, 2.0, 3.0) == pytest.approx(1.0)


def test_collective_time_and_its_exposed_part():
    ops = [Event("%fusion.1 = bf16[8] fusion(...)", 0.0, 2.0),
           Event("%all-reduce.7 = f32[16] all-reduce(...)", 1.0, 3.0),
           Event("%while.2 = (f32[8]) while(...)", 0.0, 5.0),
           Event("%fusion.9 = bf16[8] fusion(...)", 4.5, 0.5)]
    total, exposed = trace_reduce.collective_seconds(_trace(ops), 0.0, 5.0)
    assert total == pytest.approx(3.0)
    assert exposed == pytest.approx(2.0)   # 2.0 .. 4.0: nothing else ran


def test_idle_gaps_are_named_by_the_span_that_covers_them():
    ops = [Event("%fusion.1 = f32[8]", 0.0, 1.0),
           Event("%fusion.1 = f32[8]", 3.0, 1.0),
           Event("%fusion.1 = f32[8]", 5.0, 0.5)]
    spans = [Event("bench/window", 0.0, 6.0), Event("bench/update", 0.0, 1.2),
             Event("bench/loss_fetch", 1.2, 1.8), Event("bench/update", 4.0, 1)]
    gaps = dict(trace_reduce.idle_gaps(_trace(ops, spans=spans), 0.0, 6.0))
    assert gaps["bench/loss_fetch"] == pytest.approx(2.0)   # 1.0 .. 3.0
    assert gaps["bench/update"] == pytest.approx(1.0)       # 4.0 .. 5.0
    assert gaps["unattributed"] == pytest.approx(0.5)       # 5.5 .. 6.0


def test_gaps_between_programs_and_module_runs():
    mods = [Event("jit__decode(1)", 0.0, 1.0), Event("jit__decode(1)", 1.5, 1),
            Event("jit__prefill(2)", 3.0, 1.0), Event("jit_other(3)", 4.5, 1)]
    t = _trace([], modules=mods)
    assert trace_reduce.module_runs(t, ["_decode"]) == [1.0, 1.0]
    assert trace_reduce.gaps_between(t, ["_decode", "_prefill"], 0, 9) == \
        pytest.approx([0.5, 0.5])
    assert trace_reduce.median_or_none([]) is None


def test_top_ops_leave_out_containers_and_shorten_names():
    ops = [Event("%while.2 = (f32[8]) while(...)", 0.0, 5.0),
           Event("%fusion.1 = " + "f32[8] " * 100, 0.0, 2.0),
           Event("%fusion.1 = " + "f32[8] " * 100, 2.0, 1.0)]
    top = trace_reduce.top_ops(_trace(ops), 0.0, 5.0)
    assert len(top) == 1 and top[0][1] == pytest.approx(3.0)
    assert top[0][0].startswith("fusion.1 f32[8]") and len(top[0][0]) <= 120


recorded = pytest.mark.skipif(not os.path.exists(RECORDED),
                              reason="no recorded TPU trace")


@pytest.fixture(scope="module")
def recorded_trace():
    return trace_reduce.load(RECORDED)


@recorded
def test_recorded_trace_holds_what_the_reducer_expects(recorded_trace):
    t = recorded_trace
    assert t.devices == ["/device:TPU:0"]
    steps = trace_reduce.module_runs(t, ["rank_step"])
    assert len(steps) >= 1 and all(0 < s < 1.0 for s in steps)
    names = {s.name for s in t.spans}
    assert {"bench/window", "bench/update", "bench/loss_fetch"} <= names
    lo, hi = trace_reduce.window(t, "bench/window")
    busy = trace_reduce.busy(t, lo, hi)
    assert 0 < busy <= hi - lo
    fwd, n_fwd = trace_reduce.op_seconds(t, ("_flash_kernel_lse",), lo, hi)
    bwd, n_bwd = trace_reduce.op_seconds(
        t, ("_flash_bwd_fused_kernel",), lo, hi)
    # two layers: two forward and two backward kernels a step
    assert n_fwd >= 2 and n_bwd >= 2 and n_fwd == n_bwd
    assert 0 < fwd < busy and 0 < bwd < busy
    assert trace_reduce.top_ops(t, lo, hi)


@recorded
def test_per_layer_readers_on_the_recorded_trace(recorded_trace):
    with open(RECORDED + ".json") as f:
        kept = json.load(f)

    class Run:
        workload = {"name": "gpt2m-train-1chip"}
        traffic, config = kept["traffic"], kept["config"]
        peaks = harness.peaks_for("TPU v5 lite")

    class Kept:
        def load(self):
            return recorded_trace

    result = {"tracing": Kept(), "attempted": 1, "steps_per_s": 100.0,
              "metrics": {"train.dispatch_ms": 1.0, "train.mfu": 1.0}}
    metrics, device, breakdown = tracing.per_layer(
        Run, result, harness.load_manifest())
    line = kept["line"]
    for name in ("train.device_step_ms", "flash.fwd_roofline",
                 "flash.bwd_roofline"):
        assert metrics[name]["value"] == pytest.approx(
            line["metrics"][name]["value"], rel=1e-6), name
    # idle: the share of a plain window of 100 steps a second in which
    # the step program (0.113 ms on the device) was not running
    assert metrics["train.device_idle"]["value"] == pytest.approx(
        100.0 * (1 - 100.0 * 1e-3 * metrics["train.device_step_ms"]["value"]))
    assert 0 < metrics["flash.fwd_roofline"]["value"] < 100
    assert device["busy_s"] == pytest.approx(line["device"]["busy_s"])
    assert breakdown["device_ops"] and len(breakdown["device_ops"]) <= 10
