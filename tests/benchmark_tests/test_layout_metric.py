"""``train.layout_ms``: the operations that only move data, by the name
of their instruction, in ms a step.  On made-up events, then on the
trace recorded on a TPU v5e (``data/tiny_train.xplane.pb``: recorded
before the flash kernels read the qkv GEMM's rows, so q, k, v and the
heads are still copied into and out of ``[B, H, T, D]`` in it)."""

import json
import os
import re

import pytest

from benchmark import harness, trace_reduce
from benchmark.trace_reduce import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tiny_train.xplane.pb")

reader = harness.load_module("layer_metrics", "train.layout_ms")


class _Run:
    traffic = {"programs": {"step": ["rank_step"]}}


def _view(trace, lo, hi):
    return {"trace": trace, "lo": lo, "hi": hi, "run": _Run}


def test_manifest_entry():
    entry = next(m for m in harness.load_manifest()["per_layer"]
                 if m["name"] == "train.layout_ms")
    assert entry == {
        "name": "train.layout_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step program",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["gpt2m-train-1chip"]}


@pytest.mark.parametrize("name,counts", [
    ("%copy.12 = bf16[4,16,1024,64]{2,3,1,0} copy(%x)", True),
    ("%copy-done.3 = bf16[4,1024,1024] copy-done(%copy-start.3)", True),
    ("%slice-start.1 = (bf16[8]) slice-start(%x)", True),
    ("%reshape.7 = bf16[4,1024,3,16,64] reshape(%fusion.2)", True),
    ("%transpose.2 = bf16[8,4] transpose(%x)", True),
    ("%pad_maximum_fusion = f32[8] fusion(%x)", True),
    ("copy_fusion.4 = bf16[8] fusion(%x)", True),
    ("%fusion.9 = bf16[8] fusion(%copy.12, %pad.3)", False),
    ("%constant_dynamic-update-slice_fusion = bf16[8] fusion(%x)", False),
    ("%_flash_kernel_lse.2 = (bf16[8]) custom-call(%copy.1)", False),
])
def test_an_operation_counts_by_the_name_of_its_instruction(name, counts):
    assert reader.moves_data(Event(name, 0.0, 1.0)) is counts


def test_ms_a_step_of_the_window():
    ops = [Event("%copy.1 = bf16[8] copy(%x)", 0.0, 0.002),
           Event("%fusion.1 = bf16[8] fusion(%copy.1)", 0.002, 0.010),
           Event("%pad.2 = bf16[8] pad(%x)", 0.012, 0.001),
           Event("%copy.1 = bf16[8] copy(%x)", 0.020, 0.002),
           # half outside the window: its inside half counts
           Event("%reshape.5 = bf16[8] reshape(%x)", 0.039, 0.002)]
    mods = [Event("jit_rank_step(1)", 0.0, 0.015),
            Event("jit_rank_step(1)", 0.020, 0.015),
            Event("jit_other(2)", 0.036, 0.002),
            Event("jit_rank_step(1)", 0.039, 0.015)]    # ends outside
    trace = Trace({"/device:TPU:0": mods}, {"/device:TPU:0": ops}, [])
    assert reader.read(_view(trace, 0.0, 0.040)) == pytest.approx(
        (0.002 + 0.001 + 0.002 + 0.001) * 1e3 / 2)
    # no step in the window, or no device: nothing to read
    assert reader.read(_view(trace, 0.036, 0.038)) is None
    assert reader.read(_view(Trace({}, {}, []), 0.0, 1.0)) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded TPU trace")
def test_on_the_recorded_trace():
    trace = trace_reduce.load(RECORDED)
    with open(RECORDED + ".json") as f:
        kept = json.load(f)

    class Run:
        traffic = kept["traffic"]

    lo, hi = trace_reduce.window(trace, "bench/window")
    got = reader.read({"trace": trace, "lo": lo, "hi": hi, "run": Run})
    # the same sum by another road: a pattern over the whole HLO text
    first = trace.devices[0]
    head = re.compile(r"^%?(copy|slice|reshape|transpose|pad)[\w.\-]* = ")
    inside = [e for e in trace.ops[first] if e.end > lo and e.start < hi]
    want = sum(min(e.end, hi) - max(e.start, lo)
               for e in inside if head.match(e.name))
    steps = [e for e in trace.modules[first] if "rank_step" in e.name
             and e.start >= lo and e.end <= hi]
    assert steps and want > 0
    assert got == pytest.approx(want * 1e3 / len(steps))
    # it is a part of the step, and the heads' copies are in it
    step_ms = trace_reduce.median_or_none(
        trace_reduce.module_runs(trace, ["rank_step"])) * 1e3
    assert 0 < got < step_ms
    assert any(head.match(e.name) and "copy" in e.name for e in inside)
