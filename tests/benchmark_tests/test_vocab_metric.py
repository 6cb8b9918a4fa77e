"""``train.vocab_ms``: the operations whose HLO text carries a shape with
the configuration's ``vocab_size`` as a dimension, in ms a step.  On
made-up events, then on the trace recorded on a TPU v5e
(``data/tiny_train.xplane.pb``: its tiny configuration has 512 classes
AND 512 tokens a step, so ``[512,128]`` activations count there beside
the vocabulary's; at GPT-2's 50257 nothing else has the dimension)."""

import json
import os
import re

import pytest

from benchmark import harness, trace_reduce
from benchmark.trace_reduce import Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tiny_train.xplane.pb")

reader = harness.load_module("layer_metrics", "train.vocab_ms")


class _Run:
    traffic = {"programs": {"step": ["rank_step"]}}
    config = {"vocab_size": 50257}


def _view(trace, lo, hi, run=_Run):
    return {"trace": trace, "lo": lo, "hi": hi, "run": run}


def test_manifest_entry():
    entry = next(m for m in harness.load_manifest()["per_layer"]
                 if m["name"] == "train.vocab_ms")
    assert entry == {
        "name": "train.vocab_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step program",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["gpt2m-train-1chip"]}


@pytest.mark.parametrize("name,counts", [
    # by its result: the float32 log-softmax, Adam over the head
    ("%fusion.6 = f32[4096,50257]{0,1:T(8,128)} fusion("
     "bf16[4096,50257]{0,1:T(8,128)(2,1)} %get-tuple-element.13)", True),
    ("%fusion.16 = (f32[50257,1024]{1,0:T(8,128)}, f32[50257,1024]{1,0}) "
     "fusion(f32[50257,1024]{1,0} %W.1)", True),
    # by an operand alone: the row reduction, the dx GEMM, the lookup
    ("%fusion.8 = f32[4096]{0:T(1024)S(1)} fusion("
     "bf16[4096,50257]{0,1:T(8,128)(2,1)} %get-tuple-element.13)", True),
    ("%fusion.830 = (f32[1024]{0}, bf16[4096,1024]{1,0}) fusion("
     "f32[50257,1024]{1,0} %W.1, bf16[4096,50257]{0,1} %gte)", True),
    ("%fusion = f32[4096,1024]{1,0} fusion(f32[50257,1024]{1,0} %W.2, "
     "s32[4096]{0} %t)", True),
    ("%copy.3 = f32[50257]{0} copy(f32[50257]{0} %b)", True),
    # the number elsewhere than as a dimension, or inside another
    ("%fusion.50257 = f32[4096,1024]{1,0} fusion(bf16[4096,1024] %x)", False),
    ("%fusion.7 = f32[4096]{0:T(50257)} fusion(f32[4096,1024] %x)", False),
    ("%fusion.9 = f32[150257,8]{1,0} fusion(f32[8,502570]{1,0} %x)", False),
    ("%fusion.2 = bf16[4096,4096]{1,0} fusion(bf16[4096,1024]{1,0} %x)",
     False),
])
def test_an_operation_counts_by_the_shapes_in_its_text(name, counts):
    assert reader.has_dimension(name, 50257) is counts


def test_ms_a_step_of_the_window():
    head = "%fusion.1 = (bf16[4096], bf16[4096,50257]) fusion(%W)"
    ops = [Event(head, 0.0, 0.002),
           Event("%fusion.2 = bf16[4096,1024] fusion(%x)", 0.002, 0.010),
           Event("%fusion.8 = f32[4096] fusion(bf16[4096,50257] %l)",
                 0.012, 0.001),
           # a loop that holds vocabulary operations is not one itself
           Event("%while.1 = (f32[50257,1024]) while(%t)", 0.013, 0.002),
           Event(head, 0.020, 0.002),
           # half outside the window: its inside half counts
           Event("%fusion.16 = f32[50257,1024] fusion(%g)", 0.039, 0.002)]
    mods = [Event("jit_rank_step(1)", 0.0, 0.015),
            Event("jit_rank_step(1)", 0.020, 0.015),
            Event("jit_other(2)", 0.036, 0.002),
            Event("jit_rank_step(1)", 0.039, 0.015)]    # ends outside
    trace = Trace({"/device:TPU:0": mods}, {"/device:TPU:0": ops}, [])
    assert reader.read(_view(trace, 0.0, 0.040)) == pytest.approx(
        (0.002 + 0.001 + 0.002 + 0.001) * 1e3 / 2)
    # no step in the window, no device, or a configuration without a
    # vocabulary: nothing to read
    assert reader.read(_view(trace, 0.036, 0.038)) is None
    assert reader.read(_view(Trace({}, {}, []), 0.0, 1.0)) is None

    class NoVocabulary(_Run):
        config = {"num_classes": 1000}
    assert reader.read(_view(trace, 0.0, 0.040, NoVocabulary)) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded TPU trace")
def test_on_the_recorded_trace():
    trace = trace_reduce.load(RECORDED)
    with open(RECORDED + ".json") as f:
        kept = json.load(f)

    class Run:
        traffic = kept["traffic"]
        config = kept["config"]

    assert Run.config["vocab_size"] == 512
    lo, hi = trace_reduce.window(trace, "bench/window")
    got = reader.read({"trace": trace, "lo": lo, "hi": hi, "run": Run})
    # the same sum by another road: every shape of the text parsed
    first = trace.devices[0]

    def dims(text):
        return {int(d) for shape in re.findall(r"\[([\d,]+)\]", text)
                for d in shape.split(",")}

    inside = [e for e in trace.ops[first] if e.end > lo and e.start < hi]
    mine = [e for e in inside if 512 in dims(e.name)
            and not e.name.lstrip("%").startswith(("while", "call",
                                                   "conditional"))]
    want = sum(min(e.end, hi) - max(e.start, lo) for e in mine)
    steps = [e for e in trace.modules[first] if "rank_step" in e.name
             and e.start >= lo and e.end <= hi]
    assert steps and want > 0
    assert got == pytest.approx(want * 1e3 / len(steps))
    # a part of the step; the head's logits and the float32 log-softmax
    # the loss wrote when the trace was recorded are in it
    step_ms = trace_reduce.median_or_none(
        trace_reduce.module_runs(trace, ["rank_step"])) * 1e3
    assert 0 < got < step_ms
    assert any("bf16[512,512]" in e.name for e in mine)
    assert any(re.search(r"= f32\[512,512\]", e.name) for e in mine)
    # and something of the step is not the vocabulary's
    assert len(mine) < len(inside)
