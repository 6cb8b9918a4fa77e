"""``transformer.decode_hbm_roofline`` (PR 43): the reader's arithmetic
on a stand-in run with the published GPT-2-medium configuration, its
silence where the program has no ``ctx_tokens`` on its decode steps (the
parent of PR 43: the engine set the count for grouped models alone), its
one manifest entry, and a traced CPU rehearsal of the chat cell whose
own spans it reads.
"""

import pytest

from benchmark import harness, program_spans, trace_reduce, tracing
from benchmark.trace_reduce import Event, Trace

from . import _tiny
from .test_rehearsal_serve import SERVE_LIMIT

NAME = "transformer.decode_hbm_roofline"
CELL = "gpt2m-serve-chat"
DEVICE = "/device:TPU:0"


class _StandIn:
    """A run with the published configuration and the v5e's peaks."""
    config = harness.find_config(harness.load_manifest(), "gpt2-medium")
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def _reader():
    return harness.load_module("layer_metrics", NAME)


def _span(start, dur, **stats):
    return program_spans.Span("serve/decode_window", "t", start, dur, stats)


def _view(spans, modules):
    return {"trace": Trace({DEVICE: modules}, {DEVICE: []}, []),
            "lo": 0.0, "hi": 1.0, "run": _StandIn,
            "program_spans": program_spans.link(spans)}


def test_the_least_bytes_are_the_blocks_the_head_and_the_contexts():
    reader = _reader()
    c = _StandIn.config
    d, layers, vocab = 1024, 24, 50257
    # a block: qkv, output and the two MLP matrices (12 d^2) with their
    # biases (9 d) and two norms (4 d); then the final norm and the head.
    # The token and position tables are read a row a lane: left out
    fixed = layers * (12 * d * d + 13 * d) + 2 * d + d * vocab
    assert reader.fixed_weights(c) == fixed == 353_774_592
    assert reader.entry_bytes(c) * layers == 98_304
    assert reader.step_bytes(c, 0) == 2 * fixed
    # five lanes of 350 tokens: 0.88 GB, 1.07 ms of the chip's bandwidth
    ctx = 5 * 350
    assert reader.step_bytes(c, ctx) == 2 * fixed + 98_304 * ctx
    assert reader.step_bytes(c, ctx) / 819e9 == pytest.approx(
        1.074e-3, rel=1e-3)


def test_a_step_is_held_to_the_decode_run_that_starts_inside_it():
    reader = _reader()
    c = _StandIn.config
    spans = [_span(0.000, 0.030, ctx_tokens=1000, batch=4),
             _span(0.040, 0.030, ctx_tokens=2000, batch=5),
             _span(0.080, 0.020, batch=1)]          # no count: left out
    mods = [Event("jit__decode(1)", 0.001, 0.020),
            Event("jit__prefix_prefill(2)", 0.031, 0.005),
            Event("jit__decode(1)", 0.041, 0.024),
            Event("jit__decode(1)", 0.081, 0.010)]
    least = reader.step_bytes(c, 1000) + reader.step_bytes(c, 2000)
    assert reader.read(_view(spans, mods)) == pytest.approx(
        100 * least / 819e9 / 0.044)
    # it cannot pass 100 %: a step at the roofline itself reads 100
    at_peak = reader.step_bytes(c, 1000) / 819e9
    assert reader.read(_view(spans[:1], [Event("jit__decode(1)", 0.001,
                                               at_peak)])) \
        == pytest.approx(100.0)


def test_a_program_without_the_count_gives_nothing_and_does_not_raise():
    reader = _reader()
    mods = [Event("jit__decode(1)", 0.001, 0.020)]
    # the parent's decode steps: batch, bucket and step alone
    assert reader.read(_view([_span(0.0, 0.03, batch=4, bucket=4, step=7)],
                             mods)) is None
    assert reader.read(_view([], mods)) is None
    # the count without a device (a CPU rehearsal), or without a run
    counted = [_span(0.0, 0.03, ctx_tokens=9, batch=1)]
    assert reader.read(_view(counted, [])) is None
    view = _view(counted, mods)
    view["trace"] = Trace({}, {}, [])
    assert reader.read(view) is None


def test_the_manifest_lists_it_for_the_chat_cell_alone():
    m = harness.load_manifest()
    entry = next(e for e in m["per_layer"] if e["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "serving programs",
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert m["per_layer"][-1] == entry
    assert NAME in {e["name"]
                    for e in harness.metrics_for(m, CELL, "per_layer")}


def test_a_traced_rehearsal_of_the_chat_cell_carries_the_count(
        tmp_path, monkeypatch):
    """The engine counts ``ctx_tokens`` on every model's decode steps
    under tracing; on the CPU no device line exists, so the line leaves
    the share out, and over the run's own spans with a decode run laid
    inside each the reader reads them."""
    monkeypatch.setattr(tracing, "trace_dir", lambda run: str(tmp_path))
    run = _tiny.tiny_run(CELL, seconds=2.0, trace=True, limits=SERVE_LIMIT)
    line = _tiny.result(run)
    assert line["correct"] is True and line["failed"] == 0
    assert NAME not in line["metrics"]
    assert "serve.lanes_in_use" in line["metrics"]
    steps = [s for s in program_spans.load(
        trace_reduce.find_xplane(str(tmp_path)))
        if s.name == "serve/decode_window"]
    assert steps
    for s in steps:
        # every live lane has its prompt (24 tokens at least) behind it
        assert s.stats["ctx_tokens"] >= 24 * s.stats["batch"] > 0
    reader = _reader()
    view = {"trace": Trace({DEVICE: [Event("jit__decode(1)", s.start, 1e-3)
                                     for s in steps]}, {DEVICE: []}, []),
            "lo": 0.0, "hi": float("inf"), "program_spans": steps,
            "run": type("R", (), {"config": run.config,
                                  "traffic": run.traffic,
                                  "peaks": _StandIn.peaks})}
    least = sum(reader.step_bytes(run.config, s.stats["ctx_tokens"])
                for s in steps)
    assert reader.read(view) == pytest.approx(
        100 * least / 819e9 / (1e-3 * len(steps)))
