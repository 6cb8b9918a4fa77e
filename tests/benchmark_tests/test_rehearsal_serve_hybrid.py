"""The cell ``olmo-hybrid-7b-serve-docs`` end to end on the CPU, at tiny
widths of its own: the serve driver as it stands, the builder, the
reference and the new readers found by name; the control (the reference
in float8) and the timed path broken where this configuration is new (a
prefix hit served from a ZERO state, a dropped decay, ``beta`` without
its factor 2) each called not correct; the configuration file against
the catalog's row key by key; the readers' arithmetic on made-up spans
and events.

The tiny model is served in float32, as the Laguna rehearsal's is: a
sound engine reads under 0.01 and the control and the broken paths over
the limit, which is the cell's own.
"""

import copy
import json
import math
import time

import pytest

from benchmark import harness, program_spans
from benchmark.drivers import serve
from benchmark.run import Run
from benchmark.trace_reduce import Event, Trace

from . import _tiny

CELL = "olmo-hybrid-7b-serve-docs"
CONFIG = "olmo-hybrid-7b-stage"
LIMIT = {"served_logit_gap": 0.4}
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, vocab_size=256, num_hidden_layers=8,
            param_dtype="float32", snapshot_stride=64)


def tiny_files():
    """(workload, traffic, config) of the cell at the tiny widths: a
    shared prompt of two strides, so every hit restores the snapshot at
    128."""
    m = harness.load_manifest()
    w = harness.find_workload(m, CELL)
    traffic = copy.deepcopy(harness.load_traffic(w))
    config = copy.deepcopy(harness.find_config(m, w["config"]))
    config.update(TINY)
    traffic["mix"].update(rate=20.0, prefix_len=128, tail=[8, 56],
                          output=[4, 24])
    traffic["engine"].update(num_pages=192, page_size=8, max_batch=4,
                             max_context=256)
    traffic["trace_seconds"] = 1
    traffic["limits"] = LIMIT
    return w, traffic, config


def tiny_run(seed=3_000_000_019, seconds=2.0, trace=False):
    import jax
    w, traffic, config = tiny_files()
    return Run(workload=w, traffic=traffic, config=config, seed=seed,
               seconds=seconds, trace=trace, devices=jax.devices()[:1],
               peaks=None, rehearsal=True, t0=time.perf_counter())


def _check(rows, name):
    return next(r for r in rows if r["check"] == name)


def test_the_cell_runs_and_agrees_with_its_reference():
    line = _tiny.result(tiny_run())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 40
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # (on a loaded CPU no request may finish inside 2 s: the rate is
    # the chip's to read)
    assert line["metrics"]["serve_tokens_per_s"]["value"] >= 0


def test_the_control_in_float8_is_not_correct():
    run = tiny_run()
    result = serve.run(run)
    assert result["correct"]
    sound = _check(result["checks"], "served_logit_gap")["value"]
    gap, n = serve.reference_gap(run, result["spec"], result["sample"],
                                 control="fp8")
    assert n >= 20
    assert gap > LIMIT["served_logit_gap"] > 20 * sound


def test_a_hit_served_from_a_zero_state_is_not_correct(monkeypatch):
    """The timed path broken where this configuration is new: a suffix
    prefill that starts its scan from zeros, not from the snapshot the
    hit restored (the pages it shares are sound)."""
    import jax.numpy as jnp
    from chainermn_tpu.models import hybrid_delta
    scan = hybrid_delta.HybridDeltaLM._scan

    def from_zero(self, mix, x, true_len, state, before):
        if state is not None:
            state, before = jnp.zeros_like(state), jnp.zeros_like(before)
        return scan(self, mix, x, true_len, state, before)
    monkeypatch.setattr(hybrid_delta.HybridDeltaLM, "_scan", from_zero)
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def _gates_broken(monkeypatch, change):
    from chainermn_tpu.models.hybrid_delta import DeltaMixer
    gates = DeltaMixer.gates

    def broken(self, x):
        return change(*gates(self, x))
    monkeypatch.setattr(DeltaMixer, "gates", broken)


def test_a_dropped_decay_is_not_correct(monkeypatch):
    _gates_broken(monkeypatch, lambda g, beta: (0.0 * g, beta))
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_beta_without_its_factor_two_is_not_correct(monkeypatch):
    _gates_broken(monkeypatch, lambda g, beta: (g, beta / 2))
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_traced_run_reads_the_slots_and_the_snapshots():
    line = _tiny.result(tiny_run(trace=True))
    assert line["correct"] is True
    m = line["metrics"]
    # 4 lanes + 4 x 4 snapshots = 20 -> 24 slots; each tenant's prompt
    # keeps two snapshots on the trie while a holder lives
    assert 0 < m["deltanet.state_slots_in_use"]["value"] <= 100
    assert 0 < m["deltanet.state_retained_slots"]["value"] <= 2 * 4
    assert m["serve.prefix_hit_share"]["value"] >= 0
    for name in ("serve.pool_occupancy", "serve.bucket_fill",
                 "serve.queue_wait_ms", "serve.step_host_ms",
                 "serve.lanes_in_use"):
        assert m[name]["value"] is not None
    # the device's two need a device trace: left out on the CPU, and the
    # line is whole without them; Laguna's own stay Laguna's
    for name in ("deltanet.decode_hbm_roofline",
                 "deltanet.prefill_roofline", "serve.decode_hbm_roofline",
                 "serve.window_pages_per_lane", "moe.held_copies_per_step"):
        assert name not in m


# -- the configuration and the manifest --------------------------------------

def _catalog_row():
    import os
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Olmo-Hybrid-7B")


def test_the_config_keeps_every_catalog_key_but_the_depth():
    m = harness.load_manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    config = harness.find_config(m, CONFIG)
    row = _catalog_row()
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["layer_types"] == row["config"]["layer_types"]
    assert config["layer_types"][:8] == (["linear_attention"] * 3
                                         + ["full_attention"]) * 2
    assert config["stage"]["stages"] == 4
    assert "4 pipeline stages" in config["deployment"]
    assert config["param_dtype"] == "bfloat16"
    assert config["rope_parameters"] == {"rope_theta": None}
    for key in ("norm_placement", "qk_norm", "no_rotary", "head_dim",
                "linear_layer", "A_log_and_dt_bias", "precision",
                "initialisation"):
        assert config["assumed"][key]


def test_the_stage_is_2436_million_parameters():
    config = harness.find_config(harness.load_manifest(), CONFIG)
    model = harness.load_module("models", config["builder"]).build(
        config, max_len=17920)
    sizes = {path: math.prod(p.shape) for path, p in model.namedparams()}
    mixer = sum(n for path, n in sizes.items()
                if path.startswith("/blocks/0/mix/"))
    assert mixer == 88_750_332                          # 88.75 M
    period = sum(n for path, n in sizes.items()
                 if path.split("/")[2] in "0123" and "/blocks/" in path)
    assert round(period / 1e6, 2) == 832.52
    total = sum(sizes.values())
    assert round(total / 1e6, 1) == 2435.7              # 4.87 GB in bf16
    from chainermn_tpu.serving import PerSequence
    assert model.serve_cache_groups() == (
        ("full", 2, ((7680,),), None),
        ("state", 6, ((96, 5760), (34560,)), PerSequence(2048)))


@pytest.mark.parametrize("path, shape, rule", [
    ("/embed/W", (100352, 3840), ("normal", 1.0)),
    ("/blocks/0/mix/q/W", (2880, 3840), ("normal", 3840 ** -0.5)),
    ("/blocks/0/mix/gate/W", (5760, 3840), ("normal", 3840 ** -0.5)),
    ("/blocks/0/mix/conv", (11520, 4), ("normal", 0.5)),
    ("/blocks/0/mix/A_log", (30,), ("normal", 1.0)),
    ("/blocks/0/mix/norm/gamma", (192,), ("ones",)),
    ("/blocks/0/mix/o/W", (3840, 5760), ("normal", (5760 * 64) ** -0.5)),
    ("/blocks/3/mix/o/W", (3840, 3840), ("normal", (3840 * 64) ** -0.5)),
    ("/blocks/3/mlp/down/W", (3840, 11008),
     ("normal", (11008 * 64) ** -0.5)),
    ("/blocks/3/mlp/up/W", (11008, 3840), ("normal", 3840 ** -0.5)),
    ("/head/W", (100352, 3840), ("normal", 3840 ** -0.5)),
])
def test_the_seeded_weights_scale_every_output_projection(path, shape, rule):
    """LeCun normal everywhere but the down- and output-projections,
    which carry 1 / sqrt(2 x 32 published layers) besides."""
    got = harness.load_module("models", "hybrid_delta_lm").init_rule(
        path, shape)
    assert got[0] == rule[0] and got[1:] == pytest.approx(rule[1:])


def test_the_time_step_is_a_hundredth_at_a_zero_input():
    kind, bias = harness.load_module("models", "hybrid_delta_lm").init_rule(
        "/blocks/0/mix/dt_bias", (30,))
    assert kind == "full"
    assert math.log1p(math.exp(bias)) == pytest.approx(0.01)


@pytest.mark.parametrize("key", ["mix", "engine"])
def test_the_traffic_is_the_issues(key):
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    want = {"mix": dict(tenants=4, prefix_len=16384, tail=[128, 1024],
                        output=[64, 512], schedule_seed=0),
            "engine": dict(max_batch=16, page_size=16, max_context=17920,
                           num_pages=7168, max_queue=4096)}[key]
    got = {k: v for k, v in traffic[key].items() if k != "rate"}
    assert got == want
    assert traffic["check_requests"] == 4 and traffic["trace_seconds"] == 16
    assert traffic["programs"] == {
        "decode": ["_decode"], "prefill": ["_prefill", "_prefix_prefill"]}
    # the longest request fills the context to the token, and a hit at
    # 16384 is 8 whole strides
    mix = traffic["mix"]
    assert mix["prefix_len"] + mix["tail"][1] + mix["output"][1] \
        == traffic["engine"]["max_context"]
    assert mix["prefix_len"] % 2048 == 0


NEW = {
    "deltanet.prefill_roofline": ("%", "higher", "device_trace", "kernels"),
    "deltanet.decode_hbm_roofline": ("%", "higher", "device_trace",
                                     "serving programs"),
    "deltanet.state_slots_in_use": ("%", "lower", "program_counter",
                                    "serving programs"),
    "deltanet.state_retained_slots": ("slots", "lower", "program_counter",
                                      "serving programs")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_entries_of_the_new_metrics(name):
    entry = next(m for m in harness.load_manifest()["per_layer"]
                 if m["name"] == name)
    unit, better, source, layer = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}


def test_the_cell_is_listed_where_the_issue_says():
    m = harness.load_manifest()
    mine = {e["name"] for e in harness.metrics_for(m, CELL, "per_layer")}
    lagunas = {"serve.window_pages_per_lane", "serve.window_retained_pages",
               "serve.decode_hbm_roofline"}
    serve_metrics = {e["name"] for e in m["per_layer"]
                     if e["name"].startswith("serve.")}
    assert mine == (serve_metrics - lagunas) | set(NEW)
    # the cell's own are `deltanet.`, not `serve.`: the Laguna cell's
    # rehearsal holds every `serve.` metric to be Laguna's too
    assert not {n for n in NEW if n.startswith("serve.")}
    assert [e["name"] for e in harness.metrics_for(m, CELL, "end_to_end")] \
        == ["serve_tokens_per_s", "setup_s"]
    w = harness.find_workload(m, CELL)
    assert w["chips"] == 1 and w["config"] == CONFIG


# -- the readers' arithmetic ---------------------------------------------------

class _StandIn:
    """A run with the published configuration and the v5e's peaks."""
    config = harness.find_config(harness.load_manifest(), CONFIG)
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def _span(name, start, dur, **stats):
    return program_spans.Span(name, "t", start, dur, stats)


def test_hybrid_decode_roofline_counts_the_least_bytes_of_a_step():
    reader = harness.load_module("layer_metrics",
                                 "deltanet.decode_hbm_roofline")
    c = _StandIn.config
    fixed = reader.fixed_weights(c)
    # the stage less its embedding (PERF.md section 4)
    assert fixed == 2_435_748_072 - 100352 * 3840
    assert reader.slot_bytes(c) == 4 * (30 * 96 * 192 + 3 * 11520)
    assert reader.step_bytes(c, ctx_tokens=0, state_lanes=0) == 2 * fixed
    lanes, ctx = 16, 16 * 17000
    full = reader.step_bytes(c, ctx_tokens=ctx, state_lanes=lanes)
    assert full == 2 * fixed + 2 * 15360 * ctx \
        + 6 * 2 * lanes * reader.slot_bytes(c)
    # 8.4 GB of K and V, 4.1 of weights, 0.45 of state: 15.8 ms
    assert full / 819e9 == pytest.approx(0.0158, abs=2e-4)
    spans = [_span("serve/decode_window", 0.0, 0.030, ctx_tokens=ctx,
                   state_lanes=lanes),
             _span("serve/decode_window", 0.040, 0.030, ctx_tokens=ctx,
                   state_lanes=lanes),
             _span("serve/decode_window", 0.080, 0.020, batch=1)]
    mods = [Event("jit__decode(1)", 0.001, 0.020),
            Event("jit__prefill(2)", 0.031, 0.005),
            Event("jit__decode(1)", 0.041, 0.024),
            Event("jit__decode(1)", 0.081, 0.010)]
    view = {"trace": Trace({"/device:TPU:0": mods}, {"/device:TPU:0": []},
                           []),
            "lo": 0.0, "hi": 1.0, "run": _StandIn,
            "program_spans": program_spans.link(spans)}
    assert reader.read(view) == pytest.approx(
        100 * 2 * full / 819e9 / 0.044)
    # a program without the counts, or no device: nothing to read
    view["program_spans"] = spans[2:]
    assert reader.read(view) is None


def test_delta_roofline_holds_each_call_to_its_own_length():
    reader = harness.load_module("layer_metrics",
                                 "deltanet.prefill_roofline")
    fl, by = reader.recurrence_call(30, 96, 192, 17920)
    assert fl == 7 * 96 * 192 * 30 * 17920
    assert by == 30 * 17920 * (2 * 96 * 2 + 2 * 192 * 2 + 8) \
        + 2 * 30 * 96 * 192 * 4
    # memory-bound: about three quarters of a millisecond a call
    assert by / 819e9 == pytest.approx(0.77e-3, rel=0.02)
    assert fl / 197e12 < by / 819e9
    long = Event("%_gated_delta_chunk_kernel.7 = (bf16[30,280,64,192], "
                 "f32[9,30,96,192]) custom-call(%s, %w)", 0.000, 0.010)
    short = Event("%_gated_delta_chunk_kernel.9 = (bf16[30,16,64,192], "
                  "f32[1,30,96,192]) custom-call(%s, %w)", 0.020, 0.001)
    other = Event("%_flash_kernel.1 = bf16[30,17920,128] custom-call(%q)",
                  0.030, 0.050)
    assert reader.call_tokens(long) == 17920
    assert reader.call_tokens(short) == 1024
    trace = Trace({"/device:TPU:0": []},
                  {"/device:TPU:0": [long, short, other]}, [])
    view = {"trace": trace, "lo": 0.0, "hi": 1.0, "run": _StandIn}
    least = (by + reader.recurrence_call(30, 96, 192, 1024)[1]) / 819e9
    assert reader.read(view) == pytest.approx(100 * least / 0.011)
    assert reader.read({**view, "trace": Trace({}, {}, [])}) is None
    # a program without the kernel (the parent): nothing to read
    bare = Trace({"/device:TPU:0": []}, {"/device:TPU:0": [other]}, [])
    assert reader.read({**view, "trace": bare}) is None


def test_slot_readers_take_the_steps_that_had_a_batch():
    used = harness.load_module("layer_metrics", "deltanet.state_slots_in_use")
    kept = harness.load_module("layer_metrics",
                               "deltanet.state_retained_slots")
    spans = [_span("serve/step", 0.0, 0.01, running=2, state_used_slots=14,
                   state_num_slots=56, state_retained_slots=8),
             _span("serve/step", 0.1, 0.01, running=4, state_used_slots=28,
                   state_num_slots=56, state_retained_slots=16),
             _span("serve/step", 0.2, 0.01, running=0, state_used_slots=56,
                   state_num_slots=56, state_retained_slots=56)]
    view = {"program_spans": program_spans.link(spans)}
    assert used.read(view) == pytest.approx(100 * (0.25 + 0.5) / 2)
    assert kept.read(view) == pytest.approx(12.0)
    # an engine with pages alone sets no such stat
    bare = {"program_spans": program_spans.link(
        [_span("serve/step", 0.0, 0.01, running=2, used_pages=3,
               num_pages=8)])}
    assert used.read(bare) is None and kept.read(bare) is None
