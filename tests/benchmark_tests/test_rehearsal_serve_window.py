"""The cell ``laguna-s-2.1-serve-repo`` end to end on the CPU, at tiny
widths of its own: the serve driver as it stands, the builder, the
reference and the new readers found by name; the control (the reference
in float8) and the timed path broken where this configuration is new (a
sliding layer served as a full one, a dropped output gate) each called
not correct; the configuration file keeps the catalog's widths and
states what was cut beside what is held; the readers' arithmetic on
made-up spans and events.

The tiny model is served in float32: at these widths bfloat16 alone
flips one of 3-of-16 routing choices often enough that, with every
matrix LeCun normal, a sound run read up to 0.6 where the float8 control
read from 0.5 (six seeds of the bare forward), which no limit separates.
The builder's down-projections are scaled since (``init_rule``: a
flipped choice moves a logit less than rounding does; three seeds of the
bare forward in bfloat16 then read 0.04-0.12 against 0.57-0.86), but
0.12 under a limit of 0.2 is no margin for a test: in float32 the sound
engine reads under 0.001 and the control and the broken paths over 0.4.
"""

import copy
import json
import time

import pytest

from benchmark import harness, program_spans
from benchmark.drivers import serve
from benchmark.run import Run
from benchmark.trace_reduce import Event, Trace

from . import _tiny

CELL = "laguna-s-2.1-serve-repo"
CONFIG = "laguna-s-2.1-share"
LIMIT = {"served_logit_gap": 0.2}
TINY = dict(hidden_size=64, head_dim=16, num_key_value_heads=2,
            num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
            sliding_window=16, intermediate_size=96,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_hidden_layers=5, num_experts=4, vocab_size=256,
            num_experts_per_tok=3, param_dtype="float32")


def tiny_files(cell=CELL):
    """(workload, traffic, config) of the cell at the tiny widths."""
    m = harness.load_manifest()
    # a variant of the cell (its mix at another rate, other keys) is the
    # cell's entry under the variant's name, listed or not
    w = dict(harness.find_workload(m, CELL), name=cell, traffic=cell)
    traffic = copy.deepcopy(harness.load_traffic(w))
    config = copy.deepcopy(harness.find_config(m, w["config"]))
    config.update(TINY)
    config["published"] = dict(config["published"], num_experts=16)
    full = config["rope_parameters"]["full_attention"]
    full["original_max_position_embeddings"] = 32
    traffic["mix"].update(rate=20.0, prefix_len=32, tail=[8, 56],
                          output=[4, 24])
    traffic["engine"].update(num_pages=64, page_size=8, max_batch=4,
                             max_context=128)
    traffic["trace_seconds"] = 1
    traffic["limits"] = LIMIT
    return w, traffic, config


def tiny_run(seed=3_000_000_019, seconds=2.0, trace=False, cell=CELL):
    import jax
    w, traffic, config = tiny_files(cell)
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
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_control_in_float8_is_not_correct():
    run = tiny_run()
    result = serve.run(run)
    assert result["correct"]
    sound = _check(result["checks"], "served_logit_gap")["value"]
    gap, n = serve.reference_gap(run, result["spec"], result["sample"],
                                 control="fp8")
    assert n >= 40
    assert gap > LIMIT["served_logit_gap"] > sound


def test_a_sliding_layer_served_as_a_full_one_is_not_correct(monkeypatch):
    """The timed path broken where this configuration is new: the window
    layers see every position (their pool still slides, so what they
    read below the window is whatever lies in page 0)."""
    from chainermn_tpu.models import window_moe
    init = window_moe.GatedGroupedAttention.__init__

    def no_window(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.window = None
    monkeypatch.setattr(window_moe.GatedGroupedAttention, "__init__",
                        no_window)
    monkeypatch.setattr(
        window_moe.WindowMoELM, "serve_cache_groups",
        lambda self: (("full", len(self.full_layers),
                       ((2 * self.n_kv * self.head_dim,),), None),
                      ("window", len(self.window_layers),
                       ((2 * self.n_kv * self.head_dim,),), 16)))
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_dropped_gate_is_not_correct(monkeypatch):
    from chainermn_tpu.models.window_moe import GatedGroupedAttention

    def ungated(self, att, gate):
        return self.o(att.reshape(att.shape[:-2] + (-1,)))
    monkeypatch.setattr(GatedGroupedAttention, "output", ungated)
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_traced_run_reads_the_window_pool_and_the_held_experts():
    line = _tiny.result(tiny_run(trace=True))
    assert line["correct"] is True
    m = line["metrics"]
    lanes = m["serve.lanes_in_use"]["value"]
    assert 0 < m["moe.held_copies_per_step"]["value"] <= 3 * lanes
    # a window of 16 tokens in pages of 8: 3 pages and the one grown into
    assert 1.0 <= m["serve.window_pages_per_lane"]["value"] <= 4.0
    assert m["serve.window_retained_pages"]["value"] >= 0
    for name in ("serve.prefix_hit_share", "serve.pool_occupancy",
                 "serve.bucket_fill", "serve.queue_wait_ms",
                 "serve.step_host_ms"):
        assert m[name]["value"] is not None
    # the device's two need a device trace: left out on the CPU, and the
    # line is whole without them
    assert "serve.decode_hbm_roofline" not in m
    assert "flash.window_fwd_roofline" not in m
    assert "moe.held_imbalance" not in m


# -- the configuration and the manifest --------------------------------------

def _catalog_row():
    import os
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Laguna-S-2.1")


def test_the_config_keeps_the_catalogs_keys_and_states_the_cut():
    m = harness.load_manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    config = harness.find_config(m, CONFIG)
    row = _catalog_row()
    assert entry["source"] == config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: config[k] for k in config["reduced"]} == {
        "num_hidden_layers": 9, "num_experts": 16, "vocab_size": 12544}
    assert config["published"] == {
        k: row["config"][k] for k in config["reduced"]}
    share = config["share"]
    assert share["router_outputs"] == 256
    assert share["chips_sharing_a_layer"] * config["num_experts"] == 256
    assert "16" in config["deployment"]
    assert config["param_dtype"] == "bfloat16"
    for key in ("gate_function", "scoring", "qk_norm", "activation",
                "rotary_pairing", "initialisation", "precision"):
        assert config["assumed"][key]
    n = config["num_hidden_layers"]
    assert config["layer_types"][:n].count("sliding_attention") == 6
    assert config["num_attention_heads_per_layer"][:n] == [
        48, 72, 72, 72, 48, 72, 72, 72, 48]
    assert config["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 8


def test_the_share_is_1991_million_parameters():
    import math
    config = harness.find_config(harness.load_manifest(), CONFIG)
    model = harness.load_module("models", config["builder"]).build(
        config, max_len=10752)
    total = sum(math.prod(p.shape) for _, p in model.namedparams())
    assert round(total / 1e6) == 1992          # 1 991.5 M, 3.98 GB in bf16
    assert model.serve_cache_groups() == (
        ("full", 3, ((2048,),), None),
        ("window", 6, ((2048,),), 512))


@pytest.mark.parametrize("path, shape, std", [
    ("/embed/W", (12544, 3072), 1.0),
    ("/blocks/0/attn/q/W", (6144, 3072), 3072 ** -0.5),
    ("/blocks/1/attn/gate/W", (72, 3072), 3072 ** -0.5),
    ("/blocks/1/attn/o/W", (3072, 9216), 9216 ** -0.5),
    ("/blocks/1/experts/router", (256, 3072), 3072 ** -0.5),
    ("/blocks/1/experts/w_gate", (16, 1024, 3072), 3072 ** -0.5),
    ("/blocks/0/mlp/down/W", (3072, 12288), (12288 * 96) ** -0.5),
    ("/blocks/1/shared/down/W", (3072, 1024), (1024 * 96) ** -0.5),
    ("/blocks/1/experts/w_down", (16, 1024, 3072), (1024 * 96) ** -0.5),
])
def test_the_seeded_weights_scale_the_feed_forward_outputs_alone(
        path, shape, std):
    """LeCun normal everywhere but the three down-projections, which
    carry 1 / sqrt(2 x 48 published layers) besides: a routed expert's
    term stays small beside the stream, the attention's does not."""
    rule = harness.load_module("models", "window_moe_lm").init_rule
    kind, got = rule(path, shape)
    assert kind == "normal" and got == pytest.approx(std)


@pytest.mark.parametrize("key", ["mix", "engine"])
def test_the_traffic_is_the_issues(key):
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    want = {"mix": dict(tenants=8, prefix_len=8192, tail=[256, 2048],
                        output=[64, 512], schedule_seed=0),
            "engine": dict(max_batch=32, page_size=16, max_context=10752,
                           num_pages=21504, max_queue=4096)}[key]
    got = {k: v for k, v in traffic[key].items() if k != "rate"}
    assert got == want
    assert traffic["check_requests"] == 8 and traffic["trace_seconds"] == 5
    assert traffic["programs"] == {
        "decode": ["_decode"], "prefill": ["_prefill", "_prefix_prefill"]}


NEW = {"serve.window_pages_per_lane": ("pages", "lower", "program_counter",
                                       "serving programs"),
       "serve.window_retained_pages": ("pages", "lower", "program_counter",
                                       "serving programs"),
       "serve.decode_hbm_roofline": ("%", "higher", "device_trace",
                                     "serving programs"),
       "flash.window_fwd_roofline": ("%", "higher", "device_trace",
                                     "kernels")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_entries_of_the_new_metrics(name):
    entry = next(m for m in harness.load_manifest()["per_layer"]
                 if m["name"] == name)
    unit, better, source, layer = NEW[name]
    assert _tiny.without_variants(entry) == {
        "name": name, "unit": unit, "better": better,
        "source": source, "layer": layer,
        "moves": "serve_tokens_per_s", "workloads": [CELL]}


def test_the_cell_is_listed_where_the_issue_says():
    m = harness.load_manifest()
    mine = {e["name"] for e in harness.metrics_for(m, CELL, "per_layer")}
    serve_metrics = {e["name"] for e in m["per_layer"]
                     if e["name"].startswith("serve.")}
    assert mine == serve_metrics | {"moe.held_copies_per_step",
                                    "flash.window_fwd_roofline"}
    assert [e["name"] for e in harness.metrics_for(m, CELL, "end_to_end")] \
        == ["serve_tokens_per_s", "setup_s"]


# -- the readers' arithmetic ---------------------------------------------------

class _StandIn:
    """A run with the published configuration and the v5e's peaks."""
    config = harness.find_config(harness.load_manifest(), CONFIG)
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def _span(name, start, dur, **stats):
    return program_spans.Span(name, "t", start, dur, stats)


def test_decode_roofline_counts_the_least_bytes_of_a_step():
    reader = harness.load_module("layer_metrics",
                                 "serve.decode_hbm_roofline")
    c = _StandIn.config
    fixed = reader.fixed_weights(c)
    # the share less its experts and its embedding (PERF.md section 4)
    assert fixed == 1_991_505_152 - 8 * 16 * 3 * 3072 * 1024 \
        - 12544 * 3072
    one = reader.step_bytes(c, ctx_tokens=0, window_tokens=0, held_hit=0)
    assert one == 2 * fixed
    full = reader.step_bytes(c, ctx_tokens=32 * 10240,
                             window_tokens=32 * 512, held_hit=8 * 16)
    assert full == 2 * fixed + 2 * 128 * 3 * 3072 * 1024 \
        + 4096 * (3 * 32 * 10240 + 6 * 32 * 512)
    # two steps, each paired with the run that starts inside its span
    spans = [_span("serve/decode_window", 0.0, 0.020, ctx_tokens=0,
                   window_tokens=0, held_hit=0),
             _span("serve/decode_window", 0.030, 0.020, ctx_tokens=0,
                   window_tokens=0, held_hit=0),
             _span("serve/decode_window", 0.060, 0.020, batch=1)]
    mods = [Event("jit__decode(1)", 0.001, 0.010),
            Event("jit__prefill(2)", 0.021, 0.005),
            Event("jit__decode(1)", 0.031, 0.014),
            Event("jit__decode(1)", 0.061, 0.010)]
    view = {"trace": Trace({"/device:TPU:0": mods}, {"/device:TPU:0": []},
                           []),
            "lo": 0.0, "hi": 1.0, "run": _StandIn,
            "program_spans": program_spans.link(spans)}
    assert reader.read(view) == pytest.approx(
        100 * 2 * one / 819e9 / 0.024)
    # a program without the counts, or no device: nothing to read
    view["program_spans"] = spans[2:]
    assert reader.read(view) is None


def test_window_roofline_counts_the_band_alone():
    reader = harness.load_module("layer_metrics",
                                 "flash.window_fwd_roofline")
    assert reader.prefill_bucket(_StandIn.traffic) == 10752
    fl, by = reader.band_call(72, 8, 10752, 128, 512)
    assert fl == 4 * 72 * 128 * (512 * 513 / 2 + 10240 * 512)
    assert by == 2 * 80 * 10752 * 128 * 2
    # brute force at a small size
    pairs = sum(1 for i in range(40) for j in range(40) if 0 <= i - j < 8)
    assert reader.band_call(1, 1, 40, 1, 8)[0] == 4 * pairs
    ops = [Event("%_flash_window_kernel.3 = bf16[72,10752,128] "
                 "custom-call(%q, %k, %v)", 0.0, 0.004),
           Event("%_flash_kernel.1 = bf16[48,10752,128] custom-call(%q)",
                 0.005, 0.010),
           Event("%_flash_window_kernel.3 = bf16[72,10752,128] "
                 "custom-call(%q, %k, %v)", 0.020, 0.006)]
    trace = Trace({"/device:TPU:0": []}, {"/device:TPU:0": ops}, [])
    view = {"trace": trace, "lo": 0.0, "hi": 1.0, "run": _StandIn}
    assert reader.read(view) == pytest.approx(100 * fl / 197e12 / 0.005)
    assert reader.read({**view, "trace": Trace({}, {}, [])}) is None
