"""The cell ``ouro-2.6b-serve-chat`` end to end on the CPU, at tiny widths
of its own (width 64, 2 heads of 32, 3 layers applied 3 times, vocabulary
128): the serve driver as it stands, the builder, the reference and the
new readers found by name; the control (the reference in float8) and the
timed path broken where this configuration is new (a pass that reads
another pass's keys and values, a pass left out, a loop without the norm
that closes a pass) each called not correct; the configuration file
against the catalog's row key by key; the readers' arithmetic on made-up
spans and events.

The tiny model is served in float32, as the other rehearsals' are: a
sound engine reads under 0.001 and the control and the broken paths over
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

CELL = "ouro-2.6b-serve-chat"
CONFIG = "ouro-2.6b"
TINY = dict(hidden_size=64, head_dim=32, num_attention_heads=2,
            num_key_value_heads=2, intermediate_size=96, vocab_size=128,
            num_hidden_layers=3, total_ut_steps=3, param_dtype="float32")


def tiny_files(cell=CELL):
    """(workload, traffic, config) of the cell at the tiny widths; the
    limit stays the cell's own."""
    m = harness.load_manifest()
    # a variant of the cell (its mix at another rate, other keys) is the
    # cell's entry under the variant's name, listed or not
    w = dict(harness.find_workload(m, CELL), name=cell, traffic=cell)
    traffic = copy.deepcopy(harness.load_traffic(w))
    config = copy.deepcopy(harness.find_config(m, w["config"]))
    config.update(TINY)
    traffic["mix"].update(rate=20.0, prefix_len=16, tail=[8, 40],
                          output=[4, 24])
    traffic["engine"].update(num_pages=64, page_size=8, max_batch=4,
                             max_context=80)
    traffic["trace_seconds"] = 1
    return w, traffic, config


LIMIT = tiny_files()[1]["limits"]["served_logit_gap"]


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
    assert gap > LIMIT > 20 * sound


def _layer_read_wrong(monkeypatch, name):
    """``looped.<name>`` reads the FIRST pass's cache layer of its block
    in every pass (the writes stay where they belong)."""
    from chainermn_tpu.models import looped
    sound = getattr(looped, name)

    def shared(*args, layer, **kwargs):
        return sound(*args, layer=layer % TINY["num_hidden_layers"],
                     **kwargs)
    monkeypatch.setattr(looped, name, shared)


@pytest.mark.parametrize("reader", ["paged_decode_attention",
                                    "paged_prefill_attention"])
def test_a_pass_that_reads_another_passes_cache_is_not_correct(
        monkeypatch, reader):
    """The timed path broken where this configuration is new: every
    pass attends over the keys and values the FIRST pass left, in the
    decode step or in a prefix hit's suffix prefill."""
    _layer_read_wrong(monkeypatch, reader)
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_pass_left_out_is_not_correct(monkeypatch):
    from chainermn_tpu.models import looped
    init = looped.LoopedLM.__init__

    def one_fewer(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.passes -= 1
    monkeypatch.setattr(looped.LoopedLM, "__init__", one_fewer)
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_loop_without_the_norm_that_closes_a_pass_is_not_correct(
        monkeypatch):
    """The final norm closes EVERY pass and its output is the next
    pass's input: with ``ln_f`` left out the passes chain otherwise."""
    from chainermn_tpu.nn import links
    forward = links.RMSNorm.forward
    monkeypatch.setattr(
        links.RMSNorm, "forward",
        lambda self, x: x if self.name == "ln_f" else forward(self, x))
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_traced_run_reads_the_passes_and_the_exit_gate():
    line = _tiny.result(tiny_run(trace=True))
    assert line["correct"] is True
    m = line["metrics"]
    # the gate runs in the served program: a mean pass inside [1, R]
    assert 1.0 <= m["loop.expected_exit_pass"]["value"] <= 3.0
    # 3 x 3 cache layers of 256 B a token against 114 k parameters: the
    # cache is a real part of the tiny step's least bytes
    assert 0 < m["loop.cache_share_of_bytes"]["value"] < 100
    assert m["serve.prefix_hit_share"]["value"] >= 0
    for name in ("serve.pool_occupancy", "serve.bucket_fill",
                 "serve.queue_wait_ms", "serve.step_host_ms",
                 "serve.lanes_in_use"):
        assert m[name]["value"] is not None
    # the device's needs a device trace: left out on the CPU, and the
    # line is whole without it; the other models' own stay theirs
    for name in ("loop.decode_hbm_roofline", "serve.decode_hbm_roofline",
                 "deltanet.decode_hbm_roofline", "serve.decode_state_ms",
                 "moe.held_copies_per_step"):
        assert name not in m


# -- the configuration and the manifest --------------------------------------

def _catalog_row():
    import os
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Ouro-2.6B")


def test_the_config_keeps_every_catalog_key():
    m = harness.load_manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    config = harness.find_config(m, CONFIG)
    row = _catalog_row()
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert config["reduced"] == entry["reduced"] == []
    assert "nothing divided" in config["deployment"]
    assert config["param_dtype"] == "bfloat16"
    assert (config["builder"], config["reference"]) == ("looped_lm",
                                                        "looped")
    for key in ("source_of_the_equations", "sandwich_norm", "passes",
                "cache", "exit_gate", "rotary", "no_bias", "precision",
                "initialisation"):
        assert config["assumed"][key]


def test_the_model_is_2668_million_parameters_and_192_cache_layers():
    config = harness.find_config(harness.load_manifest(), CONFIG)
    model = harness.load_module("models", config["builder"]).build(
        config, max_len=352)
    sizes = {path: math.prod(p.shape) for path, p in model.namedparams()}
    layer = sum(n for path, n in sizes.items()
                if path.startswith("/blocks/0/"))
    assert layer == 51_388_416
    assert sizes["/embed/W"] == sizes["/head/W"] == 49152 * 2048
    assert sum(sizes.values()) == 2_667_974_657      # 5.34 GB in bfloat16
    assert len(model.blocks) == 48
    assert model.serve_cache_groups() == (("full", 192, ((4096,),), None),)


def test_a_threshold_under_one_is_refused():
    from chainermn_tpu.serving import UnsupportedProgramError
    config = dict(harness.find_config(harness.load_manifest(), CONFIG),
                  early_exit_threshold=0.9)
    with pytest.raises(UnsupportedProgramError, match="early_exit"):
        harness.load_module("models", config["builder"]).build(config)


@pytest.mark.parametrize("path, shape, rule", [
    ("/embed/W", (49152, 2048), ("normal", 1.0)),
    ("/blocks/0/attn/q/W", (2048, 2048), ("normal", 2048 ** -0.5)),
    ("/blocks/0/attn/o/W", (2048, 2048), ("normal", 2048 ** -0.5)),
    ("/blocks/7/mlp/down/W", (2048, 5632), ("normal", 5632 ** -0.5)),
    ("/blocks/7/ln1/gamma", (2048,), ("ones",)),
    ("/blocks/7/ln3/gamma", (2048,), ("ones",)),
    ("/blocks/7/ln2/gamma", (2048,), ("full", 96 ** -0.5)),
    ("/blocks/7/ln4/gamma", (2048,), ("full", 96 ** -0.5)),
    ("/ln_f/gamma", (2048,), ("ones",)),
    ("/gate/W", (1, 2048), ("normal", 2048 ** -0.5)),
    ("/gate/b", (1,), ("zeros",)),
    ("/head/W", (49152, 2048), ("normal", 2048 ** -0.5)),
])
def test_the_seeded_weights_scale_the_output_norms_gains(path, shape, rule):
    """LeCun normal everywhere, the output projections too: each
    sublayer's output passes a norm before it joins the stream, so the
    scale of a residual branch, 1 / sqrt(2 x the configuration's layers),
    is that norm's gain."""
    m = harness.load_manifest()
    builder = harness.load_module("models", "looped_lm")
    builder.build(harness.find_config(m, "ouro-2.6b"), max_len=352)
    got = builder.init_rule(path, shape)
    assert got[0] == rule[0] and got[1:] == pytest.approx(rule[1:])


@pytest.mark.parametrize("key", ["mix", "engine"])
def test_the_traffic_is_the_issues(key):
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    want = {"mix": dict(tenants=4, prefix_len=64, tail=[16, 96],
                        output=[32, 192], schedule_seed=0),
            "engine": dict(max_batch=16, page_size=16, max_context=352,
                           num_pages=320, max_queue=4096)}[key]
    got = {k: v for k, v in traffic[key].items() if k != "rate"}
    assert got == want
    assert traffic["check_requests"] == 8
    assert traffic["programs"] == {
        "decode": ["_decode"], "prefill": ["_prefill", "_prefix_prefill"]}
    # the longest request fills the context to the token; the pool holds
    # 16 lanes' own pages, the 4 tenants' shared ones and 16 of headroom
    mix, eng = traffic["mix"], traffic["engine"]
    assert mix["prefix_len"] + mix["tail"][1] + mix["output"][1] \
        == eng["max_context"]
    own = (mix["tail"][1] + mix["output"][1]) // eng["page_size"]
    shared = mix["prefix_len"] // eng["page_size"]
    assert eng["max_batch"] * own + mix["tenants"] * shared + 16 \
        == eng["num_pages"]


NEW = {
    "loop.decode_hbm_roofline": ("%", "higher", "device_trace"),
    "loop.cache_share_of_bytes": ("%", "lower", "program_counter"),
    "loop.expected_exit_pass": ("passes", "lower", "program_counter")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_entries_of_the_new_metrics(name):
    entry = next(m for m in harness.load_manifest()["per_layer"]
                 if m["name"] == name)
    unit, better, source = NEW[name]
    assert _tiny.without_variants(entry) == {
        "name": name, "unit": unit, "better": better,
        "source": source, "layer": "serving programs",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}


def test_the_cell_is_listed_where_the_issue_says():
    m = harness.load_manifest()
    mine = {e["name"] for e in harness.metrics_for(m, CELL, "per_layer")}
    others = {"serve.window_pages_per_lane", "serve.window_retained_pages",
              "serve.decode_hbm_roofline", "serve.decode_state_ms",
              "serve.prefill_state_ms"}
    # the seven readers of device time by role: an accepted test
    # (test_device_scopes.test_manifest_entry) holds their lists to the
    # four cells PR 38 read them in, and is a `benchmark` PR's to edit
    by_role = {"serve.decode_attn_ms", "serve.decode_proj_ms",
               "serve.decode_ffn_ms", "serve.decode_head_ms",
               "serve.decode_unscoped_ms", "serve.prefill_attn_ms",
               "serve.prefill_ffn_ms"}
    serve_metrics = {e["name"] for e in m["per_layer"]
                     if e["name"].startswith("serve.")}
    assert mine == (serve_metrics - others - by_role) | set(NEW)
    assert [e["name"] for e in harness.metrics_for(m, CELL, "end_to_end")] \
        == ["serve_tokens_per_s", "setup_s"]
    w = harness.find_workload(m, CELL)
    assert w["chips"] == 1 and w["config"] == CONFIG
    assert len(_tiny.without_variants(
        [w["name"] for w in m["workloads"]])) == 6
    assert all(cell["chips"] == 1 for cell in m["workloads"])


# -- the readers' arithmetic ---------------------------------------------------

class _StandIn:
    """A run with the published configuration and the v5e's peaks."""
    config = harness.find_config(harness.load_manifest(), CONFIG)
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def _span(name, start, dur, **stats):
    return program_spans.Span(name, "t", start, dur, stats)


def test_loop_roofline_counts_the_weights_once_a_pass():
    reader = harness.load_module("layer_metrics", "loop.decode_hbm_roofline")
    c = _StandIn.config
    assert reader.block_weights(c) == 48 * 51_388_416
    assert reader.once_weights(c) == 2048 + 2049 + 49152 * 2048
    assert reader.entry_bytes(c) == 8192
    # an empty step: the blocks four times, the head once
    least, cache = reader.step_bytes(c, ctx_tokens=0, passes=4, lanes=0)
    assert cache == 0
    assert least == 2 * (4 * reader.block_weights(c)
                         + reader.once_weights(c))
    # 16 lanes of 200 tokens (ISSUE 40): 25.0 GB, 30.5 ms at 819 GB/s,
    # the cache a fifth of it
    least, cache = reader.step_bytes(c, ctx_tokens=16 * 200, passes=4,
                                     lanes=16)
    assert cache == 192 * 8192 * (16 * 200 + 16)
    assert least / 1e9 == pytest.approx(25.0, abs=0.05)
    assert least / 819e9 == pytest.approx(0.0305, abs=1e-4)
    assert cache / least == pytest.approx(0.202, abs=2e-3)
    spans = [_span("serve/decode_window", 0.0, 0.050, ctx_tokens=3200,
                   passes=4, batch=16, exit_expected_pass=1.5),
             _span("serve/decode_window", 0.060, 0.050, ctx_tokens=3200,
                   passes=4, batch=16, exit_expected_pass=2.5),
             _span("serve/decode_window", 0.120, 0.020, batch=1)]
    mods = [Event("jit__decode(1)", 0.001, 0.040),
            Event("jit__prefill(2)", 0.051, 0.005),
            Event("jit__decode(1)", 0.061, 0.044),
            Event("jit__decode(1)", 0.121, 0.010)]
    view = {"trace": Trace({"/device:TPU:0": mods}, {"/device:TPU:0": []},
                           []),
            "lo": 0.0, "hi": 1.0, "run": _StandIn,
            "program_spans": program_spans.link(spans)}
    assert reader.read(view) == pytest.approx(
        100 * 2 * least / 819e9 / 0.084)
    share = harness.load_module("layer_metrics", "loop.cache_share_of_bytes")
    assert share.read(view) == pytest.approx(100 * cache / least)
    exits = harness.load_module("layer_metrics", "loop.expected_exit_pass")
    assert exits.read(view) == pytest.approx(2.0)
    # a program without the counts (the parent, another model): nothing
    view["program_spans"] = spans[2:]
    assert reader.read(view) is None and share.read(view) is None \
        and exits.read(view) is None
