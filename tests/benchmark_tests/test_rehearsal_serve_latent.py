"""The cell ``kimi-k2.6-serve-agent`` end to end on the CPU, at tiny
widths of its own (``_tiny.tiny_run`` knows GPT-2's and ResNet's shapes
only): the serve driver as it stands, the builder, the reference and the
two ``moe.*`` readers found by name; the control (the reference in
float8) and a broken timed path (a dropped shared expert) both called
not correct; and the configuration file keeps the published widths and
states what was cut beside what is held.

The limit here is for these tiny shapes in bfloat16, between what the
sound engine reads (0 to 0.05 over four seeds) and what the control reads
(over 0.5).
"""

import copy
import time

import pytest

from benchmark import harness
from benchmark.drivers import serve
from benchmark.run import Run

from . import _tiny

CELL = "kimi-k2.6-serve-agent"
LIMIT = {"served_logit_gap": 0.2}
TINY = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            num_hidden_layers=3, n_routed_experts=4, vocab_size=256,
            num_experts_per_tok=3)


def tiny_files():
    """(workload, traffic, config) of the cell at the tiny widths."""
    m = _tiny.manifest()
    w = harness.find_workload(m, CELL)
    traffic = copy.deepcopy(harness.load_traffic(w))
    config = copy.deepcopy(harness.find_config(m, w["config"]))
    config.update(TINY)
    config["published"] = dict(config["published"], n_routed_experts=16)
    traffic["mix"].update(rate=20.0, prefix_len=16, tail=[8, 56],
                          output=[4, 16])
    traffic["engine"].update(num_pages=64, page_size=8, max_batch=4,
                             max_context=128)
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


def test_calibrate_freed_reads_both_sides_of_the_limit(monkeypatch, capsys):
    """The reader for a cell whose engine and reference do not fit the
    chip together: the driver's own run for one seed, then the control."""
    import json

    import jax

    from benchmark.tools import calibrate_freed
    _, traffic, config = tiny_files()
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks_for", lambda kind: None)
    device_block = harness.device_block     # a CPU has no memory_stats()
    monkeypatch.setattr(harness, "device_block",
                        lambda devices, rehearsal=False:
                        device_block(devices, True))
    monkeypatch.setattr(harness, "load_traffic", lambda w: traffic)
    monkeypatch.setattr(harness, "find_config", lambda m, name: config)
    assert calibrate_freed.main(["--workload", CELL, "--seed", "77",
                                 "--seconds", "1"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.split("\n")
            if line.startswith('{"seed"')]
    by_who = {r["who"]: r for r in rows}
    assert by_who["program"]["other_checks_ok"] is True
    assert by_who["program"]["served_logit_gap"] \
        < LIMIT["served_logit_gap"] < by_who["control"]["served_logit_gap"]


def test_a_dropped_shared_expert_is_not_correct(monkeypatch):
    """The timed path broken where this configuration is new: the
    expert layers serve without their shared expert."""
    from chainermn_tpu.models.latent_moe import LatentMoEBlock

    def routed_only(self, x, valid=None):
        if not self.routed:
            return self.mlp(x), None
        return self.experts(x, valid=valid)
    monkeypatch.setattr(LatentMoEBlock, "ffn", routed_only)
    line = _tiny.result(tiny_run())
    assert line["correct"] is False


def test_a_traced_run_reads_the_held_experts_counters():
    line = _tiny.result(tiny_run(trace=True))
    assert line["correct"] is True
    m = line["metrics"]
    lanes = m["serve.lanes_in_use"]["value"]
    # 3 of 16 experts a token, 4 held: lanes * 3 * 4 / 16 if even
    assert 0 < m["moe.held_copies_per_step"]["value"] <= 3 * lanes
    assert 1.0 <= m["moe.held_imbalance"]["value"] <= 4.0
    for name in ("serve.prefix_hit_share", "serve.pool_occupancy",
                 "serve.bucket_fill", "serve.queue_wait_ms",
                 "serve.step_host_ms"):
        assert m[name]["value"] is not None


PUBLISHED_WIDTHS = dict(
    hidden_size=7168, num_attention_heads=64, num_key_value_heads=64,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, intermediate_size=18432,
    moe_intermediate_size=2048, num_experts_per_tok=8,
    routed_scaling_factor=2.827, n_shared_experts=1,
    first_k_dense_replace=1, rope_theta=50000, rms_norm_eps=1e-5,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
    norm_topk_prob=True, tie_word_embeddings=False)


def test_the_config_keeps_the_published_widths_and_states_the_cut():
    m = harness.load_manifest()
    entry = next(c for c in m["configs"] if c["name"] == "kimi-k2.6-share")
    config = harness.find_config(m, "kimi-k2.6-share")
    for key, value in PUBLISHED_WIDTHS.items():
        assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    held = {k: config[k] for k in config["reduced"]}
    assert held == {"num_hidden_layers": 5, "n_routed_experts": 12,
                    "vocab_size": 20480}
    assert config["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 384,
                                   "vocab_size": 163840}
    share = config["share"]
    assert share["router_outputs"] == 384
    assert share["chips_sharing_a_layer"] * config["n_routed_experts"] \
        == 384
    assert "32" in config["deployment"] and "12" in config["deployment"]
    assert config["param_dtype"] == "bfloat16"
    assert entry["source"] == config["source"]


@pytest.mark.parametrize("key", ["mix", "engine"])
def test_the_traffic_is_the_issues(key):
    traffic = harness.load_traffic(
        harness.find_workload(harness.load_manifest(), CELL))
    want = {"mix": dict(tenants=8, prefix_len=3584, tail=[64, 512],
                        output=[32, 256], schedule_seed=0),
            "engine": dict(max_batch=64, page_size=16, max_context=4608,
                           num_pages=18432, max_queue=4096)}[key]
    got = {k: v for k, v in traffic[key].items() if k != "rate"}
    assert got == want
    assert traffic["check_requests"] == 8 and traffic["trace_seconds"] == 5
    assert traffic["programs"] == {
        "decode": ["_decode"], "prefill": ["_prefill", "_prefix_prefill"]}
