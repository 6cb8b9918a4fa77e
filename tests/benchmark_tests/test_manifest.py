"""BENCHMARK.json is well-formed and every cell's files are found by
name; the peaks table and the chip check refuse what they must."""

import os
import re

import pytest

from benchmark import harness, run as run_module

from . import _tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    size = os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_cells_are_unique_and_within_the_four_chip_share(manifest):
    cells = manifest["workloads"]
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in manifest["configs"]}


def test_every_cell_finds_its_files_by_name():
    manifest = _tiny.manifest()   # the prepared cells' files too
    for w in manifest["workloads"]:
        traffic = harness.load_traffic(w)
        config = harness.find_config(manifest, w["config"])
        assert traffic["config"] == w["config"]
        assert "source" in config and "assumed" in config
        assert "reduced" in config
        driver = harness.load_module("drivers", traffic["driver"])
        assert callable(driver.run)
        builder = harness.load_module("models", config["builder"])
        assert callable(builder.build) and callable(builder.init_rule)
        harness.load_module("reference", config["reference"])


def test_every_cell_reports_what_the_contract_asks(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        mine = [m["name"] for m in harness.metrics_for(
            manifest, w["name"], "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        layer = harness.metrics_for(manifest, w["name"], "per_layer")
        assert layer
        for m in layer:
            # found by name, and moving a metric this cell reports
            reader = harness.load_module("layer_metrics", m["name"])
            assert callable(reader.read)
            assert m["moves"] in mine and m["moves"] in e2e


def test_config_files_keep_published_widths():
    manifest = _tiny.manifest()
    gpt2 = harness.find_config(manifest, "gpt2-medium")
    assert (gpt2["n_embd"], gpt2["n_layer"], gpt2["n_head"],
            gpt2["n_positions"], gpt2["vocab_size"]) == \
        (1024, 24, 16, 1024, 50257)
    resnet = harness.find_config(manifest, "resnet50")
    assert resnet["block_counts"] == [3, 4, 6, 3]
    assert resnet["stage_out_channels"] == [256, 512, 1024, 2048]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchmarkError, match="peaks.json"):
        harness.peaks_for("TPU v99 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_tflops"] == 197.0


def test_missing_chip_is_an_error_and_prints_no_result(manifest, capsys):
    with pytest.raises(harness.BenchmarkError, match="needs a TPU"):
        harness.require_chips(1)
    name = manifest["workloads"][0]["name"]
    rc = run_module.main(["--workload", name, "--seed", "1",
                          "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "correct" not in out.out and "needs a TPU" in out.err


def test_unknown_workload_is_an_error(capsys):
    rc = run_module.main(["--workload", "no-such-cell", "--seed", "1",
                          "--seconds", "1"])
    assert rc != 0 and "no workload" in capsys.readouterr().err
