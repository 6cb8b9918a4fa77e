"""A cell, a configuration and a per-layer metric are each added by new
files and new BENCHMARK.json entries alone: done here to a temporary
copy of the benchmark, which then runs the new cell."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import harness

CONFIG = {
    "source": "test", "builder": "transformer_lm", "reference": "gpt2",
    "vocab_size": 128, "n_positions": 32, "n_embd": 32, "n_layer": 1,
    "n_head": 2, "reduced": [], "assumed": {}}
TRAFFIC = {
    "driver": "train", "config": "dummy-lm", "why": "test",
    "per_chip_batch": 2, "data": {"kind": "lm_tokens", "seq_len": 32},
    "dataset_batches": 4, "optimizer": {"name": "adam", "alpha": 0.001},
    "fetch_every": 2, "warm_steps": 1, "trace_seconds": 1,
    "programs": {"step": ["rank_step"]},
    "limits": {"loss_gap": 0.01, "grad_norm_gap": 0.05,
               "grad_norm_median_gap": 0.01, "delta_norm_gap": 0.7,
               "delta_norm_median_gap": 0.01}}
READER = '''
def read(view):
    return float(view["result"]["attempted"])
'''
SCRIPT = '''
import time, jax
from benchmark import harness
from benchmark.run import Run, run_cell
m = harness.load_manifest()
w = harness.find_workload(m, "dummy-train")
run = Run(workload=w, traffic=harness.load_traffic(w),
          config=harness.find_config(m, w["config"]), seed=5, seconds=1.0,
          trace=True, devices=jax.devices()[:1], peaks=None, rehearsal=True,
          t0=time.perf_counter())
print(run_cell(run, m))
'''


def test_new_files_and_entries_alone_add_a_cell(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "dummy-lm.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(bench, "traffic", "dummy-train.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(bench, "layer_metrics", "dummy.steps.py"),
              "w") as f:
        f.write(READER)
    m = harness.load_manifest()
    m["configs"].append({"name": "dummy-lm", "source": "test",
                         "file": "benchmark/configs/dummy-lm.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "dummy-train", "config": "dummy-lm",
                           "traffic": "dummy-train", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] == "train_samples_per_s_per_chip":
            e["workloads"].append("dummy-train")
    m["per_layer"].append({"name": "dummy.steps", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "entry points",
                           "moves": "train_samples_per_s_per_chip",
                           "workloads": ["dummy-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, harness.ROOT]))
    done = subprocess.run([sys.executable, "-c", SCRIPT], cwd=root, env=env,
                          capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["dummy.steps"]["value"] == line["attempted"] > 0
    # nothing that was there was edited
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, path
