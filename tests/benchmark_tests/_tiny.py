"""Tiny shapes for the harness's own CPU rehearsals: the drivers run as
on the chip, on the CPU devices the test suite has, with result lines
that say ``platform: cpu``."""

import copy
import json
import time

from benchmark import harness
from benchmark.run import Run, run_cell

TINY_LM = dict(vocab_size=256, n_embd=64, n_layer=2, n_head=2,
               n_positions=128)
TINY_RESNET = dict(block_counts=[1, 1, 1, 1], num_classes=10, image_size=64)


# Cells whose files are all here but which BENCHMARK.json does not list
# yet (PERF.md section 7): the tests add them as a later PR would, by
# entries alone.
PREPARED = {"resnet50-train-1chip": "resnet50"}


# The saturated variants PR 42 made of two accepted cells (the same engine
# and mix at a higher rate, counted by stamps).  One that the chip shows
# steady is listed beside its sibling under every metric but the full
# prefill's window kernel; the tests that pin a metric's list to the cells
# it was first read in compare it without them
# (test_serve_counts.py holds the variants to their siblings' lists).
VARIANTS = {"laguna-s-2.1-serve-repo-peak": "laguna-s-2.1-serve-repo",
            "ouro-2.6b-serve-chat-peak": "ouro-2.6b-serve-chat"}


def without_variants(entry_or_names):
    """A metric's entry, or a list of cell names, less the variants."""
    if isinstance(entry_or_names, dict):
        return dict(entry_or_names, workloads=without_variants(
            entry_or_names["workloads"]))
    return [n for n in entry_or_names if n not in VARIANTS]


def listed_beside(m, siblings):
    """The manifest ``m`` with each cell of ``siblings`` that it does not
    list added as a later PR would, by entries alone: its sibling's entry
    under its own name and traffic, and its name wherever its sibling's
    is."""
    for cell, sibling in siblings.items():
        if any(w["name"] == cell for w in m["workloads"]):
            continue
        m["workloads"].append(dict(harness.find_workload(m, sibling),
                                   name=cell, traffic=cell))
        for e in m["end_to_end"] + m["per_layer"]:
            if sibling in e.get("workloads", ()):
                e["workloads"].append(cell)
    return m


def manifest():
    m = harness.load_manifest()
    for cell, config in PREPARED.items():
        if any(w["name"] == cell for w in m["workloads"]):
            continue
        m["configs"].append({"name": config, "reduced": [],
                             "file": f"benchmark/configs/{config}.json"})
        m["workloads"].append({"name": cell, "config": config,
                               "traffic": cell, "chips": 1})
        for e in m["end_to_end"] + m["per_layer"]:
            if "gpt2m-train-1chip" in e.get("workloads", ()) \
                    and not e["name"].startswith("flash."):
                e["workloads"].append(cell)
    return m


def tiny_run(workload_name, seed=3_000_000_019, seconds=1.0, trace=False,
             n_devices=1, limits=None):
    import jax
    m = manifest()
    w = harness.find_workload(m, workload_name)
    traffic = copy.deepcopy(harness.load_traffic(w))
    config = copy.deepcopy(harness.find_config(m, w["config"]))
    if config["builder"] == "transformer_lm":
        config.update(TINY_LM)
    else:
        config.update(TINY_RESNET)
    if traffic["driver"] == "train":
        traffic.update(warm_steps=2, trace_seconds=1, fetch_every=4)
        if traffic["data"]["kind"] == "lm_tokens":
            traffic["data"]["seq_len"] = 64
            traffic["per_chip_batch"] = 2
        else:
            traffic["per_chip_batch"] = 8
    else:
        traffic["mix"].update(rate=20.0, prefix_len=16, tail=[8, 56],
                              output=[4, 16])
        traffic["engine"].update(num_pages=64, page_size=8, max_batch=4,
                                 max_context=128)
        traffic["trace_seconds"] = 1
    if limits:
        traffic["limits"] = limits
    return Run(workload=w, traffic=traffic, config=config, seed=seed,
               seconds=seconds, trace=trace,
               devices=jax.devices()[:n_devices], peaks=None,
               rehearsal=True, t0=time.perf_counter())


def result(run):
    return json.loads(run_cell(run, manifest()))
