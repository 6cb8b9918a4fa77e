"""Read the numbers a cell's limits are set from, on the chip, at the
cell's own size: ``python -m benchmark.tools.calibrate --workload <name>
--seeds 1,2,... [--control-seeds 1,2,3] [--seconds 6]``.

For each seed it prints what a sound run of the program gives against
the plain reference, and for each control seed what the control (the
reference itself, computed in float8) gives against the reference.  A
limit belongs above the largest of the first and below the smallest of
the second.  Training's readings need no measured window; serving's
take a short one at the cell's own load.  One process reads all seeds,
so every program compiles once.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import sys
import time

from .. import harness
from ..run import Run

CONTROL = "fp8"


def _train(run, control):
    from ..drivers import train
    prog = train.Program(run)
    got = train.first_steps(prog)
    spec, data, gb = prog.spec, prog.data, prog.global_batch
    peak = harness.device_block(run.devices)["memory_peak_bytes"]
    del prog
    gc.collect()
    want = train.reference_steps(run, spec, data, gb)
    rows = [("program", got)]
    if control:
        rows.append(("control", train.reference_steps(
            run, spec, data, gb, precision=CONTROL)))
    for who, g in rows:
        harness.say({
            "seed": run.seed, "who": who,
            "loss_gap": max(abs(a - b) for a, b in
                            zip(g["losses"], want["losses"])),
            "grad_norm_gap": train.worst_leaf_gap(
                g["grad_norms"], want["grad_norms"]),
            "grad_norm_median_gap": train.median_leaf_gap(
                g["grad_norms"], want["grad_norms"]),
            "delta_norm_gap": train.worst_leaf_gap(
                g["delta_norms"], want["delta_norms"]),
            "delta_norm_median_gap": train.median_leaf_gap(
                g["delta_norms"], want["delta_norms"]),
            "grad_diff_gap": train.grad_diff_gap(g, want),
            "losses": g["losses"], "memory_peak_bytes": peak})


_ENGINE = []   # the one warm engine all seeds of a serving cell share


def _serve(run, control):
    from chainermn_tpu.core.link import extract_state
    from .. import weights
    from ..drivers import serve
    from ..models import _init
    if not _ENGINE:
        prog = serve.Program(run)
        prog.warm_up()
        _ENGINE.append(prog)
    else:
        prog = _ENGINE[0]
        prog.run = run
        _init.load(prog.model, weights.make_params(prog.spec, run.seed))
        prog.engine.state = extract_state(prog.model)
    checks = harness.Checks()
    with harness.watch_compiles() as watch:
        w, _, _ = serve.measure(run, prog, watch, checks)
    sample = serve.pick_sample(w["finished"], run.seed,
                               run.traffic["check_requests"])
    gap, n = serve.reference_gap(run, prog.spec, sample)
    harness.say({"seed": run.seed, "who": "program", "served_logit_gap": gap,
                 "tokens": n, "other_checks_ok": checks.ok})
    if control:
        gap, n = serve.reference_gap(run, prog.spec, sample, control=CONTROL)
        harness.say({"seed": run.seed, "who": "control",
                     "served_logit_gap": gap, "tokens": n})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    workload = harness.find_workload(manifest, args.workload)
    devices = harness.require_chips(workload["chips"])
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    base = Run(workload=workload, traffic=harness.load_traffic(workload),
               config=harness.find_config(manifest, workload["config"]),
               seed=0, seconds=args.seconds, trace=False, devices=devices,
               peaks=harness.peaks_for(devices[0].device_kind))
    reader = {"train": _train, "serve": _serve}[base.traffic["driver"]]
    for seed in seeds:
        t0 = time.perf_counter()
        reader(dataclasses.replace(base, seed=seed, t0=t0),
               seed in controls)
        gc.collect()
        harness.say({"seed": seed, "seconds": time.perf_counter() - t0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
