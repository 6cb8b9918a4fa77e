"""``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell in a new process.

The last line of standard output is the result object.  Without a TPU,
or with fewer chips than the cell asks for, the exit code is not 0 and no
result is printed: this command never falls back to the CPU.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()   # process start, for ``setup_s``

import argparse            # noqa: E402
import dataclasses         # noqa: E402
import sys                 # noqa: E402

from . import harness      # noqa: E402


@dataclasses.dataclass
class Run:
    """One run of one cell, as the drivers see it."""
    workload: dict          # the entry of BENCHMARK.json's ``workloads``
    traffic: dict           # benchmark/traffic/<workload>.json
    config: dict            # the configuration's file
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict | None      # the device's published peaks
    t0: float = T0
    rehearsal: bool = False  # the harness's own CPU tests only


def run_cell(run, manifest):
    """Drive the cell and build the result object (a dict)."""
    from . import tracing
    driver = harness.load_module("drivers", run.traffic["driver"])
    result = driver.run(run)
    name = run.workload["name"]
    device = result["device"]
    breakdown = None
    if run.trace:
        metrics, extra, breakdown = tracing.per_layer(run, result, manifest)
        device = {**device, **extra}
    else:
        metrics = {}
        for m in harness.metrics_for(manifest, name, "end_to_end"):
            metrics[m["name"]] = {"value": result["metrics"][m["name"]],
                                  "unit": m["unit"]}
    return harness.result_line(result["correct"], result["attempted"],
                               result["failed"], metrics, device, breakdown)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        manifest = harness.load_manifest()
        workload = harness.find_workload(manifest, args.workload)
        devices = harness.require_chips(workload["chips"])
        from chainermn_tpu.utils.compat import configure_persistent_cache
        cache = configure_persistent_cache()
        run = Run(workload=workload,
                  traffic=harness.load_traffic(workload),
                  config=harness.find_config(manifest, workload["config"]),
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devices=devices,
                  peaks=harness.peaks_for(devices[0].device_kind))
        harness.say({"run": args.workload, "seed": args.seed,
                     "seconds": args.seconds, "trace": args.trace,
                     "compile_cache": cache})
        line = run_cell(run, manifest)
    except harness.BenchmarkError as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
