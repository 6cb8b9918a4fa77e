"""Read a serving cell's ``served_logit_gap`` and its control where the
engine and the float32 reference do not fit the chip together: ``python
-m benchmark.tools.calibrate_freed --workload <name> --seed <n>
[--seconds 8] [--control 1]``.

``tools/calibrate.py`` keeps one warm engine for all its seeds, the
reference's float32 weights beside it; a configuration whose float32
tree fills the chip alone (``kimi-k2.6-share``: 14.0 of 16.9 GB) cannot
be read so.  This reader is the driver's own ``run`` for one seed, which
frees the engine before the reference is made, and then the control (the
reference itself in float8) over the same sample.  One seed a process;
the limit belongs above the largest sound reading and below the smallest
control reading.
"""

from __future__ import annotations

import argparse
import sys
import time

from .. import harness
from ..run import Run

CONTROL = "fp8"


def main(argv=None):
    from ..drivers import serve
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    workload = harness.find_workload(manifest, args.workload)
    devices = harness.require_chips(workload["chips"])
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()
    run = Run(workload=workload, traffic=harness.load_traffic(workload),
              config=harness.find_config(manifest, workload["config"]),
              seed=args.seed, seconds=args.seconds, trace=False,
              devices=devices,
              peaks=harness.peaks_for(devices[0].device_kind),
              t0=time.perf_counter())
    result = serve.run(run)
    sound = next(r for r in result["checks"]
                 if r["check"] == "served_logit_gap")
    harness.say({"seed": args.seed, "who": "program",
                 "served_logit_gap": sound["value"],
                 "other_checks_ok": all(
                     r["ok"] for r in result["checks"] if r is not sound)})
    if args.control:
        gap, n = serve.reference_gap(run, result["spec"], result["sample"],
                                     control=CONTROL)
        harness.say({"seed": args.seed, "who": "control",
                     "served_logit_gap": gap, "tokens": n})
    return 0


if __name__ == "__main__":
    sys.exit(main())
