"""Find the knee of a serving cell once, on the chip: ``python -m
benchmark.tools.sweep --workload <name> --rates 4,6,8 [--seconds 12]
[--seed 1]`` drives the cell's mix at each rate in turn through one warm
engine and prints, per rate, the requests finished in the window, those
in flight and queued when it closed, the share of engine steps with a
request waiting in the queue, and the tails.  The knee is the highest
rate with no growing backlog; the cell's rate is fixed at about four
fifths of it in its traffic file.  A benchmark run never searches."""

from __future__ import annotations

import argparse
import sys
import time

from .. import harness, traffic_gen
from ..run import Run


def main(argv=None):
    from ..drivers import serve
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=1)
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
    prog = serve.Program(run)
    prog.warm_up()
    harness.say({"warm_s": time.perf_counter() - run.t0,
                 "device": harness.device_block(devices)})
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(run.traffic["mix"], rate=rate)
        arrivals = traffic_gen.generate(mix, prog.vocab, args.seed,
                                        args.seconds)
        w = serve.drive(prog, arrivals, args.seconds)
        harness.say({
            "rate": rate,
            **{k: v for k, v in w.items() if not isinstance(v, list)},
            "tokens_per_s": w["tokens_in_window"] / w["window_s"],
            "ttft_p50_ms": harness.percentile(w["ttft_ms"], 50),
            "ttft_p95_ms": harness.percentile(w["ttft_ms"], 95),
            "gap_p50_ms": harness.percentile(w["gap_ms"], 50),
            "gap_p95_ms": harness.percentile(w["gap_ms"], 95),
            "late_p95_ms": harness.percentile(w["late_ms"], 95),
            "evictions": prog.engine.evictions})
    harness.say({"device": harness.device_block(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
