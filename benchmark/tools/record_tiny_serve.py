"""Record the small serving trace the CPU tests read
(``tests/benchmark_tests/data/tiny_serve_scoped.xplane.pb``): the serve
driver on the chip at a tiny GPT-2 shape, traced for a fraction of a
second, with the result line, the configuration and the mix beside it
as ``tiny_train.xplane.pb.json`` has them.

    python3 -m benchmark.tools.record_tiny_serve [--seed N] [--out DIR]

The profiler also writes every module's whole HLO proto on plane
``/host:metadata`` (hundreds of KB a program), and three stats of its
own on every device event: both are cut from the copy (``slimmed``),
which keeps everything a reader takes (the planes, lines, events with
their offsets and durations, the metadata with ``tf_op``) as written.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

from .. import harness, trace_reduce, xplane_wire
from ..run import Run, run_cell

CELL = "gpt2m-serve-chat"
TINY = dict(vocab_size=512, n_embd=128, n_layer=2, n_head=2, n_positions=256)
DROPPED = ("/host:metadata",)


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _rewritten(buf, number, rewrite):
    """The message ``buf`` with every length-delimited field ``number``
    passed through ``rewrite`` (``None``: the field is cut), every other
    field byte for byte."""
    out = bytearray()
    for n, wire, value in xplane_wire.fields(buf):
        if wire == 2:
            if n == number:
                value = rewrite(value)
                if value is None:
                    continue
            out += _varint(n << 3 | 2) + _varint(len(value)) + bytes(value)
        elif wire == 0:
            out += _varint(n << 3) + _varint(value)
        else:
            raise ValueError("a fixed-width field where xplane.proto has "
                             "none on the way to an event")
    return bytes(out)


def slimmed(data, dropped=DROPPED):
    """The ``XSpace`` without the planes named in ``dropped`` and, on the
    device planes, without each event's OWN stats (field 4 of an
    ``XEvent``: three stats an event, two thirds of the file, that no
    reader takes: ``ProfileData`` gives an event's start and duration
    from its own fields).  Everything else byte for byte."""
    def event(buf):
        return _rewritten(buf, 4, lambda stat: None)

    def line(buf):
        return _rewritten(buf, 4, event)

    def plane(buf):
        name = xplane_wire.name_of(buf)
        if name in dropped:
            return None
        if name.startswith(xplane_wire.DEVICE_PLANE):
            return _rewritten(buf, 3, line)
        return buf
    return _rewritten(memoryview(data), 1, plane)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--out", default=os.path.join(
        harness.ROOT, "chiprun_out", "tiny_serve_scoped"))
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    workload = harness.find_workload(manifest, CELL)
    devices = harness.require_chips(1)
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()
    traffic = copy.deepcopy(harness.load_traffic(workload))
    config = copy.deepcopy(harness.find_config(manifest, workload["config"]))
    config.update(TINY)
    traffic["mix"].update(rate=60.0, prefix_len=64, tail=[8, 56],
                          output=[4, 16])
    traffic["engine"].update(num_pages=128, page_size=8, max_batch=4,
                             max_context=256)
    traffic["trace_seconds"] = 0.1
    traffic["check_requests"] = 2
    run = Run(workload=workload, traffic=traffic, config=config,
              seed=args.seed, seconds=1.0, trace=True, devices=devices,
              peaks=harness.peaks_for(devices[0].device_kind),
              t0=time.perf_counter())
    line = json.loads(run_cell(run, manifest))
    from .. import tracing
    path = trace_reduce.find_xplane(tracing.trace_dir(run))
    with open(path, "rb") as f:
        data = slimmed(f.read())
    os.makedirs(args.out, exist_ok=True)
    name = os.path.join(args.out, "tiny_serve_scoped.xplane.pb")
    with open(name, "wb") as f:
        f.write(data)
    with open(name + ".json", "w") as f:
        json.dump({"line": line, "config": config, "traffic": traffic},
                  f, indent=1)
    print(json.dumps({"recorded": name, "bytes": len(data),
                      "correct": line["correct"],
                      "metrics": sorted(line["metrics"])}))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
