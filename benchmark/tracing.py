"""The traced run: the profiler around the window, the reduction, and the
per-layer readers found by name."""

from __future__ import annotations

import os
import shutil

from . import harness, trace_reduce

WINDOW_SPAN = "bench/window"


class Tracing:
    def __init__(self, trace_dir):
        self.dir = trace_dir
        self.annotation = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        # the Python call tracer is off: it slows the host it measures
        # and fills the trace; the driver's own spans are TraceAnnotations
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self.annotation.__exit__(*exc)
        jax.profiler.stop_trace()

    def load(self):
        return trace_reduce.load(trace_reduce.find_xplane(self.dir))


def trace_dir(run):
    return os.path.join(harness.HERE, ".trace", run.workload["name"])


def per_layer(run, result, manifest):
    """``(metrics, device additions, breakdown)`` of a traced run: each
    per-layer metric of this cell through its reader,
    ``benchmark/layer_metrics/<metric name>.py``.  A reader that finds
    nothing to read returns None and the metric is left out."""
    trace = result["tracing"].load()
    lo, hi = trace_reduce.window(trace, WINDOW_SPAN)
    busy_s = trace_reduce.busy(trace, lo, hi)
    view = {"trace": trace, "lo": lo, "hi": hi, "busy_s": busy_s,
            "window_s": hi - lo, "result": result, "run": run}
    metrics = {}
    for m in harness.metrics_for(manifest, run.workload["name"],
                                 "per_layer"):
        reader = harness.load_module("layer_metrics", m["name"])
        value = reader.read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": trace_reduce.top_ops(trace, lo, hi),
                 "idle_gaps": trace_reduce.idle_gaps(trace, lo, hi)}
    return metrics, {"busy_s": busy_s, "window_s": hi - lo}, breakdown
