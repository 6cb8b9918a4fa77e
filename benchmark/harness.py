"""What every driver shares: the manifest and its files found by name,
the device check, the peaks table, percentile arithmetic, the watch for
compiles inside the window, and the result line.

Nothing here is specific to one cell, configuration or per-layer metric:
those are files of their own (see README.md).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


class BenchmarkError(Exception):
    """The run cannot be made (no chip, unknown device, bad manifest)."""


# -- manifest and files found by name ---------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_workload(manifest, name):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise BenchmarkError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in manifest['workloads']]}")


def find_config(manifest, name):
    for c in manifest["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise BenchmarkError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(workload):
    """The cell's job or mix: ``benchmark/traffic/<traffic>.json``."""
    return load_json(os.path.join(HERE, "traffic",
                                  workload["traffic"] + ".json"))


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, found by file so that a
    later PR adds a file and edits none."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"benchmark/{kind}/{name}.py not found")
    modname = f"benchmark.{kind}.{name}".replace("-", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


def metrics_for(manifest, workload_name, group):
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports:
    those that list it, and those without a ``workloads`` key."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload_name in m["workloads"]]


# -- device -------------------------------------------------------------------

def require_chips(n_chips):
    """The first ``n_chips`` TPU devices, or BenchmarkError: a run that
    finds no accelerator, or fewer chips than the cell asks for, prints
    no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n_chips:
        raise BenchmarkError(
            f"the cell asks for {n_chips} chips; JAX found {len(devices)}")
    return devices[:n_chips]


def peaks_for(device_kind):
    """Published peaks of the device, from peaks.json.  A kind that is not
    in the table is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; "
            "add its published peaks with their source")
    return table[device_kind]


def device_block(devices, rehearsal=False):
    """The ``device`` object of the result line.  ``memory_peak_bytes`` is
    the peak on the fullest chip: the allocator's peak of live bytes, or,
    where that is more, its live bytes plus the bytes it holds reserved
    for the loaded programs' temporaries (a TPU keeps those apart:
    ``bytes_reserved``, which ``peak_bytes_in_use`` does not count)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            if not rehearsal:
                raise BenchmarkError(f"{d} reports no memory_stats()")
            peaks.append(0)
        else:
            peaks.append(max(
                int(stats["peak_bytes_in_use"]),
                int(stats["bytes_in_use"])
                + int(stats.get("bytes_reserved", 0))))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


# -- arithmetic ---------------------------------------------------------------

def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, as numpy's default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def timing_summary(name, values_ms, q=95):
    """Median, mean, the percentile and the sample count of a timing,
    with the highest whole percentile that has ten samples beyond it, and
    a note where the percentile asked for has fewer."""
    n = len(values_ms)
    beyond = int(n * (100 - q) / 100.0)
    out = {"timing": name, "n": n,
           "median_ms": statistics.median(values_ms) if n else None,
           "mean_ms": statistics.fmean(values_ms) if n else None,
           f"p{q}_ms": percentile(values_ms, q) if n else None,
           "samples_beyond": beyond}
    if n > 10:
        supported = min(99, int(100 * (1 - 10 / n)))
        out["supported_percentile"] = supported
        out[f"p{supported}_ms"] = percentile(values_ms, supported)
    if beyond < 10:
        out["note"] = (f"only {beyond} samples beyond p{q}: "
                       "the percentile is close to a maximum")
    return out


def say(obj):
    """One earlier line of output (never the last)."""
    print(json.dumps(obj), flush=True)


# -- no compile inside the window -----------------------------------------------

_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileWatch:
    """Counts JAX's own trace / compile / persistent-cache events (copied
    from chip_smoke.py's ``_watch_events``)."""

    def __init__(self):
        self.counts = {_COMPILE: 0, _TRACE: 0, _CACHE_HIT: 0,
                       _CACHE_MISS: 0}

    def _on_event(self, event, *args, **kwargs):
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self):
        return dict(self.counts)

    def since(self, mark):
        """(programs compiled or loaded, programs traced) since ``mark``."""
        d = {k: v - mark[k] for k, v in self.counts.items()}
        return d[_COMPILE] + d[_CACHE_HIT], d[_TRACE]

    def summary(self):
        return {"backend_compiles": self.counts[_COMPILE],
                "cache_hits": self.counts[_CACHE_HIT],
                "cache_misses": self.counts[_CACHE_MISS]}


@contextlib.contextmanager
def watch_compiles():
    from jax import monitoring
    watch = CompileWatch()
    monitoring.register_event_duration_secs_listener(watch._on_event)
    monitoring.register_event_listener(watch._on_event)
    try:
        yield watch
    finally:
        monitoring.unregister_event_duration_listener(watch._on_event)
        monitoring.unregister_event_listener(watch._on_event)


# -- the comparison that decides ``correct`` ---------------------------------------

class Checks:
    """Each number compared, beside its limit; ``ok`` is their conjunction.
    Every run prints them."""

    def __init__(self):
        self.rows = []

    def limit(self, name, value, limit):
        """``value`` (finite) must not exceed ``limit``."""
        ok = bool(value is not None and math.isfinite(value)
                  and value <= limit)
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": ok})
        return ok

    def require(self, name, cond, detail=None):
        self.rows.append({"check": name, "value": detail, "limit": None,
                          "ok": bool(cond)})
        return bool(cond)

    @property
    def ok(self):
        return all(r["ok"] for r in self.rows)

    def say(self):
        for r in self.rows:
            say(r)


def result_line(correct, attempted, failed, metrics, device, breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v["value"], "unit": v["unit"]}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
