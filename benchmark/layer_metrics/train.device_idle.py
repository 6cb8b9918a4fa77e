"""Share of a plain (unprofiled) window in which the step program was not
running on the device: 1 - the step's device time (median duration of its
XLA module in the trace, first chip) x the steps per second of the
window measured just before the profiler was started.  Not read from the
traced window's own gaps: the profiler slows a host-fed step (ResNet's
``update()`` takes twice as long under it), so those gaps are the
profiler's, not the cell's."""

from benchmark import trace_reduce


def read(view):
    rate = view["result"].get("steps_per_s")
    step = trace_reduce.median_or_none(trace_reduce.module_runs(
        view["trace"], view["run"].traffic["programs"]["step"]))
    if not rate or step is None:
        return None
    return 100.0 * (1.0 - step * rate)
