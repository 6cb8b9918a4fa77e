"""Model FLOP/s utilization: the FLOPs the forward and backward passes
need (benchmark/flops.py, from shapes) times samples per second per chip,
over the chip's published bf16 peak.  Worked out by the train driver from
the traced window's own rate."""


def read(view):
    return view["result"]["metrics"].get("train.mfu")
