"""The Pallas flash backward against its roofline: the fused kernel
(``_flash_bwd_fused_kernel``) where the step holds it, else the split
pair (``_flash_bwd_dq_kernel`` + ``_flash_bwd_dkv_kernel``) taken
together as one backward."""

from benchmark import flops, harness, trace_reduce

FUSED = ("_flash_bwd_fused_kernel",)
SPLIT = ("_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel")


def read(view):
    t, lo, hi = view["trace"], view["lo"], view["hi"]
    seconds, calls = trace_reduce.op_seconds(t, FUSED, lo, hi)
    if not calls:
        seconds, events = trace_reduce.op_seconds(t, SPLIT, lo, hi)
        calls = events // 2
    if not calls:
        return None
    shape = harness.load_module("layer_metrics",
                                "flash.fwd_roofline").call_shape(view)
    fl, by = flops.flash_backward(*shape)
    share, _bound = flops.roofline_share(fl, by, seconds / calls,
                                         view["run"].peaks)
    return share
