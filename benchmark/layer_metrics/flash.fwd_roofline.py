"""The Pallas flash forward (``_flash_kernel_lse``, found by its
``name=``) against its roofline: the least time the chip could take for
one call's FLOPs and bytes (benchmark/flops.py, from shapes) over the
kernel's mean device time.  At head size 64 and 1024 tokens the bound is
compute."""

from benchmark import flops, trace_reduce

KERNELS = ("_flash_kernel_lse",)


def call_shape(view):
    run = view["run"]
    c, job = run.config, run.traffic
    return (job["per_chip_batch"], c["n_head"], job["data"]["seq_len"],
            c["n_embd"] // c["n_head"])


def read(view):
    seconds, calls = trace_reduce.op_seconds(
        view["trace"], KERNELS, view["lo"], view["hi"])
    if not calls:
        return None
    fl, by = flops.flash_forward(*call_shape(view))
    share, _bound = flops.roofline_share(fl, by, seconds / calls,
                                         view["run"].peaks)
    return share
