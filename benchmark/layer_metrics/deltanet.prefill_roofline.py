"""The chunked-scan prefill kernel (``_gated_delta_chunk_kernel``, found
by its ``name=``) against the roofline of the RECURRENCE it computes,
whatever implements it: the least time the chip could take for the gated
delta rule's own work on the call's tokens, from shapes, over the
kernel's device time.  One call is one linear layer of one prefill
program over ``T`` tokens (a prompt's bucket, or a suffix's), read from
the shape the call's result has in the trace (``[H, T / C, C, dv]``), so
full and suffix prefills are each held to their own length.

The recurrence a token a head: the decay of the state (``dk · dv``), the
read ``k^T S`` (``2 · dk · dv``), the rank-one write (``2 · dk · dv``)
and the output ``S^T q`` (``2 · dk · dv``): ``7 · dk · dv`` FLOPs.
Bytes: q and k (``dk`` each) and v and o (``dv`` each) once in bfloat16,
``g`` and ``beta`` in float32, and the heads' states in and out (``H ·
dk · dv`` float32 each way).  At 30 heads of 96 x 192 over 17920 tokens:
69 GFLOP (0.35 ms at the MXU's peak) and 628 MB (0.77 ms): memory-bound.
The chunked form computes more than that (the products inside a chunk)
and reads operands the XLA pass before it wrote: that is the
implementation's cost, not the roofline's.  A program without the kernel
gives None."""

import re

from benchmark import trace_reduce

KERNELS = ("_gated_delta_chunk_kernel",)
_RESULT = re.compile(r"\[(\d+),(\d+),(\d+),(\d+)\]")


def recurrence_call(heads, dk, dv, tokens):
    """(FLOPs, bytes) of the recurrence over one call's tokens."""
    fl = 7.0 * dk * dv * heads * tokens
    by = heads * tokens * (2 * dk * 2 + 2 * dv * 2 + 2 * 4) \
        + 2 * heads * dk * dv * 4
    return fl, float(by)


def call_tokens(event):
    """The tokens of one call, from its first result's shape ``[H, N, C,
    dv]`` in the operation's text; None where it names none."""
    shape = _RESULT.search(event.name.partition(" = ")[2])
    if shape is None:
        return None
    return int(shape.group(2)) * int(shape.group(3))


def read(view):
    run, trace = view["run"], view["trace"]
    if not trace.devices:
        return None
    c = run.config
    heads, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                     c["linear_value_head_dim"])
    least = seconds = 0.0
    for e in trace.ops[trace.devices[0]]:
        if e.start < view["lo"] or e.end > view["hi"] \
                or not trace_reduce.is_match(e, KERNELS):
            continue
        tokens = call_tokens(e)
        if tokens is None:
            return None
        fl, by = recurrence_call(heads, dk, dv, tokens)
        least += max(fl / (run.peaks["bf16_tflops"] * 1e12),
                     by / (run.peaks["hbm_gbps"] * 1e9))
        seconds += e.dur
    if not seconds:
        return None
    return 100.0 * least / seconds
