"""The windowed Pallas forward (``_flash_window_kernel``, found by its
``name=``) against its roofline: the least time the chip could take for
the FLOPs and bytes of the BAND alone, from shapes, over the kernel's
mean device time.  One call is one sliding layer of one full prefill:
``H`` query heads over ``G`` K/V heads of ``D`` on ``T`` tokens (the
prompt's bucket), each query seeing the last ``window`` keys.

FLOPs: two products (scores, values) over the band's ``window · (window
+ 1) / 2 + (T - window) · window`` pairs a head.  Bytes: q read and the
output written once a query head, k and v read once a K/V head.  The
kernel walks whole tiles, so it computes more than the band (3 tiles of
256 x 256 a query tile for 2 tiles' worth of band): that is the
kernel's cost, not the roofline's.  A program without the kernel gives
None."""

from benchmark import flops, trace_reduce

KERNELS = ("_flash_window_kernel",)


def prefill_bucket(traffic):
    """The one power-of-two bucket (capped at ``max_context``) the mix's
    prompts land in."""
    mix, cap = traffic["mix"], traffic["engine"]["max_context"]
    buckets = set()
    for n in (mix["prefix_len"] + mix["tail"][0],
              mix["prefix_len"] + mix["tail"][1]):
        b = 16
        while b < n and b < cap:
            b *= 2
        buckets.add(min(b, cap))
    if len(buckets) != 1:
        raise ValueError(f"prompts in several prefill buckets {buckets}: "
                         "one call's shape is not known from the mix")
    return buckets.pop()


def band_call(heads, kv_heads, seq_len, head_dim, window, itemsize=2):
    """(FLOPs, bytes) of one windowed causal forward call."""
    w = min(window, seq_len)
    pairs = w * (w + 1) / 2 + (seq_len - w) * w
    fl = 2 * 2.0 * heads * pairs * head_dim
    by = 2.0 * (heads + kv_heads) * seq_len * head_dim * itemsize
    return fl, by


def read(view):
    run = view["run"]
    seconds, calls = trace_reduce.op_seconds(
        view["trace"], KERNELS, view["lo"], view["hi"])
    if not calls:
        return None
    c = run.config
    n = c["num_hidden_layers"]
    heads = {h for h, kind in zip(c["num_attention_heads_per_layer"][:n],
                                  c["layer_types"][:n])
             if kind == "sliding_attention"}
    if len(heads) != 1:
        return None
    fl, by = band_call(heads.pop(), c["num_key_value_heads"],
                       prefill_bucket(run.traffic), c["head_dim"],
                       c["sliding_window"])
    share, _bound = flops.roofline_share(fl, by, seconds / calls, run.peaks)
    return share
