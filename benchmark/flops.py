"""Operations and bytes a piece of work needs, from its shapes.

The yardstick's arithmetic: model FLOPs for ``train.mfu`` (the forward
and backward passes require them; recomputed operations do not count)
and each named kernel's FLOPs and bytes for its roofline share.  A
multiply-add is two operations.
"""

from __future__ import annotations


def transformer_forward_flops_per_token(n_embd, n_layer, vocab_size,
                                        seq_len):
    """Matrix products of one token's forward pass: per layer qkv
    (d x 3d), attention output (d x d) and the MLP (d x 4d, 4d x d),
    12 d^2 multiply-adds; the untied head d x V; causal attention, where
    a token at position p reads p+1 keys, on average (T+1)/2: scores and
    values are 2 d multiply-adds a key."""
    weights = 12 * n_layer * n_embd ** 2 + n_embd * vocab_size
    attention = n_layer * 2 * n_embd * (seq_len + 1) / 2
    return 2.0 * (weights + attention)


def resnet_forward_flops_per_image(block_counts, num_classes, image_size):
    """Convolutions and the classifier of ResNet v1 bottleneck stages
    (stride on the 3x3, projection shortcut on each stage's first
    block), from the shapes: 2 k^2 C_in C_out H_out W_out a convolution."""
    def conv(k, cin, cout, hw):
        return 2.0 * k * k * cin * cout * hw * hw

    hw = image_size // 2
    total = conv(7, 3, 64, hw)
    hw //= 2                              # 3x3 max pool, stride 2
    cin = 64
    for stage, n_blocks in enumerate(block_counts):
        mid, cout = 64 * 2 ** stage, 256 * 2 ** stage
        for b in range(n_blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            total += conv(1, cin, mid, hw)
            hw_out = hw // stride
            total += conv(3, mid, mid, hw_out)
            total += conv(1, mid, cout, hw_out)
            if b == 0:
                total += conv(1, cin, cout, hw_out)
            cin, hw = cout, hw_out
    return total + 2.0 * cin * num_classes


def train_flops_per_sample(config, job):
    """Forward plus backward (twice the forward) of one sample: a
    sequence of ``seq_len`` tokens, or an image."""
    if job["data"]["kind"] == "lm_tokens":
        T = job["data"]["seq_len"]
        return 3.0 * T * transformer_forward_flops_per_token(
            config["n_embd"], config["n_layer"], config["vocab_size"], T)
    if job["data"]["kind"] == "images":
        return 3.0 * resnet_forward_flops_per_image(
            config["block_counts"], config["num_classes"],
            config["image_size"])
    raise ValueError(f"no FLOP count for data kind {job['data']['kind']!r}")


def flash_forward(batch, heads, seq_len, head_dim, itemsize=2):
    """(FLOPs, bytes) of one causal flash-attention forward call: two
    products (scores, values) over the lower triangle; q, k, v read and
    the output written once, plus the float32 log-sum-exp row."""
    pairs = seq_len * (seq_len + 1) / 2
    fl = 2 * 2.0 * batch * heads * pairs * head_dim
    by = 4.0 * batch * heads * seq_len * head_dim * itemsize \
        + 4.0 * batch * heads * seq_len
    return fl, by


def flash_backward(batch, heads, seq_len, head_dim, itemsize=2):
    """(FLOPs, bytes) of the backward: five products over the lower
    triangle (scores again, dV, dP, dQ, dK); q, k, v, o, do read and dq,
    dk, dv written once."""
    pairs = seq_len * (seq_len + 1) / 2
    fl = 5 * 2.0 * batch * heads * pairs * head_dim
    by = 8.0 * batch * heads * seq_len * head_dim * itemsize \
        + 2 * 4.0 * batch * heads * seq_len
    return fl, by


def roofline_share(flops_needed, bytes_needed, seconds, peaks):
    """(share in %, which bound): the least time the chip could take,
    the larger of operations over peak FLOP/s and bytes over peak
    bytes/s, over the time taken."""
    t_compute = flops_needed / (peaks["bf16_tflops"] * 1e12)
    t_memory = bytes_needed / (peaks["hbm_gbps"] * 1e9)
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
