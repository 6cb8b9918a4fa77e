"""On-chip flash-attention block-size sweep (round-5 MFU chase).

The tfm1024 trace showed the Pallas attention custom-calls taking ~49%
of the transformer step while the surrounding GEMM fusions run at 90%
of MXU peak — the 128x128 default tiles serialize the online-softmax
recurrence into too-small MXU dots.  This sweeps (block_q, block_k)
explicitly (the kernel entry points take them as arguments, so one
process can compare configs without the env-knob retrace hazard) and
prints one JSON line per config.

Usage:  python tools/flash_block_sweep.py [--T 2048] [--reps 20]

SUPERSEDED for new work by tools/flash_sweep.py (`make sweep-flash`):
per-leg fwd/bwd/fwd+bwd rows, fused-vs-split backward modes, and the
flash_budgets.json regeneration.  Kept because the r5 BENCH_NOTES rows
were produced by this exact script.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=4)
    ap.add_argument("--H", type=int, default=12)
    ap.add_argument("--D", type=int, default=64)
    ap.add_argument("--T", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--blocks", default="128:128,256:256,512:512,"
                    "1024:1024,512:1024,1024:512,2048:512,512:2048")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np
    import importlib
    # the ops package re-exports the flash_attention FUNCTION under the
    # module's name — import the module itself for the fwd/bwd entries
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

    interp = jax.default_backend() == "cpu"
    B, H, T, D = args.B, args.H, args.T, args.D
    scale = 1.0 / (D ** 0.5)
    q, k, v = (jnp.asarray(np.random.RandomState(i)
                           .normal(0, 1, (B, H, T, D))
                           .astype(np.float32)).astype(jnp.bfloat16)
               for i in range(3))
    g = jnp.ones((B, H, T, D), jnp.bfloat16)

    # attention fwd+bwd model flops: fwd = 2 dots at 2 flops/MAC
    # (4*B*H*T^2*D), bwd ~= 2.5x fwd (5 dots), causal halves the work
    flops = 4 * B * H * T * T * D * 3.5 / 2

    def timed(fn, *xs):
        fn(*xs)[0].block_until_ready()
        # sync by a device->host value fetch (bench._timed_steps)
        float(jnp.sum(fn(*xs)[0].astype(jnp.float32)))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        float(jnp.sum(out[0].astype(jnp.float32)))
        return (time.perf_counter() - t0) / args.reps

    for spec in args.blocks.split(","):
        bq, bk = (int(x) for x in spec.split(":"))
        if bq > T or bk > T:
            continue

        def step(q, k, v, g, bq=bq, bk=bk):
            out, lse = fa.flash_attention_fwd(
                q, k, v, causal=True, scale=scale, block_q=bq,
                block_k=bk, interpret=interp)
            dq, dk, dv = fa.flash_attention_bwd(
                q, k, v, out, lse, g, causal=True, scale=scale,
                block_q=bq, block_k=bk, interpret=interp)
            return dq, dk, dv

        fn = jax.jit(step)
        try:
            dt = timed(fn, q, k, v, g)
        except Exception as e:  # noqa: BLE001 — report and keep sweeping
            print(json.dumps({"probe": "flash_block_sweep", "T": T,
                              "block_q": bq, "block_k": bk,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)
            continue
        print(json.dumps({"probe": "flash_block_sweep", "T": T,
                          "block_q": bq, "block_k": bk,
                          "fwd_bwd_ms": round(dt * 1e3, 2),
                          "tflops": round(flops / dt / 1e12, 1)}),
              flush=True)


if __name__ == "__main__":
    main()
