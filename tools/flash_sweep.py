"""Flash-attention tile sweep: fwd / bwd / fwd+bwd TFLOP/s per config.

The round-5 BENCH_NOTES methodology (the sweep that found the 1024-tile
forward win) as ONE reproducible command, extended to the backward:

    make sweep-flash              # = python tools/flash_sweep.py --write-budgets

For every T in ``--T`` and every (block_q, block_k) in ``--blocks``,
times three legs through the Pallas kernels — forward
(``flash_attention_fwd``), backward (``flash_attention_bwd``, both the
FUSED one-pass lowering and the legacy ``split`` two-kernel lowering),
and fwd+bwd — and prints one JSON row each.  ``--write-budgets``
regenerates ``tools/flash_budgets.json`` from the winners (per-T best
fused fwd+bwd config), preserving the committed baseline/target/
structure sections; the tier-1 gate (tests/test_flash_budget.py) then
holds future PRs to the committed numbers.

Chip discipline: on the CPU backend this runs interpret mode at clamped
T (mechanics smoke only — interpret timings are meaningless as perf)
and REFUSES ``--write-budgets``: budgets are measured artifacts.

Sync discipline (bench.py ``_timed_steps``): sync by device->host value
fetch, reps >> 1 to amortize the round-trip.
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "flash_budgets.json")

#: fwd model flops for causal attention (2 dots at 2 flops/MAC, causal
#: halves the score area); bwd ≈ 2.5× fwd (5 dots vs 2)
def model_flops(B, H, T, D, leg):
    fwd = 4.0 * B * H * T * T * D / 2.0
    return {"fwd": fwd, "bwd": 2.5 * fwd, "fwd_bwd": 3.5 * fwd}[leg]


def _timed(fn, args, reps):
    import jax.numpy as jnp
    out = fn(*args)
    # sync via value fetch
    float(jnp.sum(jnp.asarray(out[0] if isinstance(out, tuple) else out)
                  .astype(jnp.float32).ravel()[:1]))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    float(jnp.sum(jnp.asarray(out[0] if isinstance(out, tuple) else out)
                  .astype(jnp.float32).ravel()[:1]))
    return (time.perf_counter() - t0) / reps


def measure_point(fa, B, H, D, T, bq, bk, mode, reps, interp):
    """One (T, block_q, block_k, mode) sweep point → dict of leg
    timings/TFLOP/s (fwd is mode-independent but re-timed per point so
    each row stands alone).  Raises on kernel failure — callers report
    and continue."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    scale = 1.0 / (D ** 0.5)
    q, k, v = (jnp.asarray(np.random.RandomState(i)
                           .normal(0, 1, (B, H, T, D))
                           .astype(np.float32)).astype(jnp.bfloat16)
               for i in range(3))
    g = jnp.ones((B, H, T, D), jnp.bfloat16)

    def fwd(q, k, v):
        return fa.flash_attention_fwd(q, k, v, causal=True, scale=scale,
                                      block_q=bq, block_k=bk,
                                      interpret=interp)

    out, lse = jax.jit(fwd)(q, k, v)

    prev = fa._FLASH_BWD
    fa._FLASH_BWD = mode
    try:
        def bwd(q, k, v, out, lse, g):
            return fa.flash_attention_bwd(
                q, k, v, out, lse, g, causal=True, scale=scale,
                block_q=bq, block_k=bk, interpret=interp,
                bwd_block_q=bq, bwd_block_k=bk)

        def both(q, k, v, g):
            o, l = fwd(q, k, v)
            return bwd(q, k, v, o, l, g)

        row = {}
        for leg, fn, args in (
                ("fwd", jax.jit(fwd), (q, k, v)),
                ("bwd", jax.jit(bwd), (q, k, v, out, lse, g)),
                ("fwd_bwd", jax.jit(both), (q, k, v, g))):
            dt = _timed(fn, args, reps)
            row[f"{leg}_ms"] = round(dt * 1e3, 2)
            row[f"{leg}_tflops"] = round(
                model_flops(B, H, T, D, leg) / dt / 1e12, 1)
        return row
    finally:
        fa._FLASH_BWD = prev


def bwd_kernel_census(fa, mode, T=128):
    """Structural census of the backward lowering: {kernel_name: number
    of exp ops} for every pallas_call in the traced grad program (tiles
    resolve through the normal env/adaptive chain — the census counts
    kernels and exps, which are tile-independent).  The tier-1 budget
    gate pins this — the recompute-once property as a machine-checkable
    fact (fused: ONE bwd kernel, ONE exp; split: two kernels, one exp
    each)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    q, k, v = (jnp.asarray(np.random.RandomState(i)
                           .normal(0, 1, (1, 2, T, 16))
                           .astype(np.float32)) for i in range(3))
    prev = fa._FLASH_BWD
    fa._FLASH_BWD = mode
    try:
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: jax.grad(
                lambda q, k, v: jnp.sum(
                    fa._flash_diff(q, k, v, True, None, True) ** 2),
                argnums=(0, 1, 2))(q, k, v))(q, k, v)
    finally:
        fa._FLASH_BWD = prev
    calls = {}

    def count_exp(sub, n):
        for e in sub.eqns:
            if e.primitive.name == "exp":
                n[0] += 1
            for p in e.params.values():
                pj = getattr(p, "jaxpr", None)
                if pj is not None:
                    count_exp(getattr(pj, "jaxpr", pj), n)

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                n = [0]
                inner = eqn.params["jaxpr"]
                count_exp(getattr(inner, "jaxpr", inner), n)
                calls[name] = n[0]
            for p in eqn.params.values():
                pj = getattr(p, "jaxpr", None)
                if pj is not None:
                    walk(getattr(pj, "jaxpr", pj))
    walk(jaxpr.jaxpr)
    return {k: v for k, v in calls.items() if "bwd" in k}


def write_budgets(winners, args):
    """Regenerate flash_budgets.json: measured winners replace the sweep
    section, baseline/target/structure carry over from the committed
    file (they are commitments, not measurements)."""
    try:
        with open(BUDGETS_PATH) as f:
            budgets = json.load(f)
    except Exception:
        budgets = {}
    budgets["bwd_block_table"] = {
        str(t): list(w["blocks"]) for t, w in sorted(winners.items())}
    budgets["sweep"] = {
        "status": "measured",
        "geometry": {"B": args.B, "H": args.H, "D": args.D,
                     "causal": True, "dtype": "bfloat16"},
        "results": {str(t): {k: v for k, v in w.items() if k != "blocks"}
                    for t, w in sorted(winners.items())},
        "measured_at": time.strftime("%Y-%m-%d"),
    }
    tmp = BUDGETS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(budgets, f, indent=2)
        f.write("\n")
    os.replace(tmp, BUDGETS_PATH)
    print(json.dumps({"probe": "flash_sweep", "wrote": BUDGETS_PATH,
                      "winners": budgets["bwd_block_table"]}), flush=True)
    print(json.dumps({
        "probe": "flash_sweep", "note":
        "paste the winner table into ops/flash_attention.py "
        "_BWD_BLOCK_TABLE (the kernel reads the literal, not this file) "
        "and re-run the tier-1 gate"}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=4)
    ap.add_argument("--H", type=int, default=12)
    ap.add_argument("--D", type=int, default=64)
    ap.add_argument("--T", default="1024,2048,8192,16384")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--blocks", default="256:256,512:512,512:1024,"
                    "1024:512,1024:1024,2048:1024")
    ap.add_argument("--modes", default="fused,split")
    ap.add_argument("--write-budgets", action="store_true")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

    interp = jax.default_backend() == "cpu"
    seqs = tuple(int(t) for t in args.T.split(","))
    reps = args.reps
    if interp:
        seqs = tuple(t for t in seqs if t <= 256) or (128,)
        reps = 1
        print(json.dumps({"probe": "flash_sweep", "warning":
                          "cpu interpret mode: T clamped, timings "
                          "validate mechanics only", "seqs": list(seqs)}),
              flush=True)
        if args.write_budgets:
            print(json.dumps({"probe": "flash_sweep", "error":
                              "--write-budgets refused on the cpu "
                              "backend: budgets are measured artifacts "
                              "— run on the chip"}), flush=True)
            return 2

    winners = {}
    for T in seqs:
        for spec in args.blocks.split(","):
            bq, bk = (int(x) for x in spec.split(":"))
            if bq > T or bk > T or T % bq or T % bk:
                continue
            for mode in args.modes.split(","):
                base = {"probe": "flash_sweep", "T": T, "block_q": bq,
                        "block_k": bk, "bwd_mode": mode,
                        "B": args.B, "H": args.H, "D": args.D}
                if interp:
                    base["interpreted"] = True
                try:
                    row = measure_point(fa, args.B, args.H, args.D, T,
                                        bq, bk, mode, reps, interp)
                except Exception as e:  # noqa: BLE001 — keep sweeping
                    print(json.dumps(dict(
                        base, error=f"{type(e).__name__}: {e}"[:200])),
                        flush=True)
                    continue
                print(json.dumps(dict(base, **row)), flush=True)
                if mode == "fused" and not interp:
                    best = winners.get(T)
                    if best is None or row["fwd_bwd_tflops"] > \
                            best["fwd_bwd_tflops"]:
                        winners[T] = dict(row, blocks=(bq, bk))

    if args.write_budgets and winners:
        write_budgets(winners, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
