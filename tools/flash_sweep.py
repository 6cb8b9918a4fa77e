"""Flash-attention tile sweep: fwd / bwd / fwd+bwd TFLOP/s per config.

The tile sweep as ONE reproducible command:

    make sweep-flash              # = python tools/flash_sweep.py --write-budgets

For every T in ``--T`` and every (block_q, block_k) in ``--blocks``,
times three legs through the Pallas kernels — forward
(``flash_attention_fwd``), backward (``flash_attention_bwd``) and
fwd+bwd, the tiles passed as arguments — and prints one JSON row each.
``--write-budgets`` rewrites the ``sweep`` section of
``tools/flash_budgets.json`` from the winners (per-T best fwd+bwd
config), preserving the committed baseline/target/structure sections;
the tier-1 gate (tests/test_flash_budget.py) then holds future PRs to
the committed numbers.

Chip discipline: on the CPU backend this runs interpret mode at clamped
T (mechanics smoke only — interpret timings are meaningless as perf)
and REFUSES ``--write-budgets``: budgets are measured artifacts.

Sync discipline: sync by device->host value fetch, reps >> 1 to
amortize the round-trip.
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


def _kernel_ms(fn, args, reps=10):
    """{kernel name: ms a call} of the flash kernels in ``fn``, from the
    device lines of a profiler trace (the benchmark's own reduction):
    the time the rooflines in PERF.md divide by, with none of the XLA
    operations around the kernel in it."""
    import shutil
    import tempfile

    import jax
    from benchmark import trace_reduce
    jax.block_until_ready(fn(*args))
    tdir = tempfile.mkdtemp(prefix="flash_sweep_trace")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    lo, hi = trace_reduce.window(trace)
    ms = {}
    for name in ("_flash_kernel_lse", "_flash_bwd_fused_kernel"):
        seconds, calls = trace_reduce.op_seconds(trace, (name,), lo, hi)
        if calls:
            ms[name] = round(seconds / calls * 1e3, 4)
    return ms


#: calls timed back to back inside ONE program on the chip, each fed the
#: one before it (so none is folded away): a 0.2 ms kernel is then timed
#: by the device and not by the host's dispatch
CHAIN = 24


#: how a point's operands lie: ``heads`` = q, k, v as ``[B, H, T, D]``;
#: ``rows`` = the qkv GEMM's own ``[B, T, 3·H·D]``, ``128 // D`` heads a
#: 128-lane column block
FORMS = ("heads", "rows")


def measure_point(fa, B, H, D, T, bq, bk, reps, interp, form="heads"):
    """One (T, block_q, block_k) sweep point → dict of leg
    timings/TFLOP/s: a call's share of :data:`CHAIN` chained calls, and
    on the chip each kernel's own device time (``kernel_ms``), in one of
    :data:`FORMS`.  Raises on kernel failure — callers report and
    continue."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    scale = 1.0 / (D ** 0.5)

    def draw(i, shape):
        return jnp.asarray(np.random.RandomState(i).normal(0, 1, shape)
                           .astype(np.float32)).astype(jnp.bfloat16)

    if form == "heads":
        operands = tuple(draw(i, (B, H, T, D)) for i in range(3))
        g = jnp.ones((B, H, T, D), jnp.bfloat16)

        def fwd(q, k, v):
            return fa.flash_attention_fwd(q, k, v, causal=True, scale=scale,
                                          block_q=bq, block_k=bk,
                                          interpret=interp)

        def bwd(q, k, v, out, lse, g):
            return fa.flash_attention_bwd(
                q, k, v, out, lse, g, causal=True, scale=scale,
                block_q=bq, block_k=bk, interpret=interp,
                bwd_block_q=bq, bwd_block_k=bk)

        def feed(first, q):
            # the call's first output (out, or dq) has q's shape and
            # dtype: it is the next call's q
            return first
    else:
        operands = (draw(0, (B, T, 3 * H * D)),)
        g = jnp.ones((B, T, H * D), jnp.bfloat16)

        def fwd(qkv):
            return fa.flash_self_attention_fwd(
                qkv, H, causal=True, scale=scale, block_q=bq, block_k=bk,
                interpret=interp)

        def bwd(qkv, out, lse, g):
            return (fa.flash_self_attention_bwd(
                qkv, out, lse, g, H, causal=True, scale=scale,
                interpret=interp, bwd_block_q=bq, bwd_block_k=bk),)

        def feed(first, qkv):
            # the backward's output is the next call's qkv; the
            # forward's is written over its q columns
            return first if first.shape == qkv.shape else \
                jax.lax.dynamic_update_slice(qkv, first, (0, 0, 0))

    n = len(operands)
    out, lse = jax.jit(fwd)(*operands)

    def both(*args):
        o, l = fwd(*args[:n])
        return bwd(*args[:n], o, l, args[n])

    chain = 1 if interp else CHAIN

    def chained(fn):
        if chain == 1:
            return fn

        def run(x, *rest):
            return jax.lax.fori_loop(
                0, chain, lambda _, x: feed(fn(x, *rest)[0], x), x)
        return run

    row = {}
    for leg, fn, args in (
            ("fwd", fwd, operands),
            ("bwd", bwd, operands + (out, lse, g)),
            ("fwd_bwd", both, operands + (g,))):
        dt = _timed(jax.jit(chained(fn)), args, reps) / chain
        row[f"{leg}_ms"] = round(dt * 1e3, 4)
        row[f"{leg}_tflops"] = round(
            model_flops(B, H, T, D, leg) / dt / 1e12, 1)
    if not interp:
        row["kernel_ms"] = _kernel_ms(jax.jit(both), operands + (g,))
    return row


def bwd_kernel_census(fa, T=128, block=64, form="heads"):
    """Structural census of the backward lowering: for every backward
    pallas_call of a causal call whose walk is 3 tiles (T = 128 in 64 x
    64 tiles: two the diagonal crosses, one below it), the ``exp``
    equations a tile of a head costs.  Counted both ways a kernel walks:
    with the walk unrolled (``fa._STATIC_WALK_ELEMS`` as committed: the
    exps in the kernel over the tiles walked) and with it looped
    (forced: the most exps any ONE loop body holds, and ``loop_bodies``,
    since a tile is walked by exactly one body, masked where the
    diagonal crosses it and unmasked elsewhere); ``exp_per_tile`` is the
    larger.  That is the recompute-once property as a machine-checkable
    fact: ONE bwd kernel, ONE exp a tile.  ``form="rows"`` counts the
    same kernel where a block holds two heads (:data:`FORMS`): the
    counts a head are the heads-first form's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def draw(i, shape):
        return jnp.asarray(np.random.RandomState(i).normal(0, 1, shape)
                           .astype(np.float32))

    heads = 1 if form == "heads" else 2     # a block
    tiles = len(fa._causal_tile_walk(T, T, block, block)) * heads

    def subjaxprs(eqn):
        for p in eqn.params.values():
            for pj in (p if isinstance(p, (tuple, list)) else (p,)):
                pj = getattr(pj, "jaxpr", pj)  # closed or open
                if hasattr(pj, "eqns"):
                    yield pj

    def count_exp(jx):
        return sum((e.primitive.name == "exp")
                   + sum(count_exp(sub) for sub in subjaxprs(e))
                   for e in jx.eqns)

    def loop_bodies(jx):
        for e in jx.eqns:
            if e.primitive.name in ("while", "scan"):
                yield sum(count_exp(sub) for sub in subjaxprs(e))
            else:
                for sub in subjaxprs(e):
                    yield from loop_bodies(sub)

    def kernels(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], next(subjaxprs(eqn))
            else:
                for sub in subjaxprs(eqn):
                    yield from kernels(sub)

    def trace():
        if form == "heads":
            q, k, v, g = (draw(i, (1, 2, T, 16)) for i in range(4))
            out, lse = fa.flash_attention_fwd(q, k, v, causal=True,
                                              block_q=block, block_k=block,
                                              interpret=True)
            return jax.make_jaxpr(lambda *a: fa.flash_attention_bwd(
                *a, causal=True, block_q=block, block_k=block,
                bwd_block_q=block, bwd_block_k=block, interpret=True))(
                    q, k, v, out, lse, g).jaxpr
        qkv, g = draw(0, (1, T, 3 * 2 * 64)), draw(1, (1, T, 2 * 64))
        out, lse = fa.flash_self_attention_fwd(
            qkv, 2, causal=True, block_q=block, block_k=block,
            interpret=True)
        return jax.make_jaxpr(lambda *a: fa.flash_self_attention_bwd(
            *a, 2, causal=True, bwd_block_q=block, bwd_block_k=block,
            interpret=True))(qkv, out, lse, g).jaxpr

    prev = fa._STATIC_WALK_ELEMS
    try:
        unrolled = dict(kernels(trace()))
        fa._STATIC_WALK_ELEMS = 0
        looped = dict(kernels(trace()))
    finally:
        fa._STATIC_WALK_ELEMS = prev
    census = {}
    for name, jx in looped.items():
        bodies = list(loop_bodies(jx))
        per_tile = max(bodies)
        if not list(loop_bodies(unrolled[name])):   # this kernel unrolls
            exps = count_exp(unrolled[name])
            per_tile = max(per_tile, -(-exps // tiles))
        census[name] = {"loop_bodies": len(bodies) // heads,
                        "exp_per_tile": per_tile}
    return census


def write_budgets(winners, args):
    """Regenerate flash_budgets.json: measured winners replace the sweep
    section, baseline/target/structure carry over from the committed
    file (they are commitments, not measurements)."""
    try:
        with open(BUDGETS_PATH) as f:
            budgets = json.load(f)
    except Exception:
        budgets = {}
    budgets["sweep"] = {
        "status": "measured",
        "geometry": {"B": args.B, "H": args.H, "D": args.D,
                     "causal": True, "dtype": "bfloat16"},
        "results": {str(t): w for t, w in sorted(winners.items())},
        "measured_at": time.strftime("%Y-%m-%d"),
    }
    tmp = BUDGETS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(budgets, f, indent=2)
        f.write("\n")
    os.replace(tmp, BUDGETS_PATH)
    print(json.dumps({"probe": "flash_sweep", "wrote": BUDGETS_PATH,
                      "winners": {str(t): w["blocks"] for t, w in
                                  sorted(winners.items())}}), flush=True)
    print(json.dumps({
        "probe": "flash_sweep", "note":
        "a winner under T goes into ops/flash_attention.py "
        "_CAUSAL_BLOCK_TABLE and this file's causal_block_table (the "
        "kernel reads the literal, not this file); re-run the tier-1 "
        "gate"}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=4)
    ap.add_argument("--H", type=int, default=12)
    ap.add_argument("--D", type=int, default=64)
    ap.add_argument("--T", default="1024,2048,8192,16384")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--blocks", default="256:256,512:512,512:1024,"
                    "1024:512,1024:1024,2048:1024")
    ap.add_argument("--forms", default="heads",
                    help="comma list of " + ", ".join(FORMS))
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
            for form in args.forms.split(","):
                base = {"probe": "flash_sweep", "T": T, "block_q": bq,
                        "block_k": bk, "B": args.B, "H": args.H,
                        "D": args.D, "form": form}
                if interp:
                    base["interpreted"] = True
                try:
                    row = measure_point(fa, args.B, args.H, args.D, T, bq,
                                        bk, reps, interp, form=form)
                except Exception as e:  # noqa: BLE001 — keep sweeping
                    print(json.dumps(dict(
                        base, error=f"{type(e).__name__}: {e}"[:200])),
                        flush=True)
                    continue
                print(json.dumps(dict(base, **row)), flush=True)
                # the budgets' winners are the heads-first form's
                if not interp and form == "heads":
                    best = winners.get(T)
                    if best is None or row["fwd_bwd_tflops"] > \
                            best["fwd_bwd_tflops"]:
                        winners[T] = dict(row, blocks=[bq, bk])

    if args.write_budgets and winners:
        write_budgets(winners, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
