"""Capture a jax.profiler trace of the benchmark train step on the TPU.

VERDICT r2 Missing #2 / next-round #2: the MFU chase needs trace-backed
evidence of where the chip's cycles go (layout transposes? input feed?
small-conv underutilization?).  This captures an on-chip trace of the
exact bench configuration and prints a per-op-category summary.

Usage (on the real chip):
    python tools/profile_tpu_step.py [--layout NHWC] [--bs 64] [--steps 8]
    python tools/profile_tpu_step.py --model transformer --bs 8

The trace lands in /tmp/chainermn_tpu_trace/<ts>/ (TensorBoard-loadable
``plugins/profile`` directory).  The printed summary is self-contained:
it parses the trace's .xplane.pb with the pure-python protobuf walker
below (no tensorboard dependency in this image).
"""

import argparse
import glob
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "transformer"])
    ap.add_argument("--layout", default="NHWC", choices=["NHWC", "NCHW"])
    ap.add_argument("--bs", type=int, default=64)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--out", default="/tmp/chainermn_tpu_trace")
    ap.add_argument("--tag", default=None,
                    help="stable trace-dir name (default: timestamp) so "
                         "a later --compare can find it")
    ap.add_argument("--compare", nargs=2, metavar=("DIR_A", "DIR_B"),
                    default=None,
                    help="offline per-op diff of two existing traces "
                         "(no jax import, no device touch)")
    ap.add_argument("--roofline", metavar="DIR", default=None,
                    help="offline roofline table of an existing trace: "
                         "per-op achieved FLOP/s vs the HBM/MXU bound "
                         "implied by its bytes_accessed (no device touch)")
    ap.add_argument("--steps-hint", type=int, default=8,
                    help="steps the trace window covered (per-step math)")
    ap.add_argument("--platform", default=None,
                    help="override platform (cpu for a smoke run)")
    args = ap.parse_args()

    if args.compare:
        compare(*args.compare)
        return
    if args.roofline:
        roofline(args.roofline, steps=args.steps_hint)
        return

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import MomentumSGD, Adam

    devices = jax.devices()
    print(f"devices: {devices}", flush=True)

    comm = ct.create_communicator("jax_ici",
                                  allreduce_grad_dtype="bfloat16")
    rng = np.random.RandomState(0)
    if args.model == "transformer":
        from chainermn_tpu.models import TransformerLM
        model = TransformerLM(n_vocab=32768, d_model=768, n_heads=12,
                              n_layers=12, max_len=args.seq, seed=0,
                              compute_dtype=jnp.bfloat16)
        comm.bcast_data(model)
        inner = Adam(alpha=3e-4)
        inner.donate_params = True
        opt = ct.create_multi_node_optimizer(inner, comm).setup(model)
        x = jnp.asarray(rng.randint(0, 32768, (args.bs, args.seq))
                        .astype(np.int32))
        t = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))
    else:
        from chainermn_tpu.models import Classifier, ResNet50
        model = Classifier(ResNet50(n_classes=1000, seed=0,
                                    compute_dtype=jnp.bfloat16,
                                    layout=args.layout))
        comm.bcast_data(model)
        inner = MomentumSGD(lr=0.1, momentum=0.9)
        inner.donate_params = True
        opt = ct.create_multi_node_optimizer(inner, comm).setup(model)
        shape = ((args.bs, args.size, args.size, 3)
                 if args.layout == "NHWC"
                 else (args.bs, 3, args.size, args.size))
        x = jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
        t = jnp.asarray(rng.randint(0, 1000, args.bs).astype(np.int32))

    # compile + warm up OUTSIDE the trace window
    t0 = time.perf_counter()
    loss = opt.update(model, x, t)
    float(loss)
    print(f"compile+first step: {time.perf_counter() - t0:.1f}s", flush=True)
    loss = opt.update(model, x, t)
    float(loss)

    out_dir = os.path.join(args.out,
                           args.tag or time.strftime("%Y%m%d-%H%M%S"))
    if args.tag and os.path.isdir(out_dir):
        # a stable tag dir re-used across runs would hold several trace
        # sessions and the parser could pick a stale one — start fresh
        import shutil
        shutil.rmtree(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    with jax.profiler.trace(out_dir):
        for _ in range(args.steps):
            loss = opt.update(model, x, t)
        float(loss)  # real device sync: a value fetch
    t1 = time.perf_counter()
    for _ in range(args.steps):
        loss = opt.update(model, x, t)
    float(loss)
    wall = (time.perf_counter() - t1) / args.steps
    print(f"trace written to {out_dir}; untraced step {wall*1000:.1f} ms",
          flush=True)
    summarize(out_dir)


# -- minimal xplane.pb reader (no tensorboard in this image) ---------------

def _read_varint(buf, i):
    shift, val = 0, 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _walk_fields(buf):
    """Yield (field_number, wire_type, value_bytes_or_int) of one message."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _read_varint(buf, i)
            yield field, wt, v
        elif wt == 2:
            ln, i = _read_varint(buf, i)
            yield field, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:
            yield field, wt, buf[i:i + 4]
            i += 4
        elif wt == 1:
            yield field, wt, buf[i:i + 8]
            i += 8
        else:
            return


def _parse_meta_entry(v):
    """Parse one map<int64, X{Event,Stat}Metadata> entry -> (id, name).

    Entry: key(1) varint, value(2) submessage.  XEventMetadata carries
    name(2) and display_name(4) — TPU device planes put the HLO op name
    in `name`; prefer it, fall back to display_name.  XStatMetadata has
    name(2) only.
    """
    k, meta_name, disp_name = None, "", ""
    for f2, w2, v2 in _walk_fields(v):
        if f2 == 1 and w2 == 0:
            k = v2
        elif f2 == 2 and w2 == 2:
            for f3, w3, v3 in _walk_fields(v2):
                if f3 == 2 and w3 == 2:
                    meta_name = v3.decode(errors="replace")
                elif f3 == 4 and w3 == 2:
                    disp_name = v3.decode(errors="replace")
    return k, (meta_name or disp_name)


def _collect(out_dir, by_category=False):
    """Parse the trace into {plane_name: {op_name: total_ps}}.

    XSpace: planes(1) -> XPlane{name(2), lines(3) -> XLine{events(4) ->
    XEvent{metadata_id(1), duration_ps(3), stats(4)}},
    event_metadata(4) map<id, XEventMetadata{id(1), name(2),
    display_name(4), stats(5)}>, stat_metadata(5) map<id,
    XStatMetadata>}.  (Round-5 fix: event names live in plane field 4 —
    the old parser read field 5, i.e. STAT metadata, so HLO program ops
    printed as bare numeric ids.)  Prefers device planes (TPU); falls
    back to the host CPU plane for smoke runs.

    by_category=True groups by the op's `hlo_category` stat (e.g.
    "convolution", "convolution fusion") instead of individual op name;
    per-op XStats live on the event METADATA's stats(5) for TPU planes.
    """
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None  # no trace file at all (vs {}: file but no events)
    # a re-used --tag dir can hold several trace sessions; parse the
    # newest capture, not scandir order
    data = open(max(paths, key=os.path.getmtime), "rb").read()
    planes = [v for f, w, v in _walk_fields(data) if f == 1 and w == 2]

    def plane_name(plane):
        for f, w, v in _walk_fields(plane):
            if f == 2 and w == 2:
                return v.decode(errors="replace")
        return ""

    device = [p for p in planes
              if "TPU" in plane_name(p) or "/device" in plane_name(p).lower()]
    host = [p for p in planes if plane_name(p) == "/host:CPU"]
    # a process that has loaded libtpu (a compile-only client is enough)
    # writes TPU planes with no events into a CPU capture: the host
    # plane is the fallback for device planes that are empty, too
    return _plane_totals(device, by_category) \
        or _plane_totals(host, by_category)


def _plane_totals(chosen, by_category):
    result = {}
    for plane in chosen:
        name = ""
        metadata = {}        # event metadata id -> op name
        stat_names = {}      # stat metadata id -> stat name
        raw_event_meta = {}  # event metadata id -> raw submessage
        lines = []
        for f, w, v in _walk_fields(plane):
            if f == 2 and w == 2:
                name = v.decode(errors="replace")
            elif f == 3 and w == 2:
                lines.append(v)
            elif f == 4 and w == 2:
                k, nm = _parse_meta_entry(v)
                if k is not None:
                    metadata[k] = nm
                    for f2, w2, v2 in _walk_fields(v):
                        if f2 == 2 and w2 == 2:
                            raw_event_meta[k] = v2
            elif f == 5 and w == 2:
                k, nm = _parse_meta_entry(v)
                if k is not None:
                    stat_names[k] = nm
        categories = {}
        if by_category:
            # XEventMetadata.stats(5) -> XStat{metadata_id(1),
            # str_value(5)/ref_value(7)}
            for mid, raw in raw_event_meta.items():
                for f2, w2, v2 in _walk_fields(raw):
                    if f2 != 5 or w2 != 2:
                        continue
                    sid, sval = None, None
                    for f3, w3, v3 in _walk_fields(v2):
                        if f3 == 1 and w3 == 0:
                            sid = v3
                        elif f3 == 5 and w3 == 2:
                            sval = v3.decode(errors="replace")
                        elif f3 == 7 and w3 == 0:
                            sval = stat_names.get(v3, str(v3))
                    if sid is not None \
                            and stat_names.get(sid) == "hlo_category":
                        categories[mid] = sval or "uncategorized"
        totals = {}
        for line in lines:
            for f, w, v in _walk_fields(line):
                if f == 4 and w == 2:  # XEvent
                    mid, dur = None, 0
                    for f2, w2, v2 in _walk_fields(v):
                        if f2 == 1 and w2 == 0:
                            mid = v2
                        elif f2 == 3 and w2 == 0:
                            dur = v2
                    if mid is not None:
                        if by_category:
                            key = categories.get(
                                mid, metadata.get(mid, str(mid)))
                        else:
                            key = metadata.get(mid, str(mid))
                        totals[key] = totals.get(key, 0) + dur
        if totals:
            result[name] = totals
    return result


def summarize(out_dir, top=25):
    """Print per-op self-time aggregated from the device XPlane, then
    the same events grouped by `hlo_category` (conv/fusion/allreduce...)
    — the category view is what the MFU decision tree reads."""
    collected = _collect(out_dir)
    if collected is None:
        print("no xplane.pb found (trace not written?)")
        return
    if not collected:
        print("xplane.pb present but no plane had events "
              "(empty trace window?)")
        return
    for name, totals in collected.items():
        total_ps = sum(totals.values())
        print(f"\n== plane: {name} — total {total_ps/1e12:.3f} s of events")
        for op, ps in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
            print(f"  {ps/1e9:10.3f} ms  {100*ps/total_ps:5.1f}%  {op[:90]}")
    by_cat = _collect(out_dir, by_category=True) or {}
    for name, totals in by_cat.items():
        total_ps = sum(totals.values())
        print(f"\n== plane: {name} — by hlo_category")
        for op, ps in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
            print(f"  {ps/1e9:10.3f} ms  {100*ps/total_ps:5.1f}%  {op[:90]}")


def _collect_op_stats(out_dir):
    """Join device-plane event durations with their metadata's XStats.

    Returns {op_name: {"ps": total_ps, "n": events, "flops": f,
    "bytes": b, "category": c, "source": s}} — flops/bytes are PER
    EXECUTION (XLA cost-model numbers stamped on the op), so achieved
    FLOP/s = flops * n / ps.  Only ops carrying a flops or
    bytes_accessed stat are returned (i.e. real program ops, not step
    markers or async DMA span bookkeeping).
    """
    import struct
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    data = open(max(paths, key=os.path.getmtime), "rb").read()
    planes = [v for f, w, v in _walk_fields(data) if f == 1 and w == 2]
    result = {}
    for plane in planes:
        pname = ""
        stat_names = {}
        metas = {}   # mid -> raw XEventMetadata
        lines = []
        for f, w, v in _walk_fields(plane):
            if f == 2 and w == 2:
                pname = v.decode(errors="replace")
            elif f == 3 and w == 2:
                lines.append(v)
            elif f == 4 and w == 2:
                k = None
                raw = None
                for f2, w2, v2 in _walk_fields(v):
                    if f2 == 1 and w2 == 0:
                        k = v2
                    elif f2 == 2 and w2 == 2:
                        raw = v2
                if k is not None and raw is not None:
                    metas[k] = raw
            elif f == 5 and w == 2:
                k, nm = _parse_meta_entry(v)
                if k is not None:
                    stat_names[k] = nm
        if "TPU" not in pname:
            continue
        info = {}
        for mid, raw in metas.items():
            nm = ""
            st = {}
            for f2, w2, v2 in _walk_fields(raw):
                if f2 == 2 and w2 == 2:
                    nm = v2.decode(errors="replace")
                elif f2 == 5 and w2 == 2:
                    # XStat value oneof: double_value=2 (fixed64),
                    # uint64_value=3 / int64_value=4 / ref_value=7
                    # (varint), str_value=5 (len-delimited).  This
                    # profiler stamps flops/bytes_accessed via the
                    # int64_value field.
                    sid, val = None, None
                    for f3, w3, v3 in _walk_fields(v2):
                        if f3 == 1 and w3 == 0:
                            sid = v3
                        elif f3 == 2 and w3 == 1:
                            val = struct.unpack("<d", v3)[0]
                        elif f3 in (3, 4) and w3 == 0:
                            val = v3
                        elif f3 == 5 and w3 == 2:
                            val = v3.decode(errors="replace")
                        elif f3 == 7 and w3 == 0:
                            # interned string stat: resolve the ref
                            val = stat_names.get(v3, str(v3))
                    if sid is not None:
                        st[stat_names.get(sid, sid)] = val
            info[mid] = (nm, st)
        durs = {}
        for line in lines:
            for f, w, v in _walk_fields(line):
                if f == 4 and w == 2:
                    mid, dur = None, 0
                    for f2, w2, v2 in _walk_fields(v):
                        if f2 == 1 and w2 == 0:
                            mid = v2
                        elif f2 == 3 and w2 == 0:
                            dur = v2
                    if mid is not None:
                        a = durs.setdefault(mid, [0, 0])
                        a[0] += dur
                        a[1] += 1
        for mid, (ps, n) in durs.items():
            nm, st = info.get(mid, (str(mid), {}))
            flops = st.get("flops") or st.get("model_flops") or 0
            nbytes = st.get("bytes_accessed") or 0
            if not flops and not nbytes:
                continue
            # the same op name can recur across planes (multi-core) or
            # metadata ids — SUM, don't overwrite (cf. compare()'s merge)
            prev = result.get(nm)
            if prev is None:
                result[nm] = {"ps": ps, "n": n, "flops": flops,
                              "bytes": nbytes,
                              "category": st.get("hlo_category", ""),
                              "source": st.get("source", "")}
            else:
                # flops/bytes are per-execution costs: keep them, sum
                # the observed time/executions
                prev["ps"] += ps
                prev["n"] += n
    return result


def roofline(out_dir, steps=8, peak_tflops=197.0, peak_hbm_gbs=819.0,
             top=20):
    """Offline roofline: which bound (MXU flops vs HBM bytes) each op
    sits against, from the trace's own per-op cost stats.

    For each op: achieved = flops*n/ps; bound = min(peak_tflops,
    intensity * peak_hbm_gbs) where intensity = flops/bytes.  An op
    near its bandwidth bound but far from peak flops is HBM-bound —
    no amount of MXU scheduling recovers it.  Prints per-category
    aggregates then the top ops by total time.  Pure parsing — safe
    while a chip session is live.  Peaks: v5e bf16 defaults,
    override via BENCH_PEAK_TFLOPS / BENCH_PEAK_HBM_GBS env.
    """
    peak_tflops = float(os.environ.get("BENCH_PEAK_TFLOPS", peak_tflops))
    peak_hbm_gbs = float(os.environ.get("BENCH_PEAK_HBM_GBS",
                                        peak_hbm_gbs))
    ops = _collect_op_stats(out_dir)
    if not ops:
        print("no per-op cost stats found in trace")
        return
    cats = {}
    for nm, d in ops.items():
        c = cats.setdefault(d["category"] or "uncategorized",
                            [0, 0, 0])
        c[0] += d["ps"]
        c[1] += d["flops"] * d["n"]
        c[2] += d["bytes"] * d["n"]
    tot_ps = sum(c[0] for c in cats.values())
    tot_fl = sum(c[1] for c in cats.values())
    tot_by = sum(c[2] for c in cats.values())
    print(f"trace {out_dir}: {tot_ps/1e12:.3f} s of costed-op time, "
          f"{tot_fl/1e12:.2f} TFLOP, {tot_by/1e9:.2f} GB accessed "
          f"(/{steps} steps: {tot_fl/steps/1e9:.1f} GFLOP, "
          f"{tot_by/steps/1e9:.2f} GB per step)")
    print(f"peaks: {peak_tflops:.0f} TFLOP/s bf16, "
          f"{peak_hbm_gbs:.0f} GB/s HBM "
          f"(ridge {peak_tflops*1e3/peak_hbm_gbs:.0f} FLOP/byte)")
    print(f"\n{'category':<28}{'ms/step':>9}{'TFLOP/s':>9}"
          f"{'GB/s':>8}{'int.':>7}  bound")
    for cat, (ps, fl, by) in sorted(cats.items(), key=lambda kv:
                                    -kv[1][0]):
        if ps == 0:
            continue
        tfs = fl / ps * 1e12 / 1e12 if ps else 0.0
        gbs = by / ps * 1e12 / 1e9 if ps else 0.0
        inten = fl / by if by else float("inf")
        bw_bound = inten * peak_hbm_gbs / 1e3   # TFLOP/s cap from HBM
        bound = ("HBM" if bw_bound < peak_tflops else "MXU")
        util = (gbs / peak_hbm_gbs if bound == "HBM"
                else tfs / peak_tflops)
        print(f"{cat:<28}{ps/1e9/steps:>9.3f}{tfs:>9.1f}{gbs:>8.0f}"
              f"{inten:>7.0f}  {bound} ({100*util:.0f}% of its bound)")
    print(f"\ntop ops by time ({'ms/step':>7}, achieved TFLOP/s, GB/s, "
          "bound):")
    for nm, d in sorted(ops.items(), key=lambda kv: -kv[1]["ps"])[:top]:
        ps, fl, by = d["ps"], d["flops"] * d["n"], d["bytes"] * d["n"]
        tfs = fl / ps * 1e12 / 1e12 if ps else 0.0
        gbs = by / ps * 1e12 / 1e9 if ps else 0.0
        inten = fl / by if by else float("inf")
        bound = ("HBM" if inten * peak_hbm_gbs / 1e3 < peak_tflops
                 else "MXU")
        print(f"  {ps/1e9/steps:7.3f} {tfs:7.1f} {gbs:6.0f} {bound:>4}"
              f"  {nm[:70]}")


def compare(dir_a, dir_b, top=30):
    """Offline A/B diff of two traces (e.g. NCHW vs NHWC): per-op
    self-time for each side and the delta, sorted by |delta|.  Ops are
    matched by name; fusion boundaries can differ between layouts, so
    one side's missing op shows as 0.  Pure parsing — no jax import, so
    it can run from a no-jax shell while the chip session is live."""
    ca, cb = _collect(dir_a), _collect(dir_b)
    if not ca or not cb:
        print(f"missing trace: A={'ok' if ca else 'EMPTY'} "
              f"B={'ok' if cb else 'EMPTY'}")
        return

    def merge(collected):
        # multi-plane (multi-core) traces: the same op name on several
        # cores must SUM, not overwrite
        totals = {}
        for t in collected.values():
            for op, ps in t.items():
                totals[op] = totals.get(op, 0) + ps
        return totals

    ta, tb = merge(ca), merge(cb)
    sum_a, sum_b = sum(ta.values()), sum(tb.values())
    print(f"A: {dir_a} — {sum_a/1e12:.3f} s of events")
    print(f"B: {dir_b} — {sum_b/1e12:.3f} s of events")
    print(f"total delta (B-A): {(sum_b-sum_a)/1e9:+.3f} ms")
    print(f"{'A ms':>10} {'B ms':>10} {'delta ms':>10}  op")
    merged = sorted(set(ta) | set(tb),
                    key=lambda op: -abs(tb.get(op, 0) - ta.get(op, 0)))
    for op in merged[:top]:
        a, b = ta.get(op, 0), tb.get(op, 0)
        print(f"{a/1e9:10.3f} {b/1e9:10.3f} {(b-a)/1e9:+10.3f}  {op[:80]}")


if __name__ == "__main__":
    main()
