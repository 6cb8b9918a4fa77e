"""TPU perf probe: isolate where ResNet-50 MFU goes.

Measures, on the real chip:
  1. bf16 matmul MFU ceiling (what the chip can actually deliver here)
  2. ResNet-50 framework train step: python-loop dispatch vs K steps
     rolled into ONE jit via lax.scan (dispatch overhead isolation)
  3. raw conv stack NCHW vs NHWC (layout cost isolation)

Plus the chip-free byte accountants:
  PROBE=hbm_bytes      — XLA cost-analysis ``bytes accessed`` of the
                         flagship train step, per-op-category table,
                         memory_analysis peaks, committed-budget check
  PROBE=precision_audit — StableHLO dtype census
  PROBE=flash          — committed flash-backward budget table
                         (tools/flash_budgets.json) joined with a live
                         fused-vs-split kernel measurement

Prints one JSON line per experiment.  Sync discipline: device->host value
fetch (see bench.py ``_timed_steps``).

The persistent XLA compile cache is configured from ``__main__`` (NOT at
import — tests import this module for its pure helpers) through the
shared ``utils.compat.configure_persistent_cache``.
"""

import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

PEAK_TFLOPS = 197.0  # TPU v5e bf16 peak (bench._PEAK_TFLOPS)

HBM_BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "hbm_budgets.json")
AUTOTUNE_PLAN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "autotune_plan.json")


def sync(x):
    jax.tree.leaves(x)[0].block_until_ready()
    # real sync: fetch one scalar
    return float(jnp.asarray(jax.tree.leaves(x)[0]).ravel()[0])


def timeit(fn, *args, trials=3, reps=1):
    """Best-of-`trials` wall time of `fn(*args)`, amortized over `reps`
    enqueued calls per sync.  reps=1 includes one full dispatch+fetch
    round-trip in EVERY sample — fine for multi-second workloads, but it
    swamps fast kernels.  Use reps >> 1 for anything faster than ~1 s;
    device execution is FIFO, so syncing the last output bounds all
    enqueued work."""
    fn(*args)  # compile
    sync(fn(*args))
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        sync(out)
        dt = (time.perf_counter() - t0) / reps
        best = dt if best is None else min(best, dt)
    return best


def probe_matmul():
    n = 8192
    reps = 20
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def f(a, b):
        def body(c, _):
            c = (a @ c).astype(jnp.bfloat16)
            return c, ()
        c, _ = lax.scan(body, b, None, length=reps)
        return c

    dt = timeit(f, a, b)
    flops = 2 * n**3 * reps
    tf = flops / dt / 1e12
    print(json.dumps({"probe": "matmul_bf16_8192", "tflops": round(tf, 1),
                      "mfu": round(tf / PEAK_TFLOPS, 3)}))


def probe_conv(layout):
    bs, c, hw = 256, 256, 56
    k = 256
    reps = 30
    if layout == "NCHW":
        x = jnp.ones((bs, c, hw, hw), jnp.bfloat16)
        w = jnp.ones((k, c, 3, 3), jnp.bfloat16)
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        x = jnp.ones((bs, hw, hw, c), jnp.bfloat16)
        w = jnp.ones((3, 3, c, k), jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")

    @jax.jit
    def f(x, w):
        def body(y, _):
            y = lax.conv_general_dilated(y, w, (1, 1), "SAME",
                                         dimension_numbers=dn)
            return y.astype(jnp.bfloat16), ()
        y, _ = lax.scan(body, x, None, length=reps)
        return y

    dt = timeit(f, x, w)
    flops = 2 * bs * hw * hw * k * c * 9 * reps
    tf = flops / dt / 1e12
    print(json.dumps({"probe": f"conv3x3_{layout}", "tflops": round(tf, 1),
                      "mfu": round(tf / PEAK_TFLOPS, 3)}))


def probe_resnet(scan_steps):
    import chainermn_tpu as ct
    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.core.optimizer import (MomentumSGD,
                                              apply_transform_update,
                                              make_loss_and_grad)
    from chainermn_tpu.models import Classifier, ResNet50

    bs = int(os.environ.get("PROBE_BS", "256"))
    model = Classifier(ResNet50(n_classes=1000, compute_dtype=jnp.bfloat16,
                                seed=0))
    opt = MomentumSGD(lr=0.1, momentum=0.9).setup(model)
    state = extract_state(model)
    params, pstate = state["params"], state["state"]
    opt_state = opt._ensure_opt_state(params)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (bs, 3, 224, 224)).astype(np.float32))
    t = jnp.asarray(rng.randint(0, 1000, bs).astype(np.int32))
    tx = opt._transform()
    loss_and_grad = make_loss_and_grad(model, model)
    key = jax.random.PRNGKey(0)

    def one_step(carry, _):
        params, pstate, opt_state = carry
        loss, new_pstate, obs, grads = loss_and_grad(
            params, pstate, key, (x, t), {})
        new_params, new_opt_state = apply_transform_update(
            tx, grads, opt_state, params, jnp.float32(0.1), 0.0)
        return (new_params, new_pstate, new_opt_state), loss

    @jax.jit
    def k_steps(params, pstate, opt_state):
        (p, s, o), losses = lax.scan(one_step, (params, pstate, opt_state),
                                     None, length=scan_steps)
        return losses[-1]

    t0 = time.perf_counter()
    out = k_steps(params, pstate, opt_state)
    sync(out)
    compile_s = time.perf_counter() - t0

    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = k_steps(params, pstate, opt_state)
        sync(out)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    step_t = best / scan_steps
    ips = bs / step_t
    mfu = ips * 12.3e9 / (PEAK_TFLOPS * 1e12)
    print(json.dumps({"probe": f"resnet50_scan{scan_steps}", "bs": bs,
                      "images_per_sec": round(ips, 1),
                      "step_ms": round(step_t * 1e3, 1),
                      "mfu": round(mfu, 3),
                      "compile_s": round(compile_s, 1)}))


def probe_prefetch_overhead():
    """Host-side: DevicePrefetchIterator's per-fill consumer-position
    snapshot at ImageNet-scale order arrays.  VERDICT r3 Weak #5 feared
    a ~10 MB ``_order`` copy per batch; MEASURED RESULT: the snapshot
    serializer stores ndarrays by reference (DictionarySerializer →
    ``to_numpy`` aliases, and ``np.asarray(self._order)`` is a no-copy
    view), so the snapshot is ~50 µs of scalar/RNG bookkeeping with NO
    O(dataset) copy.  Recorded so the claim stays measured, not assumed.
    CPU-safe."""
    from chainermn_tpu.dataset import (DevicePrefetchIterator,
                                       SerialIterator, concat_examples)

    class TinyItems:
        def __len__(self):
            return 1281167

        def __getitem__(self, i):
            return ITEM

    ITEM = (np.zeros(8, np.float32), 0)
    n_batches = int(os.environ.get("PROBE_BATCHES", "50"))
    base = SerialIterator(TinyItems(), 256, shuffle=True, seed=0)
    it = DevicePrefetchIterator(base, size=2, converter=concat_examples)
    it.next()  # warm the pipeline (fills + first device_put)
    t0 = time.perf_counter()
    for _ in range(n_batches):
        it.next()
    per_batch_s = (time.perf_counter() - t0) / n_batches
    # the snapshot alone, isolated (the piece r3 feared was a 10 MB copy)
    t0 = time.perf_counter()
    for _ in range(200):
        it._snap(base)
    snap_s = (time.perf_counter() - t0) / 200
    order_mb = base._order.nbytes / 1e6
    print(json.dumps({
        "probe": "device_prefetch_host_overhead",
        "dataset_len": 1281167, "batch_size": 256,
        "order_array_mb": round(order_mb, 1),
        "per_batch_ms_total": round(per_batch_s * 1e3, 3),
        "per_fill_snapshot_ms": round(snap_s * 1e3, 3),
        "note": "serializer aliases _order (no O(dataset) copy/batch)"}))


def probe_input_pipeline():
    """Host input-pipeline bandwidth at flagship scale (VERDICT r4 Weak
    #6) — no chip needed.  Can the native gather engine assemble
    224²×bs-256 ImageNet batches faster than the chip consumes them on
    this host?  Demand side: r2 measured 2022 img/s (7.9 batches/s);
    the 25-30% MFU target needs ~4-5k img/s (15.6-19.5 batches/s).

    Measured per mode:
      * uint8 gather (the TPU-idiomatic pipeline: ship uint8, cast to
        bf16 on device — 38.5 MB/batch host traffic)
      * uint8 gather + host float32 cast (the reference's CPU-side
        ``concat_examples`` convention — 154 MB/batch more host writes)
      * zero_copy ring hand-off (DLPack aliasing the C++ ring slot)
    """
    from chainermn_tpu.dataset import NativeBatchIterator, TupleDataset

    n_img = int(os.environ.get("PROBE_N_IMG", "2048"))
    bs = int(os.environ.get("PROBE_BS", "256"))
    n_batches = int(os.environ.get("PROBE_BATCHES", "40"))
    rng = np.random.RandomState(0)
    # dtype-direct draw: no 8x transient int64 intermediate, full range
    x = rng.randint(0, 256, (n_img, 224, 224, 3), dtype=np.uint8)
    t = rng.randint(0, 1000, n_img).astype(np.int32)
    batch_mb = bs * x[0].nbytes / 1e6
    demand_r2 = 2022.0 / bs
    demand_mfu = 4500.0 / bs

    def run(tag, zero_copy, cast_f32):
        it = NativeBatchIterator(TupleDataset(x, t), bs, shuffle=True,
                                 seed=0, n_prefetch=2,
                                 n_threads=max(1, os.cpu_count() or 1),
                                 zero_copy=zero_copy)
        try:
            for _ in range(4):  # warm the ring
                it.next()
            t0 = time.perf_counter()
            for _ in range(n_batches):
                xb, tb = it.next()
                if cast_f32:
                    xb = np.asarray(xb).astype(np.float32)
                # touch one element so a lazy view cannot cheat the timer
                _ = xb.reshape(-1)[0] if hasattr(xb, "reshape") else xb
            dt = (time.perf_counter() - t0) / n_batches
        finally:
            it.finalize()
        bps = 1.0 / dt
        print(json.dumps({
            "probe": "input_pipeline", "mode": tag, "batch_size": bs,
            "image_mb_per_batch": round(batch_mb, 1),
            "batches_per_sec": round(bps, 2),
            "images_per_sec": round(bps * bs, 0),
            "gather_mb_per_sec": round(bps * batch_mb, 0),
            "margin_vs_r2_throughput": round(bps / demand_r2, 2),
            "margin_vs_mfu_target_4500ips": round(bps / demand_mfu, 2),
        }), flush=True)

    run("uint8_gather", zero_copy=False, cast_f32=False)
    run("uint8_gather_f32cast", zero_copy=False, cast_f32=True)
    run("uint8_zero_copy", zero_copy=True, cast_f32=False)


# ---------------------------------------------------------------------------
# PROBE=hbm_bytes — the byte accountant behind the committed HBM budgets
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
                "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1}
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_OP_RE = re.compile(r"=\s+(?:\"?stablehlo\.)([a-z_0-9]+)")
_OPERAND_RE = re.compile(r"%[A-Za-z0-9_#]+")

#: op → reported category.  Everything unlisted is "elementwise" (the
#: compare/select/add chains XLA fuses) except the data-movement set.
_OP_CATEGORY = {
    "convolution": "conv",
    "dot_general": "matmul", "dot": "matmul",
    "reduce_window": "pooling",
    "select_and_scatter": "pooling_bwd",
    "reduce": "reduce",
    "gather": "gather_scatter", "scatter": "gather_scatter",
    "dynamic_gather": "gather_scatter",
}
_DATA_MOVEMENT = {"transpose", "reshape", "broadcast_in_dim", "pad",
                  "slice", "dynamic_slice", "dynamic_update_slice",
                  "concatenate", "convert", "reverse", "iota", "copy"}


def _tensor_bytes(token):
    """Byte size of one ``tensor<4x8xbf16>`` type token (0 when a dim is
    dynamic or the dtype is exotic — conservative under-count)."""
    parts = token.split("x")
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 0
        n *= int(d)
    return n * _DTYPE_BYTES.get(parts[-1], 0)


def stablehlo_bytes_by_category(text):
    """Per-op-category ``bytes accessed`` table of a LOWERED (backend-
    neutral StableHLO) module: each op contributes its operand + result
    tensor bytes, grouped by category.

    Deliberately measured on the unoptimized program: it is a property
    of what the framework EMITS, identical on every backend and stable
    across XLA fusion-heuristic changes — the right basis for a
    regression budget (the optimized module's accounting is
    backend-specific: CPU wraps fusions in opaque ``call`` ops).  The
    numbers over-count what a fused backend actually moves; deltas
    between revisions are the signal.
    """
    cats = {}
    region_stack = []  # region ops (reduce_window, scatter, ...) whose
    # `(tensor<..>) -> tensor<..>` signature trails the closing `})`
    for line in text.splitlines():
        stripped = line.lstrip()
        if stripped.startswith("})") and region_stack:
            op = region_stack.pop()
            if "->" in line and op is not None:
                nbytes = sum(_tensor_bytes(t)
                             for t in _TENSOR_RE.findall(line))
                cat = _OP_CATEGORY.get(op)
                if cat is None:
                    cat = ("data_movement" if op in _DATA_MOVEMENT
                           else "elementwise")
                cats[cat] = cats.get(cat, 0) + nbytes
            continue
        mo = _OP_RE.search(line)
        if not mo:
            if stripped.rstrip().endswith("({"):
                region_stack.append(None)  # anonymous region (while, ...)
            continue
        op = mo.group(1)
        if line.rstrip().endswith("({"):
            # multi-line region form: signature comes with the `})` line
            region_stack.append(
                None if op in ("while", "case", "if", "map") else op)
            continue
        if op in ("constant", "return", "while", "case", "if"):
            continue
        tokens = _TENSOR_RE.findall(line)
        if not tokens:
            continue
        if "->" in line:
            nbytes = sum(_tensor_bytes(t) for t in tokens)
        else:
            # elementwise form `%r = stablehlo.add %a, %b : tensor<T>`:
            # one shared type, operands + result accesses
            head = line.split(":", 1)[0]
            head = head.split("=", 1)[1] if "=" in head else head
            n_operands = len(_OPERAND_RE.findall(head))
            nbytes = _tensor_bytes(tokens[0]) * (n_operands + 1)
        cat = _OP_CATEGORY.get(op)
        if cat is None:
            cat = "data_movement" if op in _DATA_MOVEMENT else "elementwise"
        cats[cat] = cats.get(cat, 0) + nbytes
    return cats


def hbm_budget_key(bs, size, layout):
    return f"resnet50_bs{bs}_size{size}_{layout.lower()}_bf16_train"


def load_hbm_budgets(path=None):
    try:
        with open(path or HBM_BUDGETS_PATH) as f:
            return json.load(f)
    except Exception:
        return {}


def measure_hbm_bytes(bs, size, layout="NHWC", donate=True,
                      do_compile=False):
    """Byte accounting of the flagship-shaped ResNet-50 train step.

    Returns a dict with the headline ``bytes_accessed`` (XLA
    HloCostAnalysis over the LOWERED module — see
    :func:`stablehlo_bytes_by_category` for why the unoptimized program
    is the budget basis), the per-category table, and — with
    ``do_compile`` — the optimized-module cost analysis plus
    ``memory_analysis`` peaks (argument/output/temp/alias bytes; alias
    proves params + opt-state donation).  CPU-safe: lowering never
    executes the program; only ``do_compile`` invokes backend codegen.
    """
    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.core.optimizer import (MomentumSGD,
                                              apply_transform_update,
                                              make_loss_and_grad)
    from chainermn_tpu.models import Classifier, ResNet50

    model = Classifier(ResNet50(n_classes=1000, compute_dtype=jnp.bfloat16,
                                seed=0, layout=layout))
    opt = MomentumSGD(lr=0.1, momentum=0.9).setup(model)
    state = extract_state(model)
    params, pstate = state["params"], state["state"]
    opt_state = opt._ensure_opt_state(params)
    tx = opt._transform()
    loss_and_grad = make_loss_and_grad(model, model)
    key = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)
    shape = (bs, size, size, 3) if layout == "NHWC" else (bs, 3, size, size)
    x = jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
    t = jnp.asarray(rng.randint(0, 1000, bs).astype(np.int32))

    def step(params, pstate, opt_state, x, t):
        loss, new_pstate, obs, grads = loss_and_grad(
            params, pstate, key, (x, t), {})
        new_params, new_opt_state = apply_transform_update(
            tx, grads, opt_state, params, jnp.float32(0.1), 0.0)
        return loss, new_params, new_pstate, new_opt_state

    donate_argnums = (0, 2) if donate else ()
    lowered = jax.jit(step, donate_argnums=donate_argnums).lower(
        params, pstate, opt_state, x, t)
    ca = lowered.cost_analysis() or {}
    cats = stablehlo_bytes_by_category(lowered.as_text())
    out = {
        "config": hbm_budget_key(bs, size, layout),
        "bs": bs, "image_size": size, "layout": layout, "donated": donate,
        "bytes_accessed": int(ca.get("bytes accessed", 0)),
        "flops": int(ca.get("flops", 0)),
        "bytes_by_category": {k: int(v) for k, v in
                              sorted(cats.items(), key=lambda kv: -kv[1])},
    }
    if do_compile:
        from chainermn_tpu.core.optimizer import memory_stats_dict
        compiled = lowered.compile()
        cca = compiled.cost_analysis()
        if not isinstance(cca, dict):  # some jax versions: list per device
            cca = cca[0] if cca else {}
        out["optimized_bytes_accessed"] = int(cca.get("bytes accessed", 0))
        stats = memory_stats_dict(compiled.memory_analysis())
        if stats is not None:
            out["memory_analysis"] = stats
    return out


def probe_hbm_bytes():
    """PROBE=hbm_bytes: the flagship step's byte bill, checked against
    the committed budget (tools/hbm_budgets.json).  Chip-free by design
    — pin the CPU backend like the precision audit does (the lowering is
    backend-neutral; only param init executes eagerly)."""
    try:
        jax.config.update("jax_platforms",
                          os.environ.get("PROBE_PLATFORM") or "cpu")
    except Exception:
        pass  # backend already initialized: caller chose the platform
    bs = int(os.environ.get("PROBE_BS", "64"))
    size = int(os.environ.get("PROBE_SIZE", "224"))
    layout = os.environ.get("PROBE_LAYOUT", "NHWC")
    donate = os.environ.get("PROBE_DONATE", "1") == "1"
    do_compile = os.environ.get("PROBE_COMPILE", "1") == "1"
    row = measure_hbm_bytes(bs, size, layout, donate=donate,
                            do_compile=do_compile)
    row["probe"] = "hbm_bytes"
    budgets = load_hbm_budgets()
    entry = budgets.get(row["config"])
    if entry:
        row["budget_bytes_accessed"] = entry["budget_bytes_accessed"]
        row["within_budget"] = \
            row["bytes_accessed"] <= entry["budget_bytes_accessed"]
        pre = entry.get("pre_pr_bytes_accessed")
        if pre:
            row["reduction_vs_pre_pr_pct"] = round(
                100.0 * (1.0 - row["bytes_accessed"] / pre), 1)
    print(json.dumps(row), flush=True)
    return row


def classify_contractions(text, op):
    """Count ``stablehlo.<op>`` lines by input→result dtype.  bf16
    inputs with an f32 result are the CORRECT MXU configuration (bf16
    multiply, f32 accumulate via preferred_element_type); only
    f32-INPUT contractions forgo the bf16 MXU path."""
    import re
    counts = {}
    for line in text.splitlines():
        if f"stablehlo.{op}" not in line:
            continue
        ins = re.search(
            r":\s*\(tensor<[^>]*?(bf16|f16|f32|f64)>,\s*"
            r"tensor<[^>]*?(bf16|f16|f32|f64)>\)", line)
        out = re.search(r"->\s*tensor<[^>]*?(bf16|f16|f32|f64)>", line)
        key = (f"{'x'.join(sorted(set(ins.groups())))}"
               f"->{out.group(1)}" if ins and out else "unparsed")
        counts[key] = counts.get(key, 0) + 1
    return counts


def probe_precision_audit():
    """Static StableHLO dtype audit of the compiled train steps,
    committed as reproducible tooling for both verticals.
    CPU-safe: the step is LOWERED (traced to StableHLO), never executed,
    so no chip is touched.  Counts conv / dot_general result
    dtypes: the conv/matmul path must be bf16-pure (MXU-eligible) with
    f32 confined to the loss head and statistics, and f64 must not
    appear anywhere."""
    # Self-pinning: param init / jnp.asarray below DO execute eagerly on
    # the default backend, which would open the chip.  The audit lowers
    # the CPU program by design (the attention_path caveat documents the
    # one divergence), so pin cpu here rather than trusting the caller
    # to pass PROBE_PLATFORM.
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized: caller chose the platform
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "precision_audit must run on the cpu backend (got "
            f"{jax.default_backend()!r}); run it in a fresh process")
    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.core.optimizer import (Adam, MomentumSGD,
                                              apply_transform_update,
                                              make_loss_and_grad)
    from chainermn_tpu.models import Classifier, ResNet50, TransformerLM

    def audit(tag, model, opt, args):
        state = extract_state(model)
        params, pstate = state["params"], state["state"]
        opt_state = opt._ensure_opt_state(params)
        tx = opt._transform()
        loss_and_grad = make_loss_and_grad(model, model)
        key = jax.random.PRNGKey(0)

        def step(params, pstate, opt_state):
            loss, new_pstate, obs, grads = loss_and_grad(
                params, pstate, key, args, {})
            new_params, new_opt_state = apply_transform_update(
                tx, grads, opt_state, params, jnp.float32(0.1), 0.0)
            return loss, new_params, new_pstate, new_opt_state

        text = jax.jit(step).lower(params, pstate, opt_state).as_text()
        for op in ("convolution", "dot_general"):
            counts = classify_contractions(text, op)
            row = {"probe": "precision_audit", "model": tag, "op": op}
            row.update(sorted(counts.items()))
            row["f64_free"] = "f64" not in text
            if tag.startswith("transformer") and \
                    jax.default_backend() != "tpu":
                # ops.attention dispatches to the Pallas flash kernels
                # on TPU (in-kernel dtype discipline); a CPU lowering
                # audits the xla_attention FALLBACK, whose backward
                # carries f32-input score-grad dots the TPU program
                # does not have
                row["attention_path"] = "xla_fallback (cpu lowering)"
            print(json.dumps(row), flush=True)

    rng = np.random.RandomState(0)
    bs = int(os.environ.get("PROBE_BS", "8"))
    model = Classifier(ResNet50(n_classes=1000,
                                compute_dtype=jnp.bfloat16, seed=0,
                                layout="NHWC"))
    x = jnp.asarray(rng.normal(0, 1, (bs, 224, 224, 3))
                    .astype(np.float32))
    t = jnp.asarray(rng.randint(0, 1000, bs).astype(np.int32))
    audit("resnet50_nhwc_bf16", model,
          MomentumSGD(lr=0.1, momentum=0.9).setup(model), (x, t))

    seq = int(os.environ.get("PROBE_SEQ", "256"))
    lm = TransformerLM(n_vocab=50257, d_model=768, n_heads=12,
                       n_layers=12, max_len=seq, seed=0,
                       compute_dtype=jnp.bfloat16)
    ids = jnp.asarray(rng.randint(0, 50257, (2, seq)).astype(np.int32))
    tgt = jnp.asarray(np.roll(np.asarray(ids), -1, axis=1))
    audit("transformer_lm_bf16", lm, Adam(alpha=3e-4).setup(lm),
          (ids, tgt))


def probe_comm():
    """PROBE=comm: the committed gradient-exchange budgets
    (tools/comm_budgets.json) joined with a LIVE census — one row per
    config (collective counts + exchanged-bytes accounting + structure
    verdict) and the live per-bucket table of the bucketed exchange
    (bucket index, leaf count, bytes, dtype).  Chip-free by design: the
    census is a trace property, so this runs on the simulated CPU mesh
    (like probe_hbm_bytes)."""
    # pin the 8-device simulated mesh BEFORE the backend initializes —
    # without it a direct invocation traces a 1-device mesh where every
    # exchanged-bytes field is 0 and every config reads as structure
    # drift (same pin comm_census.main applies for the CLI)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    import comm_census
    from chainermn_tpu.communicators._memory_utility import (
        DEFAULT_BUCKET_MB, bucket_table)
    if jax.device_count() < 8:
        raise SystemExit(
            "probe_comm: the jax backend initialized before the 8-device "
            "pin took effect (device_count="
            f"{jax.device_count()}); run via `make probe-comm` or set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    budgets = comm_census.load_budgets()
    for name in comm_census.CONFIGS:
        row = comm_census.config_row(name)
        row["probe"] = "comm"
        row["config"] = name
        committed = dict(budgets["structure"].get(name, {}))
        committed.pop("config", None)
        live = {k: v for k, v in row.items()
                if k not in ("probe", "config")}
        row["within_structure"] = live == committed
        print(json.dumps(row), flush=True)
    # per-hop table of the hierarchical/striped configs (ISSUE 6 + 11):
    # one row per (config, path, hop, collective) with the wire bytes
    # and dtype — read straight off the traced eqns via the SAME
    # row_hop/row_path/row_wire_bytes helpers config_row prices the
    # committed budgets with (one copy; the two surfaces cannot drift)
    for name, cfg in comm_census.CONFIGS.items():
        if cfg.get("comm") != "hierarchical":
            continue
        jaxpr, comm = comm_census.trace_step(
            exchange=cfg["exchange"],
            batch_collectives=cfg["batch_collectives"],
            grad_dtype=cfg["grad_dtype"],
            comm_name=cfg["comm"], inter_size=cfg.get("inter_size"),
            stripe_ratio=cfg.get("stripe_ratio"))
        rows = [r for r in comm_census.collective_census(jaxpr)
                if r["elems"] >= comm_census.GRAD_ELEMS_FLOOR]
        groups = {}
        for r in rows:
            # path (ISSUE 11 satellite column): which slice's exchange
            # the collective implements — "hier" on single-path
            # configs, "ici"/"dcn" on the striped allreduce ones (the
            # striped_rs chains are path-ambiguous by (prim, hop) and
            # label as prim@hop)
            key = (comm_census.row_path(r, comm),
                   comm_census.row_hop(r, comm), r["prim"], r["dtype"])
            g = groups.setdefault(key, {"count": 0, "elems": 0,
                                        "bytes": 0})
            g["count"] += 1
            g["elems"] += r["elems"]
            g["bytes"] += int(comm_census.row_wire_bytes(r, comm))
        for (path, hop, prim, dtype), g in groups.items():
            # wire_dtype: the dtype actually on the wire (== the
            # operand dtype the census priced); compression_ratio: its
            # itemsize over f32 — 0.25 for the int8/fp8 crossings, 0.5
            # for bf16, 1.0 lossless (ISSUE 8 satellite column)
            print(json.dumps({"probe": "comm_hop_table", "config": name,
                              "path": path, "hop": hop,
                              "collective": prim,
                              "dtype": dtype, "wire_dtype": dtype,
                              "compression_ratio":
                                  jnp.dtype(dtype).itemsize / 4.0,
                              **g}), flush=True)
    # MoE dispatch census (ISSUE 12): the committed moe section joined
    # with a live trace — one row per config (two-stage structure,
    # off_host_dispatch_ratio, structure verdict) and the all_to_all
    # dispatch rows of the per-hop table, priced by the SAME
    # row_hop/row_wire_bytes helpers as the gradient rows
    moe_committed = budgets.get("moe", {}).get("structure", {})
    for name in comm_census.MOE_CONFIGS:
        jaxpr, comm = comm_census.trace_moe(name)
        row = comm_census.moe_config_row(name, traced=(jaxpr, comm))
        committed = dict(moe_committed.get(name, {}))
        committed.pop("config", None)
        print(json.dumps(dict(row, probe="comm_moe", config=name,
                              within_structure=row == committed)),
              flush=True)
        rows = [r for r in comm_census.collective_census(jaxpr)
                if r["elems"] >= comm_census.GRAD_ELEMS_FLOOR]
        groups = {}
        for r in rows:
            key = (comm_census.row_hop(r, comm), r["prim"], r["dtype"])
            g = groups.setdefault(key, {"count": 0, "elems": 0,
                                        "bytes": 0})
            g["count"] += 1
            g["elems"] += r["elems"]
            g["bytes"] += int(comm_census.row_wire_bytes(r, comm))
        for (hop, prim, dtype), g in groups.items():
            print(json.dumps({"probe": "comm_hop_table", "config": name,
                              "path": "moe_dispatch", "hop": hop,
                              "collective": prim,
                              "dtype": dtype, "wire_dtype": dtype,
                              "compression_ratio":
                                  jnp.dtype(dtype).itemsize / 4.0,
                              **g}), flush=True)
    # live per-bucket table at the default bound (and PROBE_BUCKET_MB
    # override), leaf by leaf.  grad_transform plans buckets over the
    # POST-compression leaves, so the plan depends on the grad dtype:
    # emit one table per flavor (uncompressed params dtype + the
    # flagship's bf16 compression), each row labeled with grad_dtype.
    bucket_mb = float(os.environ.get("PROBE_BUCKET_MB",
                                     str(DEFAULT_BUCKET_MB)))
    vert = comm_census._Vertical.get()
    from chainermn_tpu.communicators import MeshCommunicator
    shapes, dts = MeshCommunicator.grad_leaf_specs(vert.model)
    param_dtypes = [str(d) for d in dts]
    for grad_dtype in (None, "bfloat16"):
        dtypes = param_dtypes if grad_dtype is None \
            else [grad_dtype] * len(shapes)
        for trow in bucket_table(shapes, dtypes,
                                 int(bucket_mb * 2 ** 20)):
            print(json.dumps(dict(trow, probe="comm_bucket_table",
                                  grad_dtype=grad_dtype,
                                  bucket_mb=bucket_mb)), flush=True)


def probe_autotune():
    """PROBE=autotune: the committed self-tuning plan artifact
    (tools/autotune_plan.json, gated tier-1 by
    tests/test_autotune_plan.py) joined with a LIVE startup micro-bench
    + derivation on the simulated 8-device mesh (ISSUE 19).  Emits:

    * one ``autotune_fabric`` row per measured hop (bandwidth, latency,
      probe size) — cpu-sim numbers, labeled as mechanics-only: they
      are NEVER stamped into the artifact (that takes a run on the
      real fabric);
    * the derived plan (fingerprint, bucket_mb, stripe_ratio,
      grad_dtype, derivation notes) with the artifact join: does the
      committed derivation record still track the planner's constants,
      and — once status is ``measured`` — the committed fingerprint;
    * one ``autotune_knob`` row per knob after :meth:`retuned` applies
      the plan to a free-knobbed hierarchical communicator — plan
      value, hand-set flag, applied value — the provenance table
      docs/performance.md §12 describes.

    Chip-free: the micro-bench runs on the simulated mesh."""
    # pin the 8-device simulated mesh BEFORE the backend initializes
    # (same pin as probe_comm — a 1-device mesh has no DCN hop to probe)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))
    import chainermn_tpu as ct
    from chainermn_tpu.communicators import _autotune
    if jax.device_count() < 8:
        raise SystemExit(
            "probe_autotune: the jax backend initialized before the "
            "8-device pin took effect (device_count="
            f"{jax.device_count()}); run via `make probe-autotune` or "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8")
    with open(AUTOTUNE_PLAN_PATH) as f:
        art = json.load(f)
    comm = ct.create_communicator("hierarchical", inter_size=2)
    probe_mb = float(os.environ.get("PROBE_MB", "1.0"))
    m = _autotune.measure_fabric(
        comm, probe_mb=probe_mb,
        iters=int(os.environ.get("PROBE_ITERS", "4")))
    for hop, h in sorted(m["hops"].items()):
        print(json.dumps({
            "probe": "autotune_fabric", "hop": hop, **h,
            "probe_mb": probe_mb,
            "note": "cpu-sim fabric: mechanics only, never stamped "
                    "into tools/autotune_plan.json"}), flush=True)
    plan = _autotune.agree_exchange_plan(comm, m)
    row = {"probe": "autotune", "fingerprint": plan["fingerprint"],
           "bucket_mb": plan["bucket_mb"],
           "stripe_ratio": plan["stripe_ratio"],
           "grad_dtype": plan["grad_dtype"],
           "notes": plan["derivation"]["notes"],
           "artifact_status": art["status"],
           "derivation_tracks_planner":
               art["plan_version"] == _autotune.PLAN_VERSION
               and art["derivation"]["overhead_frac"]
               == _autotune.OVERHEAD_FRAC
               and art["derivation"]["formula"]
               == plan["derivation"]["formula"]
               and art["derivation"]["bucket_rule"]
               == plan["derivation"]["bucket_rule"]}
    if art["status"] == "measured" and art.get("plan"):
        row["committed_fingerprint"] = art["plan"]["fingerprint"]
        row["committed_delta_vs_hand"] = art["steps_per_sec_delta_vs_hand"]
    print(json.dumps(row), flush=True)
    tuned = comm.retuned(plan)
    for knob, plan_val, applied in (
            ("bucket_mb", plan["bucket_mb"], tuned.bucket_mb),
            ("stripe_ratio", plan["stripe_ratio"], tuned.stripe_ratio),
            ("grad_dtype", plan["grad_dtype"],
             {"ici": str(jnp.dtype(tuned.allreduce_grad_dtype))
              if tuned.allreduce_grad_dtype is not None else None,
              "dcn": str(jnp.dtype(tuned.dcn_grad_dtype))
              if tuned.dcn_grad_dtype is not None else None})):
        print(json.dumps({
            "probe": "autotune_knob", "knob": knob,
            "plan_value": plan_val,
            "hand_set": bool(tuned._hand_knobs.get(knob)),
            "applied_value": applied}), flush=True)


def probe_serving():
    """PROBE=serving: the committed serving budgets
    (tools/serving_budgets.json, gated tier-1 by
    tests/test_serving_budget.py) joined with a LIVE decode/prefill
    census, plus the per-phase table: for each phase one row of
    structure facts and the decode roofline's byte accounting (bytes
    the step must read from the KV pool per generated token at the
    committed geometry — the number docs/serving.md §"decode roofline"
    derives).  Trace property — chip-free."""
    import serving_census

    budgets = serving_census.load_budgets()
    live = serving_census.structure()
    for phase, facts in live.items():
        committed = budgets["structure"].get(phase, {})
        print(json.dumps({"probe": "serving", "phase": phase, **facts,
                          "within_structure": facts == committed}),
              flush=True)
    g = budgets["geometry"]
    H, D = g["n_heads"], g["d_model"] // g["n_heads"]
    kv_itemsize = 2  # bf16 pages (the engine default; PR 3 discipline)
    for phase, per_tok in (
            # decode reads the whole context's K+V once per token
            ("decode", 2 * g["n_layers"] * g["max_context"] * H * D
             * kv_itemsize),
            # prefill writes each position's K+V exactly once
            ("prefill", 2 * g["n_layers"] * H * D * kv_itemsize),
            # a prefix-hit suffix token reads the whole context's K+V
            # once (decode's shape) instead of recomputing the matched
            # prefix — the byte cost of the FLOPs the hit saves
            ("prefix_prefill", 2 * g["n_layers"] * g["max_context"]
             * H * D * kv_itemsize)):
        print(json.dumps({
            "probe": "serving_phase_table", "phase": phase,
            "kv_bytes_per_token_at_max_context": per_tok,
            "page_kv_bytes": 2 * g["page_size"] * H * D * kv_itemsize,
            "pool_kv_bytes": 2 * g["n_layers"] * g["num_pages"]
            * g["page_size"] * H * D * kv_itemsize,
            "targets_status": budgets["targets"]["status"]}), flush=True)

    # -- fleet table (ISSUE 15): a tiny live 2-replica fleet, one
    # replica preempted mid-load — one row per replica seat showing the
    # router's view (live, queue depth) and the reroute counters the
    # chaos gate pins.  Chip-free like the rest of the probe.
    import numpy as np

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.serving import ReplicaFleet, Request, ServingEngine

    def _engine(_rid):
        model = TransformerLM(n_vocab=97, d_model=32, n_heads=1,
                              n_layers=1, max_len=32, seed=0)
        return ServingEngine(model, num_pages=32, page_size=16,
                             max_batch=2, max_context=32,
                             prefix_cache=False)

    fleet = ReplicaFleet(engine_factory=_engine, replicas=2)
    rng = np.random.RandomState(0)
    for i in range(6):
        fleet.submit(Request(rng.randint(1, 97, 6).astype(np.int32), 3,
                             tenant=f"t{i % 2}", arrival_time=0.0))
    fleet.step(now=1.0)
    fleet.preempt(1)
    fleet.drain(now=2.0)
    for rid in sorted(fleet.replicas):
        rep = fleet.replicas[rid]
        print(json.dumps({
            "probe": "serving_fleet", "replica": rid,
            "live": rep.live, "queue_depth": rep.queue_depth(),
            "routed": fleet.router.by_replica.get(rid, 0),
            "reroutes": fleet.reroutes,
            "completed": len(fleet.completed),
            "epoch": fleet.view.epoch, "role": fleet.view.role}),
            flush=True)


def probe_obs():
    """PROBE=obs: the runtime observability join (ISSUE 14).

    Runs a tiny SEEDED 3-step trainer and one serving request with the
    span tracer forced on (``events`` unless the env already asks for
    ``full``), then emits one JSON row per surface:

    * the exported Chrome-trace shard's event count + span-name census,
      schema-validated (the same ``validate_events`` the tier-1 gate
      runs) and round-tripped through ``tools/trace_merge.py``;
    * the MERGED metrics registry — every rank's shard folded over the
      object collectives (one loopback rank here; the pod workflow is
      identical) — rendered in Prometheus text exposition format.

    Chip-free: everything here is host bookkeeping plus two tiny CPU
    jit programs."""
    import tempfile

    import trace_merge
    from chainermn_tpu import observability as obs

    prev = obs.set_mode("events")
    obs.reset_tracer()
    obs.reset_registry()
    try:
        import chainermn_tpu as ct
        from chainermn_tpu.core.optimizer import MomentumSGD
        from chainermn_tpu.dataset import SerialIterator, TupleDataset
        from chainermn_tpu.models import MLP, Classifier, TransformerLM
        from chainermn_tpu.serving import Request, ServingEngine
        from chainermn_tpu.training import StandardUpdater, Trainer

        rng = np.random.RandomState(0)
        x = rng.normal(0, 1, (32, 12)).astype(np.float32)
        t = rng.randint(0, 3, 32).astype(np.int32)
        comm = ct.create_communicator("flat")
        model = Classifier(MLP(n_units=16, n_out=3, seed=0))
        opt = ct.create_multi_node_optimizer(
            MomentumSGD(lr=0.05), comm).setup(model)
        it = SerialIterator(TupleDataset(x, t), 8, shuffle=False)
        with tempfile.TemporaryDirectory() as tmp:
            Trainer(StandardUpdater(it, opt), (3, "iteration"),
                    out=tmp).run()

            lm = TransformerLM(n_vocab=64, d_model=32, n_heads=2,
                               n_layers=1, max_len=64, seed=0)
            eng = ServingEngine(lm, num_pages=16, page_size=8,
                                max_batch=2, max_context=32,
                                prefix_cache=False)
            eng.submit(Request(rng.randint(0, 64, 6), max_new_tokens=3,
                               arrival_time=0.0))
            step = 0
            while eng.running or eng.scheduler.pending():
                eng.step(now=float(step))
                step += 1

            shard = os.path.join(tmp, "trace-rank0.jsonl")
            n = obs.tracer().export(shard)
            merged_path = os.path.join(tmp, "merged.json")
            merged = trace_merge.merge_files([shard], merged_path)
            names = {}
            for ev in merged:
                if ev.get("ph") in ("B", "i"):
                    names[ev["name"]] = names.get(ev["name"], 0) + 1
            print(json.dumps({"probe": "obs", "mode": obs.mode(),
                              "trace_events": n,
                              "merged_events": len(merged),
                              "schema_valid": True,
                              "span_counts": dict(sorted(names.items()))}),
                  flush=True)
        reg = obs.registry().merge_across(comm)
        for line in reg.to_prometheus().rstrip("\n").split("\n"):
            print(json.dumps({"probe": "obs_prometheus", "line": line}),
                  flush=True)
    finally:
        obs.set_mode(prev)
        obs.reset_tracer()
        obs.reset_registry()


def probe_flashcmp():
    """Flash (Pallas) vs xla_attention payoff, quantified (VERDICT r3
    Missing #3): causal self-attention fwd+bwd at GPT-2-small geometry,
    T = 2048 and 8192.  Reports ms/step and the speedup ratio."""
    from chainermn_tpu.ops.flash_attention import _flash_diff, xla_attention

    B, H, D = 4, 12, 64
    # Pallas lowers natively on TPU; CPU smoke needs interpret mode
    # (timing there validates mechanics only, not perf) and a SMALL
    # default T — interpret-mode grad at 8192 is effectively unbounded
    # and xla's [B,H,8192,8192] fp32 scores would be ~13 GB on host
    interp = jax.default_backend() == "cpu"
    default_t = "256" if interp else "2048,8192"
    seqs = tuple(int(t) for t in
                 os.environ.get("PROBE_T", default_t).split(","))
    if interp:
        # clamp REQUESTED lengths too, not just the default: interpret-
        # mode grad at long T is effectively unbounded and xla's [T,T]
        # fp32 scores exhaust host RAM
        seqs = tuple(t for t in seqs if t <= 512) or (256,)
        print(json.dumps({"probe": "flash_vs_xla_attention",
                          "warning": "cpu interpret mode: requested "
                          "PROBE_T clamped; timings validate mechanics "
                          "only, not perf", "seqs": list(seqs)}),
              flush=True)
    scale = 1.0 / (D ** 0.5)

    def flash_loss(q, k, v):
        # the custom-VJP entry `attention` dispatches to on TPU:
        # Pallas forward AND backward
        return jnp.sum(_flash_diff(q, k, v, True, scale, interp)
                       .astype(jnp.float32))

    def xla_loss(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, scale=scale)
                       .astype(jnp.float32))

    for T in seqs:
        q, k, v = (jnp.asarray(np.random.RandomState(i)
                               .normal(0, 1, (B, H, T, D))
                               .astype(np.float32)).astype(jnp.bfloat16)
                   for i in range(3))
        row = {"probe": "flash_vs_xla_attention", "B": B, "H": H, "T": T,
               "D": D}
        if interp:
            row["interpreted"] = True  # mechanics smoke, not perf
        for name, loss in (("flash", flash_loss), ("xla", xla_loss)):

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                # reps amortizes the per-sync round-trip
                dt = timeit(lambda a, b, c: grad(a, b, c)[0], q, k, v,
                            reps=10)
                row[f"{name}_fwd_bwd_ms"] = round(dt * 1e3, 2)
            except Exception as e:  # e.g. HBM OOM for xla at T=8192
                row[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
        if "flash_fwd_bwd_ms" in row and "xla_fwd_bwd_ms" in row:
            row["flash_speedup"] = round(
                row["xla_fwd_bwd_ms"] / row["flash_fwd_bwd_ms"], 2)
        print(json.dumps(row), flush=True)


def probe_flash():
    """PROBE=flash: the committed flash-backward budget table
    (tools/flash_budgets.json) joined with a live fused-vs-split
    measurement — the per-kernel face of the bench rows.  On the real
    chip each row carries TFLOP/s at the committed tiles plus the
    within_target verdict at T=8192; on CPU it interpret-smokes a
    clamped T (mechanics only, labeled)."""
    import importlib
    import flash_sweep
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

    with open(flash_sweep.BUDGETS_PATH) as f:
        budgets = json.load(f)
    interp = jax.default_backend() == "cpu"
    B, H, D = 4, 12, 64
    seqs = tuple(int(t) for t in os.environ.get(
        "PROBE_T", ",".join(sorted(budgets["bwd_block_table"],
                                   key=int))).split(","))
    reps = int(os.environ.get("PROBE_REPS", "20"))
    if interp:
        seqs = tuple(t for t in seqs if t <= 256) or (128,)
        reps = 1
        print(json.dumps({"probe": "flash", "warning":
                          "cpu interpret mode: T clamped; timings "
                          "validate mechanics only, not perf",
                          "seqs": list(seqs)}), flush=True)
    for T in seqs:
        bq, bk = budgets["bwd_block_table"].get(
            str(T), (None, None)) if not interp else (32, 32)
        if bq is None:
            bq, bk = 1024, 1024
        bq, bk = min(bq, T), min(bk, T)
        if T % bq or T % bk:
            # grid = T // block silently drops the tail on ragged T —
            # refuse the row instead of mismeasuring (flash_sweep skips
            # such configs the same way)
            print(json.dumps({
                "probe": "flash", "T": T, "block_q": bq, "block_k": bk,
                "error": f"tiles do not divide T={T}: pick PROBE_T "
                         "multiples of the budget tiles"}), flush=True)
            continue
        row = {"probe": "flash", "T": T, "block_q": bq, "block_k": bk,
               "baseline_split_tflops_T8192":
                   budgets["baseline"]["fwd_bwd_tflops_T8192"],
               "target_tflops_T8192":
                   budgets["target_fwd_bwd_tflops_T8192"],
               "sweep_status": budgets["sweep"]["status"]}
        if interp:
            row["interpreted"] = True
        for mode in ("fused", "split"):
            try:
                point = flash_sweep.measure_point(
                    fa, B, H, D, T, bq, bk, mode, reps, interp)
            except Exception as e:  # noqa: BLE001 — report and continue
                row[f"{mode}_error"] = f"{type(e).__name__}: {e}"[:200]
                continue
            row[f"{mode}_fwd_bwd_ms"] = point["fwd_bwd_ms"]
            row[f"{mode}_fwd_bwd_tflops"] = point["fwd_bwd_tflops"]
        if "fused_fwd_bwd_ms" in row and "split_fwd_bwd_ms" in row:
            row["fused_speedup"] = round(
                row["split_fwd_bwd_ms"] / row["fused_fwd_bwd_ms"], 2)
        if T == 8192 and not interp and "fused_fwd_bwd_tflops" in row:
            row["within_target"] = row["fused_fwd_bwd_tflops"] >= \
                budgets["target_fwd_bwd_tflops_T8192"]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    if os.environ.get("PROBE_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["PROBE_PLATFORM"])
    which = os.environ.get("PROBE", "all")
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()
    if which == "hbm_bytes":
        probe_hbm_bytes()
    if which in ("all", "matmul"):
        probe_matmul()
    if which in ("all", "conv"):
        probe_conv("NCHW")
        probe_conv("NHWC")
    if which in ("all", "resnet"):
        probe_resnet(int(os.environ.get("PROBE_SCAN", "8")))
    if which == "prefetch":
        probe_prefetch_overhead()
    if which == "input_pipeline":
        probe_input_pipeline()
    if which == "precision_audit":
        probe_precision_audit()
    if which == "flashcmp":
        probe_flashcmp()
    if which == "flash":
        probe_flash()
    if which == "comm":
        probe_comm()
    if which == "autotune":
        probe_autotune()
    if which == "serving":
        probe_serving()
    if which == "obs":
        probe_obs()
