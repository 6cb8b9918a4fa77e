"""HBM byte census: what the train-step program the framework emits moves.

Beside ``tools/comm_census.py`` and ``tools/serving_census.py``: facts
of a LOWERED program, chip-free, held to a committed file by tier-1.

* :func:`measure_hbm_bytes` lowers the ResNet-50 train step and reads
  XLA's ``bytes accessed`` and :func:`stablehlo_bytes_by_category`'s
  per-op-category table; ``tools/hbm_budgets.json`` holds the committed
  budgets, ``tests/test_hbm_budget.py`` the gate.
* :func:`classify_contractions` counts a StableHLO text's convolutions
  or dot_generals by input -> result dtype;
  ``tests/test_precision_audit.py`` holds its regexes to the format.

``python tools/hbm_census.py [--bs 64 --size 224] [--compile]`` prints
one configuration's census as a JSON line beside its committed budget.
"""

import argparse
import json
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

HBM_BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "hbm_budgets.json")

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



def classify_contractions(text, op):
    """Count ``stablehlo.<op>`` lines by input→result dtype.  bf16
    inputs with an f32 result are the CORRECT MXU configuration (bf16
    multiply, f32 accumulate via preferred_element_type); only
    f32-INPUT contractions forgo the bf16 MXU path."""
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bs", type=int, default=64)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--compile", action="store_true",
                    help="also compile: the optimized module's bytes and "
                         "memory_analysis (donation shows as alias bytes)")
    args = ap.parse_args(argv)
    # lowering is backend-neutral; only the parameter init executes
    jax.config.update("jax_platforms", "cpu")
    row = measure_hbm_bytes(args.bs, args.size, do_compile=args.compile)
    entry = load_hbm_budgets().get(row["config"])
    if entry:
        row["budget_bytes_accessed"] = entry["budget_bytes_accessed"]
        row["within_budget"] = \
            row["bytes_accessed"] <= entry["budget_bytes_accessed"]
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
