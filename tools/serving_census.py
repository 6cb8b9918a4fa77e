"""Serving-engine structure census: the decode/prefill contract as facts.

Mirrors ``tools/comm_census.py``: the serving engine's performance
story rests on two STRUCTURAL properties of its compiled programs, and
both are trace properties — checkable off-chip, committed to
``tools/serving_budgets.json``, and gated tier-1 by
``tests/test_serving_budget.py`` so a refactor cannot silently regress
them while the numeric half waits for a chip:

* **decode**: the per-token step reads the cache through the block
  table — exactly ONE gather per pool per layer (``2·L`` total over
  K and V), ONE page scatter per pool per layer for the new token, and
  **no full-T attention**: no ``dot_general`` anywhere in the program
  whose output carries two T-sized dimensions (the ``[T, T]`` score
  matrix a dense re-prefill would materialize every token).
* **prefill**: the prompt pass reuses the PR 4 flash forward — one
  ``_flash_kernel`` Pallas call per layer, ZERO backward kernels (no
  grad is ever traced on the serving path), and the same no-[T, T]
  fact at the XLA level (scores live in kernel tiles).

Round 14 (ISSUE 13) adds the scale-out configs:

* **prefix_prefill**: the prefix-HIT suffix prefill reads the shared
  prefix through the block table — one gather per pool per layer, one
  offset scatter per pool per layer — and runs ZERO flash kernels over
  shared pages (zero Pallas kernels at all: the suffix-by-context
  softmax is the saving the hit buys) and no [T, T] score dot (scores
  are suffix-bucket × context, one T-sized dim).
* **disagg_decode_slice**: the ONLY compute program the decode slice
  runs between transfers is the decode step — zero prefill (flash)
  kernels on the decode slice, pinned against the decode trace.
* **transfer_insert**: the slice-to-slice page ship lands with ONE
  full-pool scatter (drop-fenced padding rows), no gathers, no
  kernels — shipping is data movement, never recompute.

The prefill trace forces ``CHAINERMN_TPU_FLASH_INTERPRET=1`` so the CPU
census sees the same Pallas lowering a TPU run compiles.  ``--write-
budgets`` regenerates the structure/geometry halves (trace properties —
allowed off-chip, like comm_census); the ``targets`` section is the
measured half and only a chip run may update it.
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "serving_budgets.json")

#: census vertical: small enough to trace in milliseconds, big enough
#: that every structural fact (page gather, flash tile, block table) is
#: exercised at real ranks.  prefill_T = 256 keeps the flash kernel on
#: its Pallas path (a 128-multiple) AND strictly exceeds every feature
#: dimension of the vertical (d_ff = 4·d_model = 192, qkv = 144,
#: n_vocab = 128), so the full-T detector — "a dot output with TWO dims
#: >= T" — can only fire on a genuine [T, T] score matrix, never on a
#: [B·T, features] GEMM.
#: round-14 additions: prefix_start/prefix_suffix_T shape the suffix
#: prefill trace (a 128-token page-aligned hit + a 32-token suffix
#: bucket — suffix strictly below the full-T threshold, so the no-[T,T]
#: detector stays sound for the suffix-by-context score), and
#: transfer_pages sizes the disaggregation ship's page block.
#: round-20 additions: spec_k sizes the speculative verify span (K + 1
#: queries per lane — a small constant, far below the full-T threshold,
#: so the no-[T,T] detector stays sound for the [B, H, K1, ctx] score),
#: and chunk_T is the chunked-prefill chunk size (a page multiple; the
#: chunk trace runs the offset suffix-prefill program at a page-aligned
#: mid-prompt start).
GEOMETRY = {
    "n_vocab": 128, "d_model": 48, "n_heads": 2, "n_layers": 2,
    "max_len": 256, "page_size": 16, "num_pages": 32,
    "max_context": 256, "prefill_T": 256, "decode_B": 4,
    "prefix_start": 128, "prefix_suffix_T": 32, "transfer_pages": 8,
    "spec_k": 4, "chunk_T": 32,
}


def load_budgets(path=BUDGETS_PATH):
    with open(path) as f:
        return json.load(f)


def _vertical():
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.models import TransformerLM

    g = GEOMETRY
    model = TransformerLM(n_vocab=g["n_vocab"], d_model=g["d_model"],
                          n_heads=g["n_heads"], n_layers=g["n_layers"],
                          max_len=g["max_len"], seed=0)
    state = extract_state(model)
    L, P, S = g["n_layers"], g["num_pages"], g["page_size"]
    pools = tuple(jnp.zeros((L, P, S) + shape, jnp.float32)
                  for shape in model.serve_cache_entry())
    N = g["max_context"] // S
    rng = np.random.RandomState(0)
    return model, state, pools, N, rng


def _walk_eqns(jaxpr, *, into_pallas):
    """Yield (eqn, inside_pallas) over a jaxpr and ALL its sub-jaxprs —
    including tuple/list-valued params (``lax.cond``'s ``branches`` is a
    tuple of ClosedJaxprs; skipping it would blind the no-full-T gate to
    anything a refactor tucks under a cond)."""
    def subjaxprs(p):
        vals = p if isinstance(p, (tuple, list)) else (p,)
        for v in vals:
            pj = getattr(v, "jaxpr", None)
            if pj is not None:
                yield getattr(pj, "jaxpr", pj)

    def rec(jx, inside):
        for eqn in jx.eqns:
            yield eqn, inside
            is_pallas = eqn.primitive.name == "pallas_call"
            if is_pallas and not into_pallas:
                continue
            for p in eqn.params.values():
                for sub in subjaxprs(p):
                    yield from rec(sub, inside or is_pallas)
    yield from rec(jaxpr, False)


def _census_facts(jaxpr, pool_shape, t_full):
    """Structure facts of one traced serving program.

    ``pool_shape``: the whole pool's shape ``(L, P, S, H · D)``, which
    the programs gather from and scatter into at a layer — gathers/
    scatters are attributed to the KV pool by operand shape
    (embedding lookups are gathers too; shape is the discriminator).
    ``t_full``: the full-T threshold — a dot_general output with TWO
    dims ``>= t_full`` is a dense [T, T] score matrix.  Pallas kernel
    INTERIORS are excluded from the dot census (their tiles are VMEM-
    resident by construction — the fact being pinned is about HBM-level
    materialization), but counted as kernels by name."""
    facts = {"pool_gathers": 0, "pool_scatters": 0,
             "full_t_score_dots": 0, "flash_fwd_kernels": 0,
             "bwd_kernels": 0}
    for eqn, inside in _walk_eqns(jaxpr, into_pallas=False):
        name = eqn.primitive.name
        if name == "pallas_call":
            kname = eqn.params["name"]
            if "bwd" in kname:
                facts["bwd_kernels"] += 1
            elif "_flash_kernel" in kname:
                facts["flash_fwd_kernels"] += 1
        elif name == "gather":
            if tuple(eqn.invars[0].aval.shape) == pool_shape:
                facts["pool_gathers"] += 1
        elif name == "scatter":
            if tuple(eqn.invars[0].aval.shape) == pool_shape:
                facts["pool_scatters"] += 1
        elif name == "dot_general" and not inside:
            big = sum(1 for d in eqn.outvars[0].aval.shape
                      if d >= t_full)
            if big >= 2:
                facts["full_t_score_dots"] += 1
    return facts


@contextlib.contextmanager
def _flash_interpret():
    old = os.environ.get("CHAINERMN_TPU_FLASH_INTERPRET")
    os.environ["CHAINERMN_TPU_FLASH_INTERPRET"] = "1"
    try:
        yield
    finally:
        if old is None:
            del os.environ["CHAINERMN_TPU_FLASH_INTERPRET"]
        else:
            os.environ["CHAINERMN_TPU_FLASH_INTERPRET"] = old


def decode_census(mode="paged"):
    """Facts of the decode-step program at the committed geometry."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import decode_program

    model, state, (k_pool, v_pool), N, rng = _vertical()
    g = GEOMETRY
    B = g["decode_B"]
    toks = jnp.zeros(B, jnp.int32)
    pos = jnp.full(B, g["page_size"], jnp.int32)  # mid-sequence step
    bts = jnp.zeros((B, N), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, k, v, t, p, b: decode_program(
            model, s, k, v, t, p, b, mode=mode))(
        state, k_pool, v_pool, toks, pos, bts)
    pool_shape = tuple(k_pool.shape)
    facts = _census_facts(jaxpr.jaxpr, pool_shape, g["max_context"])
    facts["attn_mode"] = mode
    return facts


def prefill_census():
    """Facts of the prefill program at the committed geometry (flash
    forced through its Pallas interpret lowering, as on TPU)."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import prefill_program

    model, state, (k_pool, v_pool), N, rng = _vertical()
    g = GEOMETRY
    T = g["prefill_T"]
    tokens = jnp.zeros((1, T), jnp.int32)
    bt_row = jnp.zeros(N, jnp.int32)
    with _flash_interpret():
        jaxpr = jax.make_jaxpr(
            lambda s, k, v, t, tl, b: prefill_program(
                model, s, k, v, t, tl, b))(
            state, k_pool, v_pool, tokens, jnp.int32(T), bt_row)
    pool_shape = tuple(k_pool.shape)
    return _census_facts(jaxpr.jaxpr, pool_shape, g["prefill_T"])


def prefix_prefill_census():
    """Facts of the prefix-HIT suffix-prefill program at the committed
    geometry: a ``prefix_start``-token shared prefix read back through
    the block table + a ``prefix_suffix_T`` suffix.  The headline fact
    is ``flash_fwd_kernels == 0`` — a prefix hit never reruns a flash
    kernel over shared pages."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import prefix_prefill_program

    model, state, (k_pool, v_pool), N, rng = _vertical()
    g = GEOMETRY
    T = g["prefix_suffix_T"]
    tokens = jnp.zeros((1, T), jnp.int32)
    bt_row = jnp.zeros(N, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, k, v, t, tl, st, b: prefix_prefill_program(
            model, s, k, v, t, tl, st, b))(
        state, k_pool, v_pool, tokens, jnp.int32(T),
        jnp.int32(g["prefix_start"]), bt_row)
    pool_shape = tuple(k_pool.shape)
    return _census_facts(jaxpr.jaxpr, pool_shape, g["max_context"])


def disagg_decode_slice_census():
    """Facts of the decode slice's step program on the disaggregated
    split.  The decode slice runs ONLY the decode step (plus the
    data-movement insert, censused separately): the committed fact is
    zero prefill kernels — ``flash_fwd_kernels == 0`` — so a refactor
    cannot quietly move FLOP-bound prefill work onto the HBM-bound
    slice."""
    return decode_census("paged")


def transfer_insert_census():
    """Facts of the disaggregation ship's receiving scatter: one
    drop-fenced full-pool scatter, zero gathers, zero kernels — the
    transfer is data movement, never recompute."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import insert_pages

    g = GEOMETRY
    L, P, S = g["n_layers"], g["num_pages"], g["page_size"]
    nb = g["transfer_pages"]
    pool = jnp.zeros((L, P, S, g["d_model"]), jnp.float32)
    block = jnp.zeros((L, nb, S, g["d_model"]), jnp.float32)
    rows = jnp.zeros(nb, jnp.int32)
    jaxpr = jax.make_jaxpr(insert_pages)(pool, block, rows)
    # attribute by the FULL pool shape: the insert scatters all layers
    # at once (one scatter per pool per transfer, not per layer)
    return _census_facts(jaxpr.jaxpr, tuple(pool.shape),
                         g["max_context"])


def spec_verify_census():
    """Facts of the speculative VERIFY program (round 20): ``spec_k +
    1`` positions scored per lane in ONE dispatch.  The headline facts
    are ``queries_per_dispatch == spec_k + 1`` — the dispatch-count
    reduction is structural, each verify prices up to K+1 emitted
    tokens — and the decode-step invariants carried over unchanged: one
    gather per pool per layer (the speculative queries ride the SAME
    cache-byte reads the single-query step pays), one drop-fenced span
    scatter per pool per layer, zero flash kernels, and NO [T, T]
    score dot (scores are ``[B, H, K1, ctx]`` — K1 is a small
    constant, never the context, so speculation never degenerates into
    a per-token dense re-prefill)."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import spec_verify_program

    model, state, (k_pool, v_pool), N, rng = _vertical()
    g = GEOMETRY
    B, K1 = g["decode_B"], g["spec_k"] + 1
    toks = jnp.zeros((B, K1), jnp.int32)
    start = jnp.full(B, g["page_size"], jnp.int32)  # mid-sequence span
    n_valid = jnp.full(B, K1, jnp.int32)
    bts = jnp.zeros((B, N), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, k, v, t, st, nv, b: spec_verify_program(
            model, s, k, v, t, st, nv, b))(
        state, k_pool, v_pool, toks, start, n_valid, bts)
    pool_shape = tuple(k_pool.shape)
    facts = _census_facts(jaxpr.jaxpr, pool_shape, g["max_context"])
    facts["queries_per_dispatch"] = K1
    return facts


def chunked_prefill_census():
    """Facts of ONE mid-prompt chunk of a chunked prefill (round 20):
    the offset suffix-prefill program at ``chunk_T`` tokens starting at
    a page-aligned mid-prompt position.  The committed facts: one
    gather per pool per layer, one offset scatter per pool per layer,
    and zero [T, T] score dots — each chunk attends chunk-by-written-
    context, so chunking a T-token prompt into T/C chunks never
    re-materializes the dense [T, T] score a monolithic prefill pays,
    and the per-chunk cost stays bounded by the chunk budget."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.serving import prefix_prefill_program

    model, state, (k_pool, v_pool), N, rng = _vertical()
    g = GEOMETRY
    T = g["chunk_T"]
    tokens = jnp.zeros((1, T), jnp.int32)
    bt_row = jnp.zeros(N, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda s, k, v, t, tl, st, b: prefix_prefill_program(
            model, s, k, v, t, tl, st, b))(
        state, k_pool, v_pool, tokens, jnp.int32(T),
        jnp.int32(g["chunk_T"]), bt_row)
    pool_shape = tuple(k_pool.shape)
    return _census_facts(jaxpr.jaxpr, pool_shape, g["max_context"])


def structure():
    return {"decode": decode_census("paged"),
            "prefill": prefill_census(),
            "prefix_prefill": prefix_prefill_census(),
            "disagg_decode_slice": disagg_decode_slice_census(),
            "transfer_insert": transfer_insert_census(),
            "spec_verify": spec_verify_census(),
            "chunked_prefill": chunked_prefill_census()}


def write_budgets():
    try:
        budgets = load_budgets()
    except Exception:
        budgets = {}
    budgets["geometry"] = GEOMETRY
    budgets["structure"] = structure()
    tmp = BUDGETS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(budgets, f, indent=2)
        f.write("\n")
    os.replace(tmp, BUDGETS_PATH)
    print(json.dumps({"probe": "serving_census", "wrote": BUDGETS_PATH}),
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-budgets", action="store_true",
                    help="regenerate the structure/geometry halves of "
                         "tools/serving_budgets.json (trace property — "
                         "allowed off-chip; targets are measured and "
                         "carried over)")
    args = ap.parse_args()
    import jax
    jax.config.update("jax_platforms",
                      os.environ.get("JAX_PLATFORMS") or "cpu")
    st = structure()
    for phase, facts in st.items():
        print(json.dumps({"probe": "serving_census", "phase": phase,
                          **facts}), flush=True)
    if args.write_budgets:
        write_budgets()


if __name__ == "__main__":
    main()
