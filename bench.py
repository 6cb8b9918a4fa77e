"""Benchmark harness: training and serving throughput per chip.

One process, one mode per invocation (``BENCH_MODEL``), one JSON line
per result on standard output; the LAST line is authoritative (a mode
may print a preliminary line after its first timing trial, and later
trials only improve it):

  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "mfu": ..., "compile_s": ..., "platform": ..., ...}

Exit code: 0 when the mode produced its result.  A mode that fails
prints ``{"metric": ..., "value": null, "error": ...}`` and exits 1.
Without a TPU the harness exits 1 before any timing — unless
``JAX_PLATFORMS=cpu`` is set explicitly, which is the CPU rehearsal:
those runs clamp their sizes and label every row ``"cpu_smoke": true``;
their numbers say nothing about the device.

Baseline derivation (BASELINE.md: reference published numbers): the
ChainerMN scaling study (arXiv:1710.11351) trains ResNet-50/ImageNet 100
epochs in ~4.4 h on 128 P100s → 1.28M images × 100 / (4.4·3600 s) / 128
≈ 225 images/sec/GPU.  ``vs_baseline`` is measured throughput per chip
against that per-device figure.

MFU: analytic ResNet-50 flops model.  Forward ≈ 4.1 GFLOP/image at 224²
(standard count, multiply-add = 2 flops); training step ≈ 3× forward
(bwd ≈ 2× fwd).  MFU = achieved flops/sec ÷ peak bf16 flops of the chip,
from the ``_PEAK_TFLOPS`` table keyed by ``device_kind``; a device that
is not in the table is an error, not a default.

The training step is the framework's real data-parallel path:
``create_multi_node_optimizer`` over a ``jax_ici`` communicator spanning
all available chips, bf16 conv compute, bf16 gradient compression — the
TPU translation of the reference's flagship ``pure_nccl`` fp16
configuration (SURVEY §2.1 pure_nccl).

Env knobs (defaults = the flagship config):

  measurement   BENCH_MODEL (resnet50|transformer|longcontext|serving|
                moe),
                BENCH_BS, BENCH_SIZE, BENCH_LAYOUT (NHWC|NCHW),
                BENCH_SCAN, BENCH_REMAT, BENCH_INPUT_PIPELINE — resnet;
                BENCH_SEQ, BENCH_D_MODEL, BENCH_LAYERS, BENCH_VOCAB,
                BENCH_HEADS, BENCH_REMAT_POLICY — transformer;
                BENCH_LC_SEQS (default 16384,32768), BENCH_LC_XLA_T
                (default 8192: the stock-XLA contrast leg),
                BENCH_LC_BS/BENCH_LC_HEAD_DIM/BENCH_LC_REPS —
                longcontext (T=16k/32k flash fwd+bwd rows + the
                "XLA fails to compile, flash runs" contrast);
                BENCH_SERVE_QPS (default 16), BENCH_SERVE_TENANTS (4),
                BENCH_SERVE_REQUESTS (64), BENCH_SERVE_MAX_NEW (32),
                BENCH_SERVE_PROMPT (64), BENCH_SERVE_MAX_BATCH (8),
                BENCH_SERVE_PAGE (16), BENCH_SERVE_PAGES (256),
                BENCH_SERVE_PREFIX (16: per-tenant shared system-prompt
                tokens in the chat-shaped load; 0 disables the prefix
                cache — the A/B off leg), BENCH_SERVE_DISAGG (0|1:
                disaggregated prefill/decode slices),
                BENCH_SERVE_TP (1: tensor-parallel decode ways),
                BENCH_SERVE_SPEC_K (0: speculative decoding — K n-gram
                proposals verified per dispatch, bit-identical tokens;
                rows grow spec_steps/accepted_tokens_per_dispatch/
                spec_acceptance_rate/draft_overhead),
                BENCH_SERVE_CHUNK (0: chunked prefill — C-token chunks
                AND a mixed short/long load, every fourth prompt up to
                4x BENCH_SERVE_PROMPT; rows grow chunked_admissions/
                chunk_prefills),
                BENCH_SERVE_REPLICAS (1: >1 serves through a
                ReplicaFleet behind the router — rows grow replicas/
                reroutes/weight_sync_s), BENCH_FLEET_KILL_AT (-1:
                decode step at which the highest replica preempts;
                its in-flight sequences reroute with zero drops and a
                cold replica joins via the multicast-tree weight
                sync), BENCH_DIURNAL (0|1: sinusoidal arrival rate
                plus a CapacityBroker auto-applying the hysteresis
                policy's +1/-1 as REAL training<->serving role
                transfers — rows grow conversions/role_transfers/
                convert_s), BENCH_DIURNAL_PERIOD (8.0 s),
                BENCH_DIURNAL_AMP (0.8), BENCH_DIURNAL_WORLD (2:
                synthetic training ranks eligible to convert),
                BENCH_DIURNAL_UP (8) / BENCH_DIURNAL_DOWN (0:
                queue-depth water marks) — serving (continuous-batching
                engine under a
                seeded open-loop Poisson load: tokens/sec + p50/p99
                per-token latency + page-pool occupancy +
                prefix_hit_rate / effective_capacity_x /
                transferred_page_bytes / tp;
                CPU runs clamp to a labeled cpu_smoke row);
                BENCH_MOE_EXPERTS (chip count), BENCH_MOE_TOPK (1),
                BENCH_MOE_CAPACITY (1.25), BENCH_MOE_TWO_STAGE
                (''=auto|0|1) — moe (Switch-FFN expert-parallel
                vertical: tokens/sec/chip + exchanged dispatch bytes
                per fabric + moe_dropped_frac; the hierarchical
                BENCH_EXCHANGE legs run the two-stage ici×dcn dispatch
                and BENCH_GRAD_DTYPE=int8 quantizes its DCN crossing;
                CPU runs clamp to a labeled cpu_smoke row);
                BENCH_STEPS (steps/trial), BENCH_TRIALS,
                BENCH_DONATE=0 (A/B leg: disable params/opt-state
                buffer donation),
                BENCH_MEMSTATS=0 (skip the memory_analysis row fields),
                BENCH_EXCHANGE (per_leaf|flat|bucketed|reduce_scatter|
                hierarchical|hierarchical_rs — gradient-exchange
                structure of the DP step; default flat, the historical
                flagship config; the hierarchical legs run
                the two-level ici × dcn exchange and carry
                topology/ici_size/dcn_size + per-hop exchanged-byte
                columns),
                BENCH_BUCKET_MB (bucket bound for bucketed, default 4),
                BENCH_INTER_SIZE (hierarchical legs: force a dcn × ici
                split of the local chips — the on-host structural A/B;
                default: one dcn group per controller process)
  compile cache JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
                (utils.compat.configure_persistent_cache)
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_PER_SEC = 225.0  # ChainerMN-era images/sec/P100 (docstring)

# Flagship-config defaults.  OOM backoff halves the batch at most twice.
DEFAULT_BS = 64
DEFAULT_SIZE = 224
DEFAULT_SEQ = 1024
DEFAULT_STEPS = 40      # steps per timing trial
DEFAULT_TF_STEPS = 20
# transformer-mode flagship config (GPT-2-small-class)
DEFAULT_TF_BS = 8
DEFAULT_TF_D_MODEL = 768
DEFAULT_TF_LAYERS = 12
DEFAULT_TF_VOCAB = 32768

# Peak bf16 flops by TPU generation (per chip; Google Cloud TPU
# documentation).  v5 lite = v5e.  "cpu" is the rehearsal entry: no
# peak, so no MFU field.
_PEAK_TFLOPS = {
    "v5 lite": 197.0, "v5e": 197.0, "v5p": 459.0,
    "v4": 275.0, "v6e": 918.0, "cpu": None,
}


def _env_float(name, default):
    """float env knob; unset or empty means ``default``."""
    return float(os.environ.get(name, "") or default)


def _env_int(name, default):
    """int env knob; unset or empty means ``default``."""
    return int(os.environ.get(name, "") or default)


def _steps(default):
    """Steps per timing trial: ``BENCH_STEPS`` or the mode's default."""
    return _env_int("BENCH_STEPS", default)


def _emit(result):
    print(json.dumps(result), flush=True)


def _resnet50_train_flops_per_image(image_size):
    """Analytic flops model: fwd ~4.1 GFLOP at 224² (scales with area),
    train = fwd + bwd ≈ 3× fwd."""
    fwd = 4.1e9 * (image_size / 224.0) ** 2
    return 3.0 * fwd


def _peak_tflops(devices):
    """Peak bf16 TFLOP/s of the device, from ``_PEAK_TFLOPS``.  A device
    kind that is not in the table raises: an MFU over an assumed peak
    is not a measurement."""
    kind = devices[0].device_kind
    for name, peak in _PEAK_TFLOPS.items():
        if name in kind.lower():
            return peak
    raise ValueError(f"device kind {kind!r} is not in bench._PEAK_TFLOPS; "
                     "add its published peak with its source")


def _transformer_flops_per_token(d_model, n_layers, n_vocab, seq_len):
    """Analytic train-step flops per token for the causal LM: matmul
    fwd = 2·(12·L·d² + d·V), attention fwd = 4·T·d·L (scores + values,
    causal halving ignored ≈ upper bound), train ≈ 3× fwd."""
    matmul = 2.0 * (12.0 * n_layers * d_model ** 2 + d_model * n_vocab)
    attn = 4.0 * seq_len * d_model * n_layers
    return 3.0 * (matmul + attn)


def _exchange_config():
    """(exchange, bucket_mb_or_None) from the env, validated against
    the ONE exchange vocabulary (communicators.EXCHANGES; flat is the
    historical flagship — other flavors are measured variants)."""
    from chainermn_tpu.communicators import EXCHANGES
    exchange = os.environ.get("BENCH_EXCHANGE", "flat")
    if exchange not in EXCHANGES:
        raise ValueError(
            f"unknown BENCH_EXCHANGE={exchange!r} ({'|'.join(EXCHANGES)})")
    bucket_mb = os.environ.get("BENCH_BUCKET_MB")
    return exchange, (float(bucket_mb) if bucket_mb else None)


def _make_bench_communicator(exchange, bucket_mb):
    """Communicator for the requested gradient exchange, from the same
    env knobs every bench mode reads (BENCH_GRAD_DTYPE /
    BENCH_INTER_SIZE / BENCH_STRIPE_RATIO / BENCH_ERROR_FEEDBACK).
    Split out of `_make_dp_optimizer` because the MoE vertical needs
    the communicator BEFORE the model exists (the expert bank shards
    over it).  Returns ``(comm, opt_exchange)``."""
    import chainermn_tpu as ct
    comm_name, bc, opt_exchange = ct.communicators.exchange_knobs(exchange)
    autotune = os.environ.get("BENCH_AUTOTUNE", "0") == "1"
    inter_size = _env_int("BENCH_INTER_SIZE", 0) or None
    grad_dtype = os.environ.get("BENCH_GRAD_DTYPE", "bfloat16")
    grad_dtype = None if grad_dtype.lower() in ("none", "") else grad_dtype
    if autotune and "BENCH_GRAD_DTYPE" not in os.environ:
        # the autotune leg (ISSUE 19) leaves every knob
        # the operator did not explicitly set free for the agreed plan
        # to fill — applying the flagship bf16 default here would read
        # as a hand knob and pin the dtype ladder shut
        grad_dtype = None
    # the striped legs (ISSUE 11) need a NONZERO ratio or they would
    # silently measure the strict hierarchical schedule under the
    # striped name: BENCH_STRIPE_RATIO, else the committed default —
    # except under autotune, where an unset ratio stays FREE for the
    # derived plan (that is the measurement)
    stripe_ratio = None
    if exchange in ("striped", "striped_rs"):
        from chainermn_tpu.communicators._memory_utility import \
            DEFAULT_STRIPE_RATIO
        stripe_ratio = _env_float("BENCH_STRIPE_RATIO", 0) or None
        if stripe_ratio is None and not autotune:
            stripe_ratio = DEFAULT_STRIPE_RATIO
    comm = ct.create_communicator(comm_name,
                                  allreduce_grad_dtype=grad_dtype,
                                  batch_collectives=bc,
                                  bucket_mb=bucket_mb,
                                  inter_size=inter_size
                                  if comm_name == "hierarchical" else None,
                                  stripe_ratio=stripe_ratio,
                                  error_feedback=os.environ.get(
                                      "BENCH_ERROR_FEEDBACK", "1") == "1",
                                  autotune=True if autotune else None)
    return comm, opt_exchange


def _make_dp_optimizer(inner, model, exchange, bucket_mb, comm=None,
                       opt_exchange=None):
    """Communicator + multi-node wrapper for the requested gradient
    exchange (flagship bf16 gradient compression on every flavor;
    BENCH_GRAD_DTYPE overrides — ``none`` for lossless, ``int8`` /
    ``float8_e4m3`` / ``float8_e5m2`` for the quantized-wire A/B, where
    a scalar quantized dtype compresses the DCN hop only, per the
    communicator's own rule; BENCH_ERROR_FEEDBACK=0 is the ablation
    leg).  The hierarchical legs honor BENCH_INTER_SIZE (force a
    dcn × ici split of the local chips — the on-host structural A/B the
    queue runs as 2×4; default: infer from the controller topology,
    i.e. a real multi-host run gets one dcn group per host).  Pass a
    prebuilt ``comm`` (+ its ``opt_exchange``) when the model already
    holds it — the MoE vertical's expert-parallel axis IS the
    data-parallel communicator."""
    import chainermn_tpu as ct
    if comm is None:
        comm, opt_exchange = _make_bench_communicator(exchange, bucket_mb)
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(inner, comm,
                                         exchange=opt_exchange)
    return comm, opt.setup(model)


def _exchange_row_fields(model, comm, exchange):
    """Row fields documenting the exchange: structure knobs, the
    TOPOLOGY columns (ici/dcn split — 1×N on flat communicators), and
    the per-replica wire-byte accounting (ring decomposition — the
    same formulas tools/comm_budgets.json commits; 0 on a single chip;
    hierarchical legs additionally split the bill by hop).

    Every crossing is priced at its WIRE dtype — the itemsize of the
    packed buffer that actually crosses (ISSUE 8 satellite: the old
    gradient-dtype accounting happened to be right for bf16 casts and
    wrong for everything else).  Quantized wires change the collective
    SHAPE too (all_gather of codewords / all_to_all of segments), so
    they route through ``quantized_hop_bytes``, never the psum ring
    formula."""
    from chainermn_tpu.communicators._memory_utility import (
        exchanged_bytes, hierarchical_exchanged_bytes, is_quantized_dtype,
        quantized_hop_bytes)
    arrays = [p.array for p in model.params() if p.array is not None]
    n_params = sum(int(np.prod(a.shape)) for a in arrays)
    param_bytes = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                      for a in arrays)
    gdtype = comm.allreduce_grad_dtype
    q_wire = comm.quantized_wire_dtype
    grad_bytes = (n_params * gdtype.itemsize if gdtype is not None
                  else param_bytes)  # uncompressed grads ride param dtype
    size = comm.size
    fields = {"exchange": exchange,
              "bucket_mb": comm.bucket_mb if exchange == "bucketed"
              else None,
              "topology": comm.topology,
              "ici_size": comm.ici_size,
              "dcn_size": comm.dcn_size,
              # elastic columns (ISSUE 10): the controller world the row
              # was measured at, and how many membership epochs the
              # COMMUNICATOR has been through at construction (bench.py
              # itself never resizes mid-measurement — the elastic
              # measurement is bench_scaling --preempt-rank, whose rows
              # carry recovery-stats resize counts; here >0 means the
              # row was measured on a resize-scarred world)
              "world_size": getattr(comm, "inter_size", 1),
              "resizes": int(getattr(comm, "epoch", 0)),
              "grad_dtype": str(gdtype) if gdtype is not None else None,
              "dcn_wire_dtype": str(comm.dcn_grad_dtype)
              if comm.dcn_grad_dtype is not None else None,
              "error_feedback": comm.error_feedback
              if q_wire is not None else None}
    if comm.striped:
        # striped multi-path split (ISSUE 11): each path priced as its
        # own two-level exchange — the ICI path fast-hop-major, the
        # DCN path transposed — with the hop labels mapped back to
        # FABRICS, padding element counts exactly like the wire does
        # (each slice to its own ring multiple).  Rows carry the ratio
        # plus the same per-fabric byte columns the hierarchical legs
        # carry, so the A/B deltas line up column-for-column.
        from chainermn_tpu.communicators._memory_utility import \
            stripe_plan
        fields["stripe_ratio"] = comm.stripe_ratio
        intra, inter = comm.ici_size, comm.dcn_size
        wire_itemsize = gdtype.itemsize if gdtype is not None else 4
        dcn_itemsize = (comm.dcn_grad_dtype.itemsize
                        if comm.dcn_grad_dtype is not None
                        else wire_itemsize)
        n_i, n_d = stripe_plan(n_params, comm.stripe_ratio)
        if exchange == "striped_rs":
            size = comm.size
            n_pa = -(-n_i // size) * size
            n_pb = -(-n_d // size) * size
            ga = hierarchical_exchanged_bytes(
                n_pa * wire_itemsize, intra, inter, "reduce_scatter",
                dcn_n_bytes=n_pa // intra * dcn_itemsize)
            gb = hierarchical_exchanged_bytes(
                n_pb * dcn_itemsize, inter, intra, "reduce_scatter",
                dcn_n_bytes=n_pb // inter * 4)
            hops = {"ici": ga["ici"] + gb["dcn"],
                    "dcn": ga["dcn"] + gb["ici"]}
            pa = hierarchical_exchanged_bytes(n_pa * 4, intra, inter,
                                              "all_gather")
            pb = hierarchical_exchanged_bytes(n_pb * 4, inter, intra,
                                              "all_gather")
            p_hops = {"ici": pa["ici"] + pb["dcn"],
                      "dcn": pa["dcn"] + pb["ici"]}
        elif q_wire is not None:
            # quantized DCN crossings on BOTH paths: the ICI path's
            # chunk rides the gather-of-codewords hop, the DCN path
            # quantizes its whole pre-reduction slice (gather over dcn
            # + lossless full-slice psum over ici)
            n_pa = -(-n_i // intra) * intra
            hops = {
                "ici": exchanged_bytes(n_pa * wire_itemsize, intra,
                                       "psum")
                + exchanged_bytes(n_d * 4, intra, "psum"),
                "dcn": quantized_hop_bytes(n_pa // intra, inter,
                                           "psum", q_wire)
                + quantized_hop_bytes(n_d, inter, "psum", q_wire)}
            p_hops = None
        else:
            # the ONE per-path pricing surface (also what the census
            # identities are pinned against) — it pads each slice to
            # its ring multiple exactly like the wire does
            from chainermn_tpu.communicators._memory_utility import \
                striped_exchanged_bytes
            paths = striped_exchanged_bytes(
                n_params * wire_itemsize, intra, inter,
                comm.stripe_ratio, itemsize=wire_itemsize,
                dcn_itemsize=dcn_itemsize
                if comm.dcn_grad_dtype is not None else None)
            hops = {"ici": paths["ici_path"]["ici"]
                    + paths["dcn_path"]["ici"],
                    "dcn": paths["ici_path"]["dcn"]
                    + paths["dcn_path"]["dcn"]}
            p_hops = None
        fields["exchanged_grad_bytes"] = hops["ici"] + hops["dcn"]
        fields["exchanged_dcn_bytes"] = hops["dcn"]
        fields["exchanged_ici_bytes"] = hops["ici"]
        fields["exchanged_bytes"] = fields["exchanged_grad_bytes"]
        if exchange == "striped_rs":
            fields["exchanged_bytes"] += p_hops["ici"] + p_hops["dcn"]
            fields["exchanged_dcn_bytes"] += p_hops["dcn"]
            fields["exchanged_ici_bytes"] += p_hops["ici"]
        return fields
    if comm.hierarchy is not None:
        # per-hop split.  The accounting pads ELEMENTS exactly like the
        # wire does (pad_to_multiple on the packed vector: to intra for
        # the per-bucket exchange, to the full size for the sharded
        # update), then prices each hop in its own wire dtype — the dcn
        # dtype may differ from the ici wire dtype.
        intra, inter = comm.ici_size, comm.dcn_size
        coll = ("reduce_scatter"
                if exchange in ("reduce_scatter", "hierarchical_rs")
                else "psum")
        multiple = intra * inter if coll == "reduce_scatter" else intra
        n_pad = -(-n_params // multiple) * multiple
        wire_itemsize = gdtype.itemsize if gdtype is not None else 4
        if q_wire is not None:
            # quantized DCN: the slow hop is a different collective
            # shape with its own pricing; ICI keeps the lossless ring
            hops = hierarchical_exchanged_bytes(
                n_pad * wire_itemsize, intra, inter, coll)
            hops["dcn"] = quantized_hop_bytes(
                n_pad // intra, inter, coll, q_wire)
        else:
            dcn_itemsize = (comm.dcn_grad_dtype.itemsize
                            if comm.dcn_grad_dtype is not None
                            else wire_itemsize)
            hops = hierarchical_exchanged_bytes(
                n_pad * wire_itemsize, intra, inter, coll,
                dcn_n_bytes=n_pad // intra * dcn_itemsize)
        fields["exchanged_grad_bytes"] = hops["ici"] + hops["dcn"]
        fields["exchanged_dcn_bytes"] = hops["dcn"]
        fields["exchanged_ici_bytes"] = hops["ici"]
        fields["exchanged_bytes"] = fields["exchanged_grad_bytes"]
        if coll == "reduce_scatter":
            # params rebuild: the sharded update all-gathers the PACKED
            # flat params vector (tree_pack's concatenate promotes to
            # one dtype — f32 on the bench models)
            p_hops = hierarchical_exchanged_bytes(n_pad * 4, intra,
                                                  inter, "all_gather")
            fields["exchanged_bytes"] += p_hops["ici"] + p_hops["dcn"]
            fields["exchanged_dcn_bytes"] += p_hops["dcn"]
            fields["exchanged_ici_bytes"] += p_hops["ici"]
        return fields
    if is_quantized_dtype(gdtype):
        # flat quantized exchange: all_gather of codewords (allreduce)
        # or all_to_all of segments (reduce-scatter update), priced at
        # the 1-byte wire
        coll = "reduce_scatter" if exchange == "reduce_scatter" else "psum"
        grad = quantized_hop_bytes(n_params, size, coll, gdtype)
        fields["exchanged_grad_bytes"] = grad
        fields["exchanged_bytes"] = grad + (
            exchanged_bytes(param_bytes, size, "all_gather")
            if exchange == "reduce_scatter" else 0)
    elif exchange == "reduce_scatter":
        grad = exchanged_bytes(grad_bytes, size, "reduce_scatter")
        fields["exchanged_bytes"] = grad + exchanged_bytes(
            param_bytes, size, "all_gather")
        fields["exchanged_grad_bytes"] = grad
    else:
        fields["exchanged_bytes"] = exchanged_bytes(grad_bytes, size,
                                                    "psum")
        fields["exchanged_grad_bytes"] = fields["exchanged_bytes"]
    return fields


def _timed_steps(do_steps, calls, trials=None, on_first=None):
    """Shared timing discipline for every bench mode: one trace+compile
    call, 1 warmup call, then best-of-``trials`` over ``calls``
    dispatches per trial — each trial synced by a real device->host
    value fetch (float(loss)): the host cannot hold the number before
    the device has produced it, whatever a backend's
    ``block_until_ready`` does.  ``on_first(elapsed, compile_s)`` fires
    right after the first trial so the caller can print a preliminary
    result.  Returns (best_seconds, compile_s)."""
    if trials is None:
        trials = int(os.environ.get("BENCH_TRIALS", "1"))
    t0 = time.perf_counter()
    loss = do_steps()  # first call: trace + XLA compile
    float(loss)
    compile_s = time.perf_counter() - t0
    loss = do_steps()  # warmup dispatch
    float(loss)
    best = None
    for i in range(trials):
        start = time.perf_counter()
        for _ in range(calls):
            loss = do_steps()
        float(loss)
        elapsed = time.perf_counter() - start
        best = elapsed if best is None else min(best, elapsed)
        if i == 0 and on_first is not None:
            on_first(elapsed, compile_s)
    return best, compile_s


def _step_hbm_stats(opt):
    """``memory_analysis`` of the step program just benchmarked: the
    donation proof (params + opt-state alias bytes) and the
    peak-resident figure for the result row.  AOT re-lower + compile
    from shape specs — a hit in the persistent compile cache.  None
    when the knob is off or the backend implements no analysis."""
    if os.environ.get("BENCH_MEMSTATS", "1") != "1":
        return None
    from chainermn_tpu.core.optimizer import memory_stats_dict
    return memory_stats_dict(opt.compiled_step_memory_analysis())


def _run_bench_transformer():
    """Auxiliary bench mode (BENCH_MODEL=transformer): GPT-2-small-class
    causal LM, tokens/sec/chip + MFU.  No reference-era baseline exists
    for this vertical (vs_baseline=null); recorded for the long-context
    story alongside the headline ResNet number."""
    import jax
    import jax.numpy as jnp

    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import Adam
    from chainermn_tpu.models import TransformerLM

    per_chip_bs = int(os.environ.get("BENCH_BS", str(DEFAULT_TF_BS)))
    seq_len = int(os.environ.get("BENCH_SEQ", str(DEFAULT_SEQ)))
    n_steps = _steps(DEFAULT_TF_STEPS)
    exchange, bucket_mb = _exchange_config()
    exchange_info = {"exchange": exchange, "bucket_mb": bucket_mb}
    d_model = int(os.environ.get("BENCH_D_MODEL",
                                 str(DEFAULT_TF_D_MODEL)))
    n_layers = int(os.environ.get("BENCH_LAYERS",
                                  str(DEFAULT_TF_LAYERS)))
    n_vocab = int(os.environ.get("BENCH_VOCAB", str(DEFAULT_TF_VOCAB)))
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    # BENCH_REMAT_POLICY ("dots", "full", or a jax.checkpoint_policies
    # name): what the per-block remat recomputes — meaningless without
    # BENCH_REMAT=1, and silently ignoring it would mislabel a no-remat
    # measurement as a policy run (models/transformer.py · _remat_policy)
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "")
    if remat_policy and not remat:
        raise ValueError("BENCH_REMAT_POLICY is set but BENCH_REMAT is "
                         "not 1 — the policy would not be applied")
    remat_arg = (remat_policy or True) if remat else False
    n_heads = int(os.environ.get("BENCH_HEADS", "0")) or max(1, d_model // 64)
    if d_model % n_heads:
        raise ValueError(f"BENCH_D_MODEL={d_model} is not divisible by "
                         f"n_heads={n_heads}; set BENCH_HEADS explicitly")
    donate = os.environ.get("BENCH_DONATE", "1") == "1"

    devices = jax.devices()
    n_devices = len(devices)
    platform = devices[0].platform

    def mk_result(tokens_per_sec, compile_s, used_bs, hbm=None):
        per_chip = tokens_per_sec / n_devices
        result = {
            "metric": "transformer_lm_train_throughput",
            "value": round(per_chip, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": None,
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", platform),
            "n_devices": n_devices,
            "per_chip_batch": used_bs,
            "seq_len": seq_len,
            "d_model": d_model,
            "n_layers": n_layers,
            "n_vocab": n_vocab,
            "remat": remat,
            "remat_policy": remat_policy,
            "n_steps": n_steps,
            "donated": donate,
            "compile_s": round(compile_s, 1),
        }
        result.update(exchange_info)
        if hbm is not None:
            result["peak_hbm_bytes"] = hbm["peak_hbm_bytes"]
            result["hbm"] = hbm
        peak = _peak_tflops(devices)
        if peak:
            fpt = _transformer_flops_per_token(d_model, n_layers, n_vocab,
                                               seq_len)
            result["mfu"] = round(per_chip * fpt / (peak * 1e12), 4)
            result["peak_tflops_bf16"] = peak
        return result

    def run(per_chip_bs):
        model = TransformerLM(n_vocab=n_vocab, d_model=d_model,
                              n_heads=n_heads, n_layers=n_layers,
                              max_len=seq_len, seed=0, remat=remat_arg,
                              compute_dtype=jnp.bfloat16)
        inner = Adam(alpha=3e-4)
        inner.donate_params = donate  # BENCH_DONATE=0 = the A/B leg
        comm, opt = _make_dp_optimizer(inner, model, exchange, bucket_mb)
        exchange_info.update(_exchange_row_fields(model, comm, exchange))

        global_bs = per_chip_bs * n_devices
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(0, n_vocab, (global_bs, seq_len))
                        .astype(np.int32))
        t = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))

        def on_first(elapsed, compile_s):
            tps = n_steps * global_bs * seq_len / elapsed
            _emit(mk_result(tps, compile_s, per_chip_bs))

        best, compile_s = _timed_steps(lambda: opt.update(model, x, t),
                                       n_steps, on_first=on_first)
        return (n_steps * global_bs * seq_len / best, compile_s,
                _step_hbm_stats(opt))

    tokens_per_sec = None
    last_err = None
    used_bs = None
    for bs in (per_chip_bs, per_chip_bs // 2, per_chip_bs // 4):
        if bs < 1:
            break
        try:
            tokens_per_sec, compile_s, hbm = run(bs)
            used_bs = bs
            break
        except Exception as e:  # e.g. HBM OOM at the largest batch
            last_err = e
    if tokens_per_sec is None:
        raise last_err
    return mk_result(tokens_per_sec, compile_s, used_bs, hbm)


def _run_bench_moe():
    """BENCH_MODEL=moe: the Switch-FFN MoE transformer vertical (ISSUE
    12) — expert-parallel feed-forward blocks over the SAME communicator
    the data-parallel gradient exchange rides, so a hierarchical
    BENCH_EXCHANGE gives BOTH the two-level gradient sync and the
    two-stage (ici → dcn) token dispatch, and BENCH_GRAD_DTYPE's dcn
    entry compresses both slow-hop crossings.  Reports tokens/sec/chip
    plus the exchanged DISPATCH bytes per fabric per step (the
    activation-scaled wire bill the gradient rows cannot see), the
    committed off_host_dispatch_ratio, and the routing-honesty column
    moe_dropped_frac (capacity-cut fraction, from the model's own
    reported observation).

    Knobs: BENCH_MOE_EXPERTS (default = chip count; experts are
    rank-sharded one per device, so any other value on this mesh is a
    loud error — the knob exists for pods), BENCH_MOE_TOPK (1 = Switch
    top-1 routing, >1 = the GShard top-k mixture),
    BENCH_MOE_CAPACITY (capacity factor, default 1.25),
    BENCH_MOE_TWO_STAGE (''=topology-aware auto, 0 = the explicit
    flat-dispatch escape on a hierarchical comm — the structural A/B).
    CPU runs clamp to a labeled cpu_smoke row."""
    import jax
    import jax.numpy as jnp

    import chainermn_tpu as ct
    from chainermn_tpu.core import reporter
    from chainermn_tpu.core.optimizer import Adam
    from chainermn_tpu.models import MoETransformerLM

    devices = jax.devices()
    n_devices = len(devices)
    platform = devices[0].platform
    cpu_smoke = jax.default_backend() == "cpu"

    per_chip_bs = _env_int("BENCH_BS", 8)
    seq_len = _env_int("BENCH_SEQ", 512)
    d_model = _env_int("BENCH_D_MODEL", 512)
    n_layers = _env_int("BENCH_LAYERS", 6)
    n_vocab = _env_int("BENCH_VOCAB", DEFAULT_TF_VOCAB)
    n_steps = _steps(DEFAULT_TF_STEPS)
    topk = _env_int("BENCH_MOE_TOPK", 1)
    capacity_factor = _env_float("BENCH_MOE_CAPACITY", 1.25)
    experts = _env_int("BENCH_MOE_EXPERTS", n_devices)
    ts_env = os.environ.get("BENCH_MOE_TWO_STAGE", "")
    two_stage = None if ts_env == "" else ts_env == "1"
    donate = os.environ.get("BENCH_DONATE", "1") == "1"
    if cpu_smoke:
        # clamp: the CPU smoke must finish in seconds — labeled, and
        # never readable as an MoE measurement
        per_chip_bs = min(per_chip_bs, 2)
        seq_len = min(seq_len, 32)
        d_model = min(d_model, 64)
        n_layers = min(n_layers, 2)
        n_vocab = min(n_vocab, 512)
        n_steps = min(n_steps, 3)
    if experts != n_devices:
        raise ValueError(
            f"BENCH_MOE_EXPERTS={experts}: experts are rank-sharded one "
            f"per device and this mesh has {n_devices} — the knob exists "
            f"for larger pods, it cannot invent experts here")
    n_heads = _env_int("BENCH_HEADS", 0) or max(1, d_model // 64)
    exchange, bucket_mb = _exchange_config()

    comm, opt_exchange = _make_bench_communicator(exchange, bucket_mb)
    model = MoETransformerLM(
        n_vocab=n_vocab, ep_comm=comm, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, max_len=seq_len, seed=0,
        capacity_factor=capacity_factor, topk=topk, two_stage=two_stage,
        compute_dtype=jnp.bfloat16)
    inner = Adam(alpha=3e-4)
    inner.donate_params = donate
    comm, opt = _make_dp_optimizer(inner, model, exchange, bucket_mb,
                                   comm=comm, opt_exchange=opt_exchange)
    exchange_info = {"exchange": exchange, "bucket_mb": bucket_mb}
    exchange_info.update(_exchange_row_fields(model, comm, exchange))

    # dispatch wire bill (the activation-scaled bytes this vertical
    # exists to measure): tokens route per rank per layer through an
    # [E, C, D] capacity buffer at the bf16 compute dtype; priced by
    # the ONE surface the census identities are pinned against
    from chainermn_tpu.communicators._memory_utility import \
        moe_dispatch_exchanged_bytes
    from chainermn_tpu.parallel.moe import _resolve_two_stage, moe_capacity
    # the resolution rule and capacity formula the dispatch itself
    # applies — so the priced byte columns can never describe a
    # different exchange than the model runs (and an impossible
    # request fails here, before any compile, with the dispatch's own
    # error)
    resolved_two_stage = _resolve_two_stage(comm, two_stage)
    tokens_local = per_chip_bs * seq_len
    capacity = moe_capacity(tokens_local, experts, capacity_factor,
                            k=max(topk, 1))
    disp_elems = experts * capacity * d_model
    wire_itemsize = 2  # bf16 compute dtype
    dcn_wire = comm.dcn_grad_dtype
    hops = moe_dispatch_exchanged_bytes(
        disp_elems * wire_itemsize, comm.ici_size, comm.dcn_size,
        two_stage=resolved_two_stage,
        dcn_n_bytes=disp_elems * dcn_wire.itemsize
        if (resolved_two_stage and dcn_wire is not None) else None)
    moe_info = {
        "moe_experts": experts, "moe_topk": topk,
        "capacity_factor": capacity_factor,
        "moe_capacity": capacity,
        "two_stage": resolved_two_stage,
        "off_host_dispatch_ratio":
            (comm.dcn_size - 1) / comm.dcn_size
            if comm.hierarchy is not None else None,
        # per step = per layer bill × layers (dispatch + combine round
        # trip each); flat single-axis rows carry the joint figure
        "dispatch_bytes_ici": hops.get("ici", 0) * n_layers,
        "dispatch_bytes_dcn": hops.get("dcn", 0) * n_layers,
        "dispatch_bytes_world": hops.get("world", 0) * n_layers,
    }

    global_bs = per_chip_bs * n_devices
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, n_vocab, (global_bs, seq_len))
                    .astype(np.int32))
    t = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))

    def mk_result(tokens_per_sec, compile_s, dropped, hbm=None):
        per_chip = tokens_per_sec / n_devices
        result = {
            "metric": "moe_lm_train_throughput",
            "value": round(per_chip, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": None,  # greenfield: the reference had no MoE
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", platform),
            "n_devices": n_devices,
            "per_chip_batch": per_chip_bs,
            "seq_len": seq_len, "d_model": d_model,
            "n_layers": n_layers, "n_vocab": n_vocab,
            "n_steps": n_steps, "donated": donate,
            "moe_dropped_frac": dropped,
            "compile_s": round(compile_s, 1),
        }
        result.update(exchange_info)
        result.update(moe_info)
        if cpu_smoke:
            result["cpu_smoke"] = True
        if hbm is not None:
            result["peak_hbm_bytes"] = hbm["peak_hbm_bytes"]
            result["hbm"] = hbm
        return result

    # capture the model's own routing-honesty observation (reported
    # through the reporter on every update) alongside the timings —
    # observers must be registered on the scoped reporter or the
    # in-step report raises at trace time.  The value is READ (a
    # device->host sync) only outside the timed loop: a per-step
    # float() inside do_steps would serialize dispatches and deflate
    # tokens/sec relative to every other bench vertical.
    rep = reporter.Reporter()
    rep.add_observer("main", model)
    rep.add_observers("main", model.namedlinks(skipself=True))
    obs = {}

    def do_steps():
        with rep.scope(obs):
            return opt.update(model, x, t)

    def dropped():
        for key, value in obs.items():
            if key.endswith("moe_dropped"):
                return round(float(value), 4)
        return None

    def on_first(elapsed, compile_s):
        tps = n_steps * global_bs * seq_len / elapsed
        _emit(mk_result(tps, compile_s, dropped()))

    best, compile_s = _timed_steps(do_steps, n_steps, on_first=on_first)
    result = mk_result(n_steps * global_bs * seq_len / best, compile_s,
                       dropped(), _step_hbm_stats(opt))
    return result


def _run_bench_longcontext():
    """BENCH_MODEL=longcontext: the long-context feasibility claim as
    result rows.  Emits one row per T of the causal flash
    attention fwd+bwd (GPT-2-small head geometry, T = BENCH_LC_SEQS,
    default 16k and 32k) through the default FUSED backward, plus the
    contrast row: XLA attention at BENCH_LC_XLA_T (default 8192), which
    on a real chip fails to compile/fit its [B, H, T, T] score tensors
    while the flash rows run — that recorded failure IS the datum.  The
    summary line's value is the largest T the flash kernels completed.

    CPU fallback (smoke only): interpret mode with T clamped to ≤512 —
    mechanics validation, labeled ``interpreted`` so nobody reads the
    timings as the feasibility claim."""
    import importlib

    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")

    # default geometry matches the sweep/probe tools and the r5 baseline
    # row (B4 H12 D64 causal bf16) so the rows compare directly — and so
    # the XLA contrast leg's score tensors are genuinely unfittable
    B = _env_int("BENCH_LC_BS", 4)
    H = _env_int("BENCH_HEADS", 12)
    D = _env_int("BENCH_LC_HEAD_DIM", 64)
    seqs = tuple(int(t) for t in os.environ.get(
        "BENCH_LC_SEQS", "16384,32768").split(","))
    xla_t = _env_int("BENCH_LC_XLA_T", 8192)
    reps = _env_int("BENCH_LC_REPS", 10)

    devices = jax.devices()
    platform = devices[0].platform
    interp = jax.default_backend() == "cpu"
    if interp:
        # interpret-mode grad at long T is effectively unbounded (see
        # probe_perf.probe_flashcmp) — clamp hard, label loudly
        seqs = tuple(t for t in seqs if t <= 512) or (256,)
        xla_t = min(xla_t, 128)
        reps = 1

    scale = 1.0 / (D ** 0.5)
    bwd_mode = fa._flash_bwd_mode()
    peak = _peak_tflops(devices)

    def _qkvg(T, dtype=jnp.bfloat16):
        mk = lambda i: jnp.asarray(
            np.random.RandomState(i).normal(0, 1, (B, H, T, D))
            .astype(np.float32)).astype(dtype)
        return mk(0), mk(1), mk(2), jnp.ones((B, H, T, D), dtype)

    def common(row):
        row.update({"platform": platform,
                    "device_kind": getattr(devices[0], "device_kind",
                                           platform),
                    "B": B, "H": H, "head_dim": D,
                    "bwd_mode": bwd_mode})
        if interp:
            row["interpreted"] = True  # mechanics smoke, not perf
        return row

    rows = []
    max_ok_t = None
    compile_total = 0.0
    for T in seqs:
        # ragged-T guard: _adaptive_block falls back to 128 when no
        # candidate divides T, and grid = T // block would then silently
        # drop the tail rows — refuse the row instead of mismeasuring
        bq, bk = fa._flash_blocks(tq=T, tk=T)
        if T % min(bq, T) or T % min(bk, T):
            rows.append(common({
                "T": T,
                "error": f"tiles ({bq},{bk}) do not divide T={T}: pick "
                         "BENCH_LC_SEQS multiples of 128 (or set "
                         "CHAINERMN_TPU_FLASH_BLOCK_Q/K)"}))
            continue
        q, k, v, g = _qkvg(T)

        def step(q, k, v, g):
            out, lse = fa.flash_attention_fwd(
                q, k, v, causal=True, scale=scale, interpret=interp)
            dq, dk, dv = fa.flash_attention_bwd(
                q, k, v, out, lse, g, causal=True, scale=scale,
                interpret=interp)
            # scalar sync handle: a real device->host value fetch
            # (_timed_steps)
            return (dq[0, 0, 0, 0].astype(jnp.float32)
                    + dk[0, 0, 0, 0] + dv[0, 0, 0, 0])

        fn = jax.jit(step)
        try:
            best, compile_s = _timed_steps(
                lambda: fn(q, k, v, g), reps, trials=1)
            dt = best / reps
        except Exception as e:
            rows.append(common({"T": T,
                                "error": f"{type(e).__name__}: {e}"[:300]}))
            continue
        compile_total += compile_s
        flops = 4 * B * H * T * T * D * 3.5 / 2  # causal fwd+bwd model
        row = common({"T": T, "fwd_bwd_ms": round(dt * 1e3, 2),
                      "tflops": round(flops / dt / 1e12, 1),
                      "compile_s": round(compile_s, 1)})
        if peak:
            row["mfu"] = round(flops / dt / (peak * 1e12), 3)
        rows.append(row)
        max_ok_t = T
    for row in rows:
        _emit(dict(row, metric="longcontext_flash_row"))

    # the contrast leg: stock XLA attention at the T where the flash
    # path demonstrably runs — on chip this fails (scores tensor alone
    # at T=8192 is B·H·T²·4 bytes ≈ 12.9 GB fp32) and the recorded
    # failure is the artifact
    xla_row = {"T": xla_t}
    q, k, v, g = _qkvg(xla_t)

    def xla_step(q, k, v, g):
        def loss(q, k, v):
            return jnp.sum(fa.xla_attention(q, k, v, causal=True,
                                            scale=scale)
                           .astype(jnp.float32))
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return dq[0, 0, 0, 0] + dk[0, 0, 0, 0] + dv[0, 0, 0, 0]

    xfn = jax.jit(xla_step)
    try:
        best, compile_s = _timed_steps(
            lambda: xfn(q, k, v, g), max(1, reps // 2), trials=1)
        xla_row["fwd_bwd_ms"] = round(best / max(1, reps // 2) * 1e3,
                                      2)
        xla_row["compile_s"] = round(compile_s, 1)
    except Exception as e:
        xla_row["failed"] = f"{type(e).__name__}: {e}"[:300]
    _emit(common(dict(xla_row, metric="longcontext_xla_contrast")))

    result = common({
        "metric": "longcontext_flash_feasibility",
        "value": max_ok_t,
        "unit": "tokens_context",
        "vs_baseline": None,
        "n_devices": len(devices),
        "seqs": list(seqs),
        "rows": [{k: v for k, v in r.items()} for r in rows],
        "xla_contrast": xla_row,
        "compile_s": round(compile_total, 1),
    })
    if peak:
        result["peak_tflops_bf16"] = peak
    return result


def _run_bench_serving():
    """BENCH_MODEL=serving: the continuous-batching engine under a
    seeded synthetic OPEN-LOOP load (ISSUE 9).  Arrivals are a Poisson
    process at BENCH_SERVE_QPS spread over BENCH_SERVE_TENANTS tenants
    — generated up front from a fixed seed, independent of the service
    rate (open loop: a slow engine builds queue, it does not slow the
    offered load).  Reports tokens/sec (generated tokens over the
    measured window), p50/p99 PER-TOKEN latency (first token: arrival →
    production, includes queueing + prefill; later tokens: gap since
    the previous token of the same request, includes preemption
    stalls), p50/p99 QUEUE WAIT (the sum of the request's
    per-admission waits — arrival → first admission plus each
    eviction-requeue → re-admission dwell; the pure scheduling share
    of its latency, ISSUE 14), and page-pool occupancy (mean/max over
    decode steps).

    Round 14: the load is CHAT-SHAPED — every tenant re-sends a fixed
    ``BENCH_SERVE_PREFIX``-token system prompt ahead of a random tail —
    and the row carries the measured prefix economics
    (``prefix_hit_rate``, ``effective_capacity_x``, ``forks``), the
    disaggregation ship's ``transferred_page_bytes``
    (``BENCH_SERVE_DISAGG=1``) and the ``tp`` decode ways
    (``BENCH_SERVE_TP``).  ``BENCH_SERVE_PREFIX=0`` is the sharing-off
    A/B leg (engine prefix cache disabled).

    Two phases on ONE engine: a warmup pass first drives every prefill/
    decode bucket the load will touch (all jit compiles land here), then
    the engine is drained and the measured load runs against warm
    programs — the trace counters are asserted flat across the
    measured phase.

    CPU rehearsal (``JAX_PLATFORMS=cpu``, smoke only): the model and
    load CLAMP to a seconds-scale configuration and the row is labeled
    ``cpu_smoke: true`` — mechanics validation, never a serving
    number."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.serving import Request, ServingEngine

    devices = jax.devices()
    platform = devices[0].platform
    cpu_smoke = jax.default_backend() == "cpu"

    qps = _env_float("BENCH_SERVE_QPS", 16.0)
    tenants = _env_int("BENCH_SERVE_TENANTS", 4)
    n_requests = _env_int("BENCH_SERVE_REQUESTS", 64)
    max_new = _env_int("BENCH_SERVE_MAX_NEW", 32)
    prompt_max = _env_int("BENCH_SERVE_PROMPT", 64)
    max_batch = _env_int("BENCH_SERVE_MAX_BATCH", 8)
    page_size = _env_int("BENCH_SERVE_PAGE", 16)
    num_pages = _env_int("BENCH_SERVE_PAGES", 256)
    # round-14 scale-out knobs: the chat-shaped load (per-tenant shared
    # system prompt — what prefix sharing exists for), the
    # disaggregated prefill/decode split, and tensor-parallel decode
    prefix_len = _env_int("BENCH_SERVE_PREFIX", 16)
    disagg = os.environ.get("BENCH_SERVE_DISAGG", "0") == "1"
    tp = _env_int("BENCH_SERVE_TP", 1)
    # round-20 knobs (ISSUE 20): BENCH_SERVE_SPEC_K=K turns on
    # speculative decoding (n-gram self-draft, K proposals verified in
    # one dispatch — bit-identical tokens, fewer dispatches);
    # BENCH_SERVE_CHUNK=C turns on chunked prefill AND switches the
    # load to mixed short/long — every fourth request carries a LONG
    # prompt (up to 4x BENCH_SERVE_PROMPT) that admits in C-token
    # chunks between decode steps, which is exactly the head-of-line
    # blocking the p99 column measures
    spec_k = max(0, _env_int("BENCH_SERVE_SPEC_K", 0))
    chunk_env = max(0, _env_int("BENCH_SERVE_CHUNK", 0))
    long_factor = 4 if chunk_env else 1
    # round-16 fleet knobs (ISSUE 15): BENCH_SERVE_REPLICAS > 1 serves
    # through a ReplicaFleet behind the router; BENCH_FLEET_KILL_AT=K
    # preempts the highest replica at decode step K (its in-flight
    # sequences reroute — zero drops) and a cold replica then joins via
    # the multicast-tree weight sync (weight_sync_s measures it)
    from chainermn_tpu.serving.fleet import fleet_mode as _fleet_mode
    replicas = max(1, _env_int("BENCH_SERVE_REPLICAS", 1))
    if not _fleet_mode():
        replicas = 1   # CHAINERMN_TPU_FLEET=off: single-engine hatch
    fleet_kill_at = _env_int("BENCH_FLEET_KILL_AT", -1)
    # round-17 diurnal scenario (ISSUE 16): BENCH_DIURNAL=1 modulates
    # the arrival rate sinusoidally — λ(t) = qps·(1 + amp·sin(2πt/T))
    # — and runs a CapacityBroker over a synthetic training group next
    # to the fleet: the peak trips the hysteresis policy's +1 and a
    # training rank CONVERTS into a serving replica; the trough trips
    # the -1 and it retires back.  The row's conversions /
    # role_transfers / convert_s columns measure the transfers.
    diurnal = os.environ.get("BENCH_DIURNAL", "0") == "1"
    if not _fleet_mode():
        diurnal = False   # no fleet to grow: nothing to convert into
    diurnal_period = _env_float("BENCH_DIURNAL_PERIOD", 8.0)
    diurnal_amp = _env_float("BENCH_DIURNAL_AMP", 0.8)
    diurnal_world = max(2, _env_int("BENCH_DIURNAL_WORLD", 2))
    d_model = _env_int("BENCH_D_MODEL", 256)
    n_layers = _env_int("BENCH_LAYERS", 4)
    n_vocab = _env_int("BENCH_VOCAB", 8192)
    n_heads = _env_int("BENCH_HEADS", 0) or max(1, d_model // 64)
    if cpu_smoke:
        # clamp: the CPU interpret smoke must finish in seconds — it is
        # labeled
        n_requests = min(n_requests, 12)
        max_new = min(max_new, 8)
        prompt_max = min(prompt_max, 24)
        d_model = min(d_model, 64)
        n_layers = min(n_layers, 2)
        n_vocab = min(n_vocab, 512)
        n_heads = max(1, d_model // 32)
        num_pages = min(num_pages, 64)
        # keep the chunk threshold below the clamped long prompts so
        # the smoke actually exercises chunked admission
        if chunk_env:
            chunk_env = min(chunk_env, 16)
    if cpu_smoke:
        long_factor = min(long_factor, 2)
    # the shared prefix must leave room for a per-request tail
    prefix_len = max(0, min(prefix_len, prompt_max - 8))
    long_max = prompt_max * long_factor
    max_context = 1
    while max_context < long_max + max_new:
        max_context *= 2
    # chunk size: page-multiple (the engine's admission contract),
    # bounded by the context
    chunk_tokens = None
    if chunk_env:
        chunk_tokens = min(max(page_size,
                               (chunk_env // page_size) * page_size),
                           max_context)

    model = TransformerLM(n_vocab=n_vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          max_len=max_context, seed=0,
                          compute_dtype=jnp.bfloat16)

    def _build_engine(rid=0):
        return ServingEngine(model, num_pages=num_pages,
                             page_size=page_size, max_batch=max_batch,
                             max_context=max_context,
                             max_queue=n_requests + max_batch,
                             prefix_cache=prefix_len > 0, disagg=disagg,
                             tp=tp, spec_k=spec_k,
                             chunk_tokens=chunk_tokens)

    broker = None
    if replicas > 1 or diurnal:
        from chainermn_tpu.serving import ReplicaFleet
        scale_policy = None
        if diurnal:
            from chainermn_tpu.serving.fleet import QueueDepthScalePolicy
            scale_policy = QueueDepthScalePolicy(
                scale_up_depth=_env_float("BENCH_DIURNAL_UP", 8),
                scale_down_depth=_env_float("BENCH_DIURNAL_DOWN", 0),
                min_replicas=1,
                max_replicas=replicas + diurnal_world - 1)
        fleet = ReplicaFleet(engine_factory=_build_engine,
                             replicas=replicas,
                             scale_policy=scale_policy)
        if fleet_kill_at >= 0:
            # seeded kill-under-load: the HIGHEST replica preempts at
            # that decode step (deterministic — the same discipline as
            # the elastic BENCH_PREEMPT_RANK leg)
            fleet.replicas[max(fleet.replicas)].kill_at = fleet_kill_at
        if diurnal:
            # the diurnal scenario's training side is synthetic (this
            # is a single-host bench): diurnal_world ranks sit in a
            # LocalTrainGroup and the broker EXECUTES the policy's
            # decisions as real role transfers — the converted rank's
            # engine joins through the same tree-sync path a gloo
            # fleet uses, its compiles landing as conversion cost
            from chainermn_tpu.elastic import (CapacityBroker,
                                               LocalTrainGroup)
            broker = CapacityBroker(LocalTrainGroup(world=diurnal_world),
                                    fleet, engine_factory=_build_engine,
                                    min_world=1)
        target = fleet
        engines = [r.engine for r in fleet.live_replicas()]
    else:
        fleet = None
        engine = _build_engine()
        target = engine
        engines = [engine]

    rng = np.random.RandomState(0)
    # chat-shaped load: every tenant re-sends its own fixed system
    # prompt (prefix_len tokens) ahead of a random tail — the traffic
    # shape prefix sharing multiplies effective pool capacity on
    sys_prompts = [rng.randint(0, n_vocab, prefix_len).astype(np.int32)
                   for _ in range(tenants)]

    def synth_requests(n, t0):
        reqs, t = [], t0
        for _ in range(n):
            lam = qps
            if diurnal:
                # sinusoidal day: λ(t) = qps·(1 + amp·sin(2πt/T)),
                # floored so the trough still trickles arrivals — the
                # peak builds the queue that trips the +1, the trough
                # drains it for the -1
                lam = max(qps * 0.05,
                          qps * (1.0 + diurnal_amp * np.sin(
                              2.0 * np.pi * t / diurnal_period)))
            t += rng.exponential(1.0 / lam)
            ten = rng.randint(tenants)
            hi = prompt_max - prefix_len + 1
            if chunk_tokens is not None and len(reqs) % 4 == 3:
                # the mixed-load long leg: a prompt past the chunk
                # threshold, admitted in chunks between decode steps
                hi = long_max - prefix_len + 1
            tail = rng.randint(
                0, n_vocab, rng.randint(4, hi)).astype(np.int32)
            reqs.append(Request(
                np.concatenate([sys_prompts[ten], tail]),
                max_new_tokens=max_new,
                tenant=f"tenant{ten}",
                arrival_time=t))
        return reqs

    # -- warmup: compile every bucketed program BEFORE the window (the
    # engine's never-retrace contract needs all buckets pre-traced)
    t0 = time.perf_counter()
    for e in engines:
        e.warmup()
    compile_s = time.perf_counter() - t0
    traces_before = sum(e.prefill_traces + e.decode_traces
                        + e.spec_traces + e.chunk_traces
                        for e in engines)

    # -- measured open-loop window
    for req in synth_requests(n_requests, 0.0):
        target.submit(req)
    occ, cap_x, steps = [], [], 0
    joined = False
    base = time.monotonic()
    while (fleet.pending() if fleet is not None
           else engine.running or engine.prefilling
           or engine.scheduler.pending()):
        st = target.step(now=time.monotonic() - base)
        if broker is not None and st.get("scale_decision"):
            # auto-apply INSIDE the loop: the -1 fires mid-drain (the
            # hysteresis policy disarms after answering, and a
            # post-drain read returns 0) so the decision must be
            # executed the step it surfaces
            broker.apply(st["scale_decision"],
                         now=time.monotonic() - base)
        if fleet is not None and fleet.sheds and not joined:
            # scale back after the kill: a COLD replica joins mid-load
            # and syncs weights over the multicast tree — weight_sync_s
            # is the row's cold-start cost column (its compiles are
            # cold-start cost too, outside the initial engines'
            # never-retrace window)
            fleet.join()
            joined = True
        if st["decoded"] == 0 and st["admitted"] == 0:
            # open-loop idle tick: nothing arrived yet — wait for the
            # load, don't spin (idle ticks are not decode steps and
            # must not dilute the occupancy series)
            time.sleep(0.002)
            continue
        occ.append(st["occupancy"])
        cap_x.append(st["capacity_x"])
        steps += 1
    elapsed = time.monotonic() - base

    completed = (fleet.completed if fleet is not None
                 else engine.completed)
    all_engines = engines if fleet is None else \
        [r.engine for r in fleet.replicas.values() if not r.remote]

    lat = []
    for req in completed:
        if not req.token_times:
            continue
        lat.append(req.token_times[0] - req.arrival_time)
        lat.extend(np.diff(req.token_times))
    lat = np.asarray(lat) if lat else np.asarray([0.0])
    # scheduler health (ISSUE 14 satellite): queue wait = the SUM of
    # the request's per-admission waits (arrival -> first admission,
    # plus eviction-requeue -> re-admission) — the pure scheduling
    # share of its life, decode time excluded.  The same per-admission
    # values the observability histogram buckets when tracing is on;
    # the bench reports them exactly (per-request sums, not bucket
    # bounds), trace on or off.
    qwait = np.asarray([r.queue_wait_s for r in completed
                        if r.admit_time is not None
                        or r.queue_wait_s > 0] or [0.0])
    # token_times, not tokens: an evicted request's generated tokens
    # fold into its prompt (recompute on re-admit) but each kept its
    # one production timestamp — len(tokens) would deflate tokens/sec
    # exactly on the saturation rows where eviction happens
    n_tokens = sum(len(r.token_times) for r in completed)

    result = {
        "metric": "serving_engine_throughput",
        "value": round(n_tokens / elapsed, 1) if elapsed > 0 else None,
        "unit": "tokens/sec",
        "vs_baseline": None,   # greenfield: the reference had no serving
        "platform": platform,
        "device_kind": getattr(devices[0], "device_kind", platform),
        "n_devices": len(devices),
        "p50_token_latency_ms": round(float(np.percentile(lat, 50)) * 1e3,
                                      2),
        "p99_token_latency_ms": round(float(np.percentile(lat, 99)) * 1e3,
                                      2),
        "p50_queue_wait_ms": round(float(np.percentile(qwait, 50)) * 1e3,
                                   2),
        "p99_queue_wait_ms": round(float(np.percentile(qwait, 99)) * 1e3,
                                   2),
        "page_occupancy_mean": round(float(np.mean(occ)), 3) if occ
        else 0.0,
        "page_occupancy_max": round(float(np.max(occ)), 3) if occ
        else 0.0,
        "qps": qps, "tenants": tenants, "requests": n_requests,
        "completed": len(completed),
        "generated_tokens": int(n_tokens),
        "evictions": sum(e.evictions for e in all_engines),
        "decode_steps": steps,
        "max_batch": max_batch, "page_size": page_size,
        "num_pages": num_pages, "max_context": max_context,
        "d_model": d_model, "n_layers": n_layers, "n_vocab": n_vocab,
        "attn_mode": engines[0].mode,
        "page_dtype": str(engines[0].kv.dtype),
        # round-14 scale-out surface: the chat-shaped load's measured
        # prefix economics, the disagg ship's wire bytes, and tp
        "prefix_tokens": prefix_len,
        "prefix_hit_rate": round(
            sum(e.prefix_hits for e in all_engines)
            / max(1, sum(e.admissions for e in all_engines)), 3),
        "prefix_matched_tokens": int(sum(e.prefix_tokens_matched
                                         for e in all_engines)),
        "forks": sum(e.forks for e in all_engines),
        "effective_capacity_x": round(float(np.mean(cap_x)), 3)
        if cap_x else 1.0,
        "effective_capacity_x_max": round(float(np.max(cap_x)), 3)
        if cap_x else 1.0,
        "disagg": engines[0].disagg,
        "transferred_page_bytes": int(sum(e.transferred_page_bytes
                                          for e in all_engines)),
        "tp": engines[0].tp,
        # round-20 surface (ISSUE 20): the speculative economics — the
        # dispatch-count reduction IS accepted_tokens_per_dispatch; a
        # draft model's extra dispatches show up as draft_overhead —
        # and the chunked-prefill admission counters (present on EVERY
        # serving row; zeros when the knobs are off)
        "spec_k": spec_k,
        "chunk_tokens": chunk_tokens or 0,
        "spec_steps": sum(e.spec_steps for e in all_engines),
        "accepted_tokens_per_dispatch": round(
            sum(e.spec_emitted for e in all_engines)
            / max(1, sum(e.spec_lane_steps for e in all_engines)), 3),
        "spec_acceptance_rate": round(
            sum(e.spec_accepted for e in all_engines)
            / max(1, sum(e.spec_proposed for e in all_engines)), 3),
        "draft_overhead": round(
            sum(e.draft_dispatches for e in all_engines)
            / max(1, sum(e.spec_steps for e in all_engines)), 3),
        "chunked_admissions": sum(e.chunked_admissions
                                  for e in all_engines),
        "chunk_prefills": sum(e.chunk_prefills for e in all_engines),
        "compile_s": round(compile_s, 1),
        # the never-retrace contract, measured: bucket programs compiled
        # in warmup, zero traces during the window — counted over the
        # INITIAL replicas (a mid-window joiner compiles cold by
        # design; that cost is the join's, not the window's)
        "window_retraces": (sum(e.prefill_traces + e.decode_traces
                                + e.spec_traces + e.chunk_traces
                                for e in engines) - traces_before),
        # round-16 fleet surface (ISSUE 15): present on EVERY serving
        # row (single-engine rows backfill the fleet-less defaults, so
        # row consumers never key-miss)
        "replicas": replicas,
        "reroutes": fleet.reroutes if fleet is not None else 0,
        "weight_sync_s": round(fleet.weight_sync_s, 3)
        if fleet is not None else 0.0,
        "fleet_kill_at": fleet_kill_at if fleet is not None else -1,
        # round-17 capacity surface (ISSUE 16): present on EVERY
        # serving row (broker-less rows backfill zeros); any non-zero
        # conversions/role_transfers payload-fences the row from the
        # flagship cache — the measured world changed ROLE mid-window
        "conversions": broker.stats["conversions"]
        if broker is not None else 0,
        "role_transfers": broker.stats["role_transfers"]
        if broker is not None else 0,
        "convert_s": round(broker.stats["convert_s"], 3)
        if broker is not None else 0.0,
        "diurnal": diurnal,
        "diurnal_period": diurnal_period if diurnal else 0.0,
    }
    if cpu_smoke:
        # labeled loudly: mechanics smoke, not a serving measurement
        result["cpu_smoke"] = True
    return result


def _run_bench():
    import jax
    import jax.numpy as jnp

    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.models import Classifier, ResNet50

    # smoke-test knobs (defaults are the real benchmark configuration)
    per_chip_bs = int(os.environ.get("BENCH_BS", str(DEFAULT_BS)))
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    image_size = int(os.environ.get("BENCH_SIZE", str(DEFAULT_SIZE)))
    n_steps = _steps(DEFAULT_STEPS)
    exchange, bucket_mb = _exchange_config()
    exchange_info = {"exchange": exchange, "bucket_mb": bucket_mb}
    # BENCH_SCAN=K fuses K steps per dispatch via update_scan (one jit
    # containing a lax.scan) — isolates device throughput from host
    # dispatch latency; 0 = plain per-step update() dispatch.  The
    # input-pipeline mode defaults to K=4 (set BENCH_SCAN=0 to disable):
    # overlapped host feed + multi-step fused dispatch is the composed
    # configuration that mode exists to measure.
    _scan_env = os.environ.get("BENCH_SCAN", "")
    # activation layout: NHWC is the TPU-native convolution layout
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")
    # BENCH_INPUT_PIPELINE=1: feed each step from the REAL host pipeline
    # (uint8 synthetic rows → batch assembly in BENCH_ITERATOR workers →
    # DevicePrefetchIterator overlapped placement → in-graph input_norm
    # cast) instead of one pre-staged device batch — measures on chip how
    # much of the host feed the overlapped dispatch actually hides (the
    # delta vs the pre-staged flagship row is the exposed input cost,
    # also reported directly as input_stall_ms).  Composes with
    # BENCH_SCAN: K fed batches are stacked ON DEVICE per fused dispatch.
    input_pipeline = os.environ.get("BENCH_INPUT_PIPELINE", "0") == "1"
    scan_k = int(_scan_env) if _scan_env else (4 if input_pipeline else 0)
    # BENCH_ITERATOR: which host iterator assembles batches —
    # multiprocess (process pool + shared-memory slots, default),
    # native (C++ gather engine), thread (GIL-bound prefetch thread)
    iterator_kind = os.environ.get("BENCH_ITERATOR", "multiprocess")
    if input_pipeline and iterator_kind not in ("multiprocess", "native",
                                                "thread"):
        raise ValueError(f"unknown BENCH_ITERATOR={iterator_kind!r} "
                         "(multiprocess|native|thread)")
    if input_pipeline and iterator_kind == "native":
        # fail fast: before the OOM-backoff loop's model rebuilds
        from chainermn_tpu.utils.native import load_library
        if load_library() is None:
            raise RuntimeError(
                "BENCH_ITERATOR=native requires the native loader "
                "(g++ toolchain) — unavailable on this host")

    donate = os.environ.get("BENCH_DONATE", "1") == "1"

    devices = jax.devices()  # raises if the backend is unavailable
    n_devices = len(devices)
    platform = devices[0].platform
    device_kind = getattr(devices[0], "device_kind", platform)

    def mk_result(images_per_sec, compile_s, used_bs, feed_stats=None,
                  hbm=None):
        per_chip = images_per_sec / n_devices
        result = {
            "metric": "resnet50_imagenet_train_throughput",
            "value": round(per_chip, 2),
            "unit": "images/sec/chip",
            "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC, 3),
            "platform": platform,
            "device_kind": device_kind,
            "n_devices": n_devices,
            "per_chip_batch": used_bs,
            "image_size": image_size,
            "layout": layout,
            "remat": remat,
            "n_steps": n_steps,
            "input_pipeline": input_pipeline,
            "donated": donate,
            "compile_s": round(compile_s, 1),
            "fused_steps_per_dispatch": scan_k or 1,
        }
        result.update(exchange_info)
        if hbm is not None:
            result["peak_hbm_bytes"] = hbm["peak_hbm_bytes"]
            result["hbm"] = hbm
        if input_pipeline:
            result["iterator_kind"] = iterator_kind
            if feed_stats is not None:
                # consumer time blocked on the host feed, normalized to
                # one trial's worth of dispatches — 0 means the
                # overlapped feed fully hid batch assembly + H2D behind
                # device compute
                result["input_stall_ms"] = round(feed_stats(), 1)
        peak = _peak_tflops(devices)
        if peak:
            flops = _resnet50_train_flops_per_image(image_size)
            result["mfu"] = round(per_chip * flops / (peak * 1e12), 4)
            result["peak_tflops_bf16"] = peak
        return result

    def _make_input_feed(global_bs, shape, rng):
        """The real host pipeline: uint8 rows → BENCH_ITERATOR batch
        assembly → DevicePrefetchIterator overlapped H2D.  Returns the
        device-feed iterator (finalize() it after timing)."""
        from chainermn_tpu.dataset import (DevicePrefetchIterator,
                                           MultiprocessIterator,
                                           MultithreadIterator,
                                           TupleDataset, concat_examples)
        n_img = max(2 * global_bs * max(1, scan_k), 256)
        xs = rng.randint(0, 256, (n_img,) + shape[1:], dtype=np.uint8)
        ys = rng.randint(0, 1000, n_img).astype(np.int32)
        converter = None
        if iterator_kind == "native":
            from chainermn_tpu.dataset import NativeBatchIterator
            base = NativeBatchIterator((xs, ys), global_bs, seed=0)
        elif iterator_kind == "thread":
            base = MultithreadIterator(TupleDataset(xs, ys), global_bs,
                                       seed=0)
            converter = concat_examples
        else:
            base = MultiprocessIterator(
                TupleDataset(xs, ys), global_bs, seed=0, as_arrays=True,
                n_processes=_env_int("BENCH_LOADER_PROCS", 4),
                n_prefetch=2)
        return DevicePrefetchIterator(base, size=2, converter=converter)

    def run(per_chip_bs):
        global_bs = per_chip_bs * n_devices
        model = Classifier(ResNet50(
            n_classes=1000, remat=remat, compute_dtype=jnp.bfloat16,
            seed=0, layout=layout,
            input_norm="imagenet" if input_pipeline else None))
        inner = MomentumSGD(lr=0.1, momentum=0.9)
        inner.donate_params = donate  # BENCH_DONATE=0 = the A/B leg
        comm, opt = _make_dp_optimizer(inner, model, exchange, bucket_mb)
        exchange_info.update(_exchange_row_fields(model, comm, exchange))

        rng = np.random.RandomState(0)
        shape = ((global_bs, image_size, image_size, 3) if layout == "NHWC"
                 else (global_bs, 3, image_size, image_size))

        it = None
        feed_stats = None
        if input_pipeline:
            it = _make_input_feed(global_bs, shape, rng)
            stall_base = [0.0]
            dispatch_no = [0]
            feed_calls = [1]  # timed dispatches per trial (set below)

            def feed_stats():
                # stall accumulates across ALL timed trials while the
                # throughput is best-of-trials: normalize to one trial's
                # worth of dispatches (timed dispatches = total - the 2
                # compile/warmup calls) so BENCH_TRIALS>1 does not
                # inflate the reported exposed input cost
                timed = max(1, dispatch_no[0] - 2)
                return (it.input_stall_ms - stall_base[0]) \
                    * feed_calls[0] / timed

            def _count_dispatch():
                # rebase the stall baseline at the START of call 3 —
                # after trace+compile (call 1) and warmup (call 2) have
                # fully drained their cold-pipeline fill — so the
                # emitted input_stall_ms covers only the timed trials'
                # steady-state exposed input cost
                dispatch_no[0] += 1
                if dispatch_no[0] == 3:
                    stall_base[0] = it.input_stall_ms
            if scan_k:
                # fused multi-step dispatch over the REAL feed: pull K
                # batches (device-resident), stack on device, one
                # update_scan dispatch — host feed and collective fusion
                # compose instead of excluding each other
                def do_steps():
                    _count_dispatch()
                    batches = [it.next() for _ in range(scan_k)]
                    xs_ = jnp.stack([b[0] for b in batches])
                    ts_ = jnp.stack([b[1] for b in batches])
                    return opt.update_scan(model, xs_, ts_)[-1]
                steps_per_call, calls = scan_k, max(1, n_steps // scan_k)
            else:
                def do_steps():
                    _count_dispatch()
                    return opt.update(model, *it.next())
                steps_per_call, calls = 1, n_steps
            feed_calls[0] = calls
        else:
            x = jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
            t = jnp.asarray(rng.randint(0, 1000, global_bs)
                            .astype(np.int32))
            if scan_k:
                xs = jnp.broadcast_to(x, (scan_k,) + x.shape)
                ts = jnp.broadcast_to(t, (scan_k,) + t.shape)
                do_steps = lambda: opt.update_scan(model, xs, ts)[-1]
                steps_per_call, calls = scan_k, max(1, n_steps // scan_k)
            else:
                do_steps = lambda: opt.update(model, x, t)
                steps_per_call, calls = 1, n_steps

        def on_first(elapsed, compile_s):
            ips = calls * steps_per_call * global_bs / elapsed
            _emit(mk_result(ips, compile_s, per_chip_bs, feed_stats))

        try:
            if feed_stats is not None:
                # construction-time baseline; _count_dispatch refines it
                # once compile+warmup have drained their cold fill
                stall_base[0] = it.input_stall_ms
            best, compile_s = _timed_steps(do_steps, calls,
                                           on_first=on_first)
            return (calls * steps_per_call * global_bs / best, compile_s,
                    feed_stats, _step_hbm_stats(opt))
        finally:
            if it is not None:
                it.finalize()  # stop pool/threads before any OOM rebuild

    images_per_sec = None
    last_err = None
    used_bs = None
    for bs in (per_chip_bs, per_chip_bs // 2, per_chip_bs // 4):
        if bs < 1:
            break
        try:
            images_per_sec, compile_s, feed_stats, hbm = run(bs)
            used_bs = bs
            break
        except Exception as e:  # e.g. HBM OOM at the largest batch
            last_err = e
    if images_per_sec is None:
        raise last_err
    return mk_result(images_per_sec, compile_s, used_bs, feed_stats, hbm)


def _err_metric():
    model = os.environ.get("BENCH_MODEL", "resnet50")
    if model == "transformer":
        return ("transformer_lm_train_throughput", "tokens/sec/chip")
    if model == "longcontext":
        return ("longcontext_flash_feasibility", "tokens_context")
    if model == "serving":
        return ("serving_engine_throughput", "tokens/sec")
    if model == "moe":
        return ("moe_lm_train_throughput", "tokens/sec/chip")
    return ("resnet50_imagenet_train_throughput", "images/sec/chip")


_MODES = {
    "resnet50": _run_bench,
    "transformer": _run_bench_transformer,
    "longcontext": _run_bench_longcontext,
    "serving": _run_bench_serving,
    "moe": _run_bench_moe,
}


def main():
    """Run one mode; 0 on a result, 1 on any failure (after printing a
    machine-readable error line under the mode's metric)."""
    metric, unit = _err_metric()

    def fail(err):
        _emit({"metric": metric, "value": None, "unit": unit,
               "vs_baseline": None, "error": err})
        return 1

    bench_model = os.environ.get("BENCH_MODEL", "resnet50")
    if bench_model not in _MODES:
        return fail(f"unknown BENCH_MODEL={bench_model!r} "
                    f"({'|'.join(_MODES)})")
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        return fail(f"no TPU: JAX found {platform!r} (set "
                    "JAX_PLATFORMS=cpu for the labeled CPU rehearsal)")
    from chainermn_tpu.utils.compat import configure_persistent_cache
    configure_persistent_cache()
    try:
        result = _MODES[bench_model]()
    except Exception as e:  # noqa: BLE001 — the boundary: report, exit 1
        import traceback
        traceback.print_exc()
        return fail(f"{type(e).__name__}: {e}")
    _emit(result)  # final (possibly improved over the early emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
