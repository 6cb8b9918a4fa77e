#!/usr/bin/env python3
"""Chip smoke: drive the trainer and the serving engine once on a TPU.

The quickest proof that the system still starts on the chip.  One
process; every phase goes through the entry points a user calls
(``create_communicator`` -> ``create_multi_node_optimizer`` ->
``update`` / ``Trainer.run`` for training, ``ServingEngine`` for
serving) at the full width of a model the repo supports, with seeded
random weights and data.  A phase that raises, or whose check fails,
ends the run with a non-zero exit: nothing here catches and carries on.

    python chip_smoke.py            # one chip: three default phases
    python chip_smoke.py --chips 4  # four chips: the data-parallel path
                                    # against its one-chip reference only

Lines before the last are smoke observations (seconds, bytes), never
benchmark results.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import math
import sys
import tempfile
import time
import warnings

import numpy as np

#: the d768 x 12 GPT-2-small-class LM
TRANSFORMER = dict(n_vocab=32768, d_model=768, n_heads=12, n_layers=12,
                   seq_len=1024, per_chip_batch=8)
#: the source paper's flagship (BASELINE.json): ResNet-50, ImageNet shapes
RESNET = dict(block_counts=(3, 4, 6, 3), n_classes=1000, image_size=224,
              per_chip_batch=64)
SERVE = dict(num_pages=256, page_size=16, max_batch=8, max_context=256,
             n_requests=8, prompt_lens=(16, 200), max_new_tokens=32)
#: --chips 4: same model, per-chip batch 2 x 4 chips vs batch 8 x 1 chip
DATA_PARALLEL = dict(TRANSFORMER, per_chip_batch=2)

#: |prefill+decode logits - one-shot logits| bound, bf16 compute, d768 x 12.
#: Measured on a TPU v5 lite (PR 21): max 0.0586 over 32 positions of
#: logits with |max| 5.19; the bound is ~1.7x that.
SERVE_LOGIT_ATOL = 0.1
#: 4-chip vs 1-chip loss trajectory, bf16 compute + f32 gradient exchange.
#: Measured on 4 x TPU v5 lite (PR 21): max relative difference 2.23e-5
#: over 3 steps; the bound is ~9x that (different reduction orders).
DP_LOSS_RTOL = 2e-4

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class _Events:
    """Counts of JAX's own trace / compile / persistent-cache events."""

    def __init__(self):
        self.counts = {_TRACE: 0, _COMPILE: 0, _CACHE_HIT: 0,
                       _CACHE_MISS: 0}

    def _on_event(self, event, *args, **kwargs):
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self):
        return dict(self.counts)

    def since(self, mark):
        return {k: v - mark[k] for k, v in self.counts.items()}


@contextlib.contextmanager
def _watch_events():
    from jax import monitoring
    ev = _Events()
    monitoring.register_event_duration_secs_listener(ev._on_event)
    monitoring.register_event_listener(ev._on_event)
    try:
        yield ev
    finally:
        monitoring.unregister_event_duration_listener(ev._on_event)
        monitoring.unregister_event_listener(ev._on_event)


def check(cond, message):
    if not cond:
        raise AssertionError(message)


def pallas_kernel_names(jaxpr):
    """Names of every ``pallas_call`` in a jaxpr and its sub-jaxprs."""
    names = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for p in eqn.params.values():
                for v in (p if isinstance(p, (tuple, list)) else (p,)):
                    sub = getattr(v, "jaxpr", None)
                    if sub is not None:
                        walk(getattr(sub, "jaxpr", sub))
                    elif hasattr(v, "eqns"):
                        walk(v)
    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return names


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


def _memory(device):
    """``peak_bytes_in_use`` / ``bytes_in_use`` of a device (None where
    the backend keeps no such statistics, i.e. the CPU rehearsal)."""
    stats = device.memory_stats()
    if stats is None:
        check(device.platform != "tpu", "TPU reports no memory_stats()")
        return {"peak_bytes_in_use": None, "bytes_in_use": None}
    return {"peak_bytes_in_use": stats["peak_bytes_in_use"],
            "bytes_in_use": stats["bytes_in_use"]}


def _no_fallback(caught):
    msgs = [str(w.message) for w in caught
            if str(w.message).startswith("flash attention:")]
    check(not msgs, f"a flash shape left the Pallas path: {msgs}")


def _report(phase, **fields):
    print(json.dumps({"smoke": phase, **fields}), flush=True)
    return fields


def _lm_batch(cfg, global_bs, seed):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    x = rng.randint(0, cfg["n_vocab"],
                    (global_bs, cfg["seq_len"])).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(np.roll(x, -1, axis=1))


def _make_lm(cfg, seed, max_len=None):
    import jax.numpy as jnp
    from chainermn_tpu.models import TransformerLM
    return TransformerLM(n_vocab=cfg["n_vocab"], d_model=cfg["d_model"],
                         n_heads=cfg["n_heads"], n_layers=cfg["n_layers"],
                         max_len=max_len or cfg["seq_len"], seed=seed,
                         compute_dtype=jnp.bfloat16)


def _check_flash_step(opt):
    """The lowered train step holds the Pallas forward and the fused
    backward by name — i.e. the XLA attention fallback was not taken.
    Returns the kernel names and the lowered step."""
    traced = opt.traced_step()
    names = set(pallas_kernel_names(traced.jaxpr))
    want = {"_flash_kernel_lse", "_flash_bwd_fused_kernel"}
    check(want <= names, f"train step kernels {sorted(names)} lack "
                         f"{sorted(want - names)}")
    lowered = traced.lower()
    if _on_tpu():
        check("tpu_custom_call" in lowered.as_text(),
              "no tpu_custom_call in the lowered train step")
    return sorted(names), lowered


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_transformer(cfg=TRANSFORMER, steps=5, seed=0):
    """TransformerLM under the multi-node optimizer: ``steps`` updates on
    one repeated seeded batch, each ended by fetching the loss."""
    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import Adam

    comm = ct.create_communicator("jax_ici")
    model = _make_lm(cfg, seed)
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(Adam(alpha=3e-4), comm) \
        .setup(model)
    x, t = _lm_batch(cfg, cfg["per_chip_batch"] * comm.size, seed)

    losses, step_s = [], []
    with warnings.catch_warnings(record=True) as caught, \
            _watch_events() as ev:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        losses.append(float(opt.update(model, x, t)))
        compile_s = time.perf_counter() - t0
        first = ev.snapshot()
        for _ in range(steps - 1):
            t0 = time.perf_counter()
            losses.append(float(opt.update(model, x, t)))
            step_s.append(time.perf_counter() - t0)
        later = ev.since(first)
    _no_fallback(caught)
    check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    ln_v = math.log(cfg["n_vocab"])
    check(abs(losses[0] - ln_v) < 0.15 * ln_v,
          f"first loss {losses[0]} not near ln(V)={ln_v:.3f}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(later[_TRACE] == 0 and later[_COMPILE] == 0
          and later[_CACHE_HIT] == 0,
          f"retrace/compile after step 1: {later}")
    kernels, _ = _check_flash_step(opt)
    return _report(
        "train/transformer", losses=losses, compile_s=compile_s,
        step_s=step_s, kernels=kernels,
        cache_hits=first[_CACHE_HIT], cache_misses=first[_CACHE_MISS],
        **_memory(comm.mesh.devices.flat[0]))


def train_resnet(cfg=RESNET, steps=3, seed=0):
    """ResNet (NHWC, bf16) under ``Trainer`` + ``StandardUpdater`` on one
    repeated seeded batch — the trainer loop itself runs on the chip."""
    import jax.numpy as jnp
    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import MomentumSGD
    from chainermn_tpu.models import Classifier
    from chainermn_tpu.models.resnet import ResNet
    from chainermn_tpu.training import StandardUpdater, Trainer

    comm = ct.create_communicator("jax_ici")
    model = Classifier(ResNet(list(cfg["block_counts"]),
                              n_classes=cfg["n_classes"],
                              compute_dtype=jnp.bfloat16, seed=seed,
                              layout="NHWC"))
    comm.bcast_data(model)
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.02, momentum=0.9), comm).setup(model)

    global_bs = cfg["per_chip_batch"] * comm.size
    size = cfg["image_size"]
    rng = np.random.RandomState(seed)
    images = rng.normal(0, 1, (global_bs, size, size, 3)) \
        .astype(np.float32)
    labels = rng.randint(0, cfg["n_classes"], global_bs).astype(np.int32)
    # one epoch == one batch, unshuffled: every iteration sees the same
    # batch, so the loss must fall
    it = ct.SerialIterator(ct.TupleDataset(images, labels), global_bs,
                           repeat=True, shuffle=False)
    bn = model.predictor.conv1.bn
    mean_before = np.asarray(bn.avg_mean).copy()

    losses, stamps, marks = [], [], []

    with tempfile.TemporaryDirectory() as out, _watch_events() as ev:
        def fetch_loss(trainer):
            losses.append(float(trainer.observation["main/loss"]))
            stamps.append(time.perf_counter())
            marks.append(ev.snapshot())

        trainer = Trainer(StandardUpdater(it, opt), (steps, "iteration"),
                          out=out)
        trainer.extend(fetch_loss, trigger=(1, "iteration"))
        t0 = time.perf_counter()
        trainer.run()
        later = ev.since(marks[0])
    check(len(losses) == steps, f"trainer ran {len(losses)}/{steps} steps")
    check(later[_COMPILE] == 0 and later[_CACHE_HIT] == 0,
          f"the step compiled again after step 1: {later}")
    check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(not np.allclose(np.asarray(bn.avg_mean), mean_before),
          "BN running mean did not move")
    return _report(
        "train/resnet50", losses=losses, compile_s=stamps[0] - t0,
        step_s=[b - a for a, b in zip(stamps, stamps[1:])],
        cache_hits=marks[0][_CACHE_HIT], cache_misses=marks[0][_CACHE_MISS],
        **_memory(comm.mesh.devices.flat[0]))


def _serve_requests(cfg, n_vocab, seed):
    from chainermn_tpu.serving import Request
    rng = np.random.RandomState(seed)
    lo, hi = cfg["prompt_lens"]
    # both ends of the range, the rest drawn between them
    lens = [lo, hi] + list(rng.randint(lo, hi + 1, cfg["n_requests"] - 2))
    return [Request(rng.randint(0, n_vocab, int(n)), cfg["max_new_tokens"],
                    request_id=i) for i, n in enumerate(lens)]


def _paged_logits(model, cfg, prompt, forced):
    """Logits of ``prefill_program`` then one ``decode_program`` step per
    token of ``forced``, through hand-held block tables — the engine's
    device programs without its scheduling, so every step's logits are
    observable (the harness of tests/serving_tests/test_decode_parity)."""
    import jax
    import jax.numpy as jnp
    from chainermn_tpu.core.link import extract_state
    from chainermn_tpu.serving import (BlockAllocator, PagedKVCache,
                                       decode_program, prefill_program)
    S = cfg["page_size"]
    n_entries = cfg["max_context"] // S
    kv = PagedKVCache(model.serve_cache_layers, 2 * n_entries, S,
                      model.serve_cache_entry(), dtype=model.compute_dtype)
    alloc = BlockAllocator(2 * n_entries, S)
    state = extract_state(model)
    prefill = jax.jit(functools.partial(prefill_program, model))
    decode = jax.jit(functools.partial(decode_program, model,
                                       mode="paged"))

    def bt_row():
        row = np.zeros(n_entries, dtype=np.int32)
        table = alloc.block_table(0)
        row[:len(table)] = table
        return jnp.asarray(row)

    L0 = len(prompt)
    alloc.ensure(0, L0 + 1)
    Tb = max(S, 1 << (L0 - 1).bit_length())
    tokens = np.zeros((1, Tb), dtype=np.int32)
    tokens[0, :L0] = prompt
    k, v, logits = prefill(state, kv.k_pool, kv.v_pool,
                           jnp.asarray(tokens), jnp.int32(L0), bt_row())
    rows = [np.asarray(logits)]
    for n, tok in enumerate(forced):
        pos = L0 + n
        alloc.ensure(0, pos + 1)
        k, v, logits, _ = decode(
            state, k, v, jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32), bt_row()[None])
        rows.append(np.asarray(logits)[0])
    return np.stack(rows)


def serve(model_cfg=TRANSFORMER, cfg=SERVE, seed=0,
          logit_atol=SERVE_LOGIT_ATOL):
    """A ``ServingEngine`` answering seeded requests of mixed length,
    then parity of one request against the plain one-shot forward.
    ``TransformerLM`` is the served model here; the other two,
    ``LatentMoELM`` and ``WindowMoELM``, are driven on the chip by their
    benchmark cells (``kimi-k2.6-serve-agent``,
    ``laguna-s-2.1-serve-repo``) and compiled for a described chip by
    ``tests/test_chip_compile.py``."""
    import jax
    import jax.numpy as jnp
    from chainermn_tpu.core.link import bind_state, extract_state
    from chainermn_tpu.serving import ServingEngine, prefill_program

    device = jax.devices()[0]
    model = _make_lm(model_cfg, seed, max_len=cfg["max_context"])
    requests = _serve_requests(cfg, model_cfg["n_vocab"], seed)
    prompts = [r.prompt.copy() for r in requests]

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        before_pools = _memory(device)["bytes_in_use"]
        engine = ServingEngine(
            model, num_pages=cfg["num_pages"], page_size=cfg["page_size"],
            max_batch=cfg["max_batch"], max_context=cfg["max_context"])
        with_pools = _memory(device)["bytes_in_use"]
        engine.warmup()
        warmed = _memory(device)["bytes_in_use"]
        compile_s = time.perf_counter() - t0

        def traces():
            return (engine.prefill_traces + engine.prefix_prefill_traces
                    + engine.decode_traces + engine.spec_traces
                    + engine.chunk_traces + engine.fork_traces)
        warm = traces()
        pool_before = engine.kv.k_pool
        for r in requests:
            engine.submit(r)
        t0 = time.perf_counter()
        engine.drain()
        run_s = time.perf_counter() - t0
        window_retraces = traces() - warm

        check(len(engine.completed) == len(requests),
              f"{len(engine.completed)}/{len(requests)} requests completed")
        check(all(len(r.tokens) == cfg["max_new_tokens"] for r in requests),
              f"token counts {[len(r.tokens) for r in requests]}")
        check(window_retraces == 0,
              f"{window_retraces} retraces after warm-up")
        check(engine.evictions == 0, "the pool was sized to never evict")

        # the prefill program holds the Pallas forward at every bucket
        state = extract_state(model)
        for Tb in engine.prefill_buckets:
            jaxpr = jax.make_jaxpr(
                functools.partial(prefill_program, model))(
                    state, engine.kv.k_pool, engine.kv.v_pool,
                    jax.ShapeDtypeStruct((1, Tb), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((engine.n_block_entries,),
                                         jnp.int32))
            names = pallas_kernel_names(jaxpr)
            check(names.count("_flash_kernel") == model_cfg["n_layers"],
                  f"prefill bucket {Tb}: kernels {names}")

        # donation: the pre-run pool buffer was consumed in place, and
        # the run left no second pool-sized buffer behind.  (Warm-up
        # itself adds device memory that is no array: the loaded
        # programs, ~250 MB for the 15 of them on a v5e.)
        mem = _memory(device)
        pool_bytes = engine.kv.k_pool.nbytes
        if _on_tpu():
            check(pool_before.is_deleted(), "the KV pools were not donated")
            check(with_pools - before_pools >= 2 * pool_bytes,
                  f"pool pair takes {with_pools - before_pools} bytes "
                  f"< 2 x {pool_bytes}")
            check(mem["bytes_in_use"] - warmed < pool_bytes,
                  f"{mem['bytes_in_use'] - warmed} more bytes live after "
                  f"the run: a second pool-sized buffer ({pool_bytes})")

        # parity for the longest request: the engine's greedy tokens and
        # the paged programs' logits against ONE plain causal forward
        # (padded to a flash tile; causality keeps the padding out)
        req = max(requests, key=lambda r: len(prompts[r.request_id]))
        prompt = prompts[req.request_id]
        full = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
        padded = np.zeros(cfg["max_context"], np.int32)
        padded[:len(full)] = full

        def oneshot(state, toks):
            with bind_state(model, state):
                return model.logits(toks)
        ref = np.asarray(jax.jit(oneshot)(state, jnp.asarray(padded[None])),
                         np.float32)[0]
        ref = ref[len(prompt) - 1:len(full) - 1]
        paged = _paged_logits(model, cfg, prompt, req.tokens[:-1])
    _no_fallback(caught)

    check(np.isfinite(paged).all() and np.isfinite(ref).all(),
          "non-finite logits")
    logit_err = float(np.max(np.abs(paged - ref)))
    check(logit_err <= logit_atol,
          f"paged vs one-shot logits differ by {logit_err} > {logit_atol}")
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * logit_atol
    agree = np.argmax(ref, axis=-1) == np.asarray(req.tokens)
    check(bool(np.all(agree[decided])),
          f"greedy tokens differ at decided positions: "
          f"{np.flatnonzero(decided & ~agree).tolist()}")
    n_tokens = sum(len(r.tokens) for r in requests)
    return _report(
        "serve", completed=len(engine.completed), tokens=n_tokens,
        compile_s=compile_s, run_s=run_s, s_per_token=run_s / n_tokens,
        decode_steps=engine.decode_steps, window_retraces=window_retraces,
        logit_max_abs_err=logit_err, logit_max_abs=float(np.abs(ref).max()),
        decided_positions=int(decided.sum()),
        agreeing_positions=int(agree.sum()), pool_bytes=pool_bytes,
        loaded_program_bytes=None if warmed is None else warmed - with_pools,
        **mem)


def data_parallel(devices, cfg=DATA_PARALLEL, steps=3, seed=0,
                  loss_rtol=DP_LOSS_RTOL):
    """The paper's subject at a real width: the data-parallel step over
    ``devices`` against the same model and seed on ONE device with the
    merged batch (the golden rule of ``__graft_entry__``)."""
    import jax
    import chainermn_tpu as ct
    from chainermn_tpu.core.optimizer import Adam

    n = len(devices)
    check(len({d.id for d in devices}) == n, "devices are not distinct")
    x, t = _lm_batch(cfg, cfg["per_chip_batch"] * n, seed)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        # the reference: plain optimizer, one device, merged batch
        golden = _make_lm(cfg, seed)
        gopt = Adam(alpha=3e-4).setup(golden)
        t0 = time.perf_counter()
        golden_losses = [float(gopt.update(golden, x, t))
                         for _ in range(steps)]
        golden_s = time.perf_counter() - t0
        del golden, gopt
        gc.collect()

        comm = ct.create_communicator("jax_ici", devices=devices)
        check(comm.size == n, f"communicator spans {comm.size} != {n}")
        model = _make_lm(cfg, seed)
        comm.bcast_data(model)
        opt = ct.create_multi_node_optimizer(Adam(alpha=3e-4), comm) \
            .setup(model)
        # the batch the step consumes, laid out as the step lays it out
        from jax.sharding import NamedSharding, PartitionSpec as P
        xs = jax.device_put(x, NamedSharding(comm.mesh, P(comm.axis_name)))
        ts = jax.device_put(t, NamedSharding(comm.mesh, P(comm.axis_name)))
        t0 = time.perf_counter()
        losses = [float(opt.update(model, xs, ts)) for _ in range(steps)]
        dp_s = time.perf_counter() - t0
    _no_fallback(caught)

    shard_devices = {s.device.id for s in xs.addressable_shards}
    check(shard_devices == {d.id for d in devices},
          f"batch shards sit on {sorted(shard_devices)}")
    check(all(s.data.shape[0] == cfg["per_chip_batch"]
              for s in xs.addressable_shards), "uneven batch shards")
    for p in model.params():
        on = {s.device.id for s in p.array.addressable_shards}
        check(on == shard_devices and p.array.is_fully_replicated,
              f"parameter {p.name} not replicated over the mesh: {on}")
    kernels, lowered = _check_flash_step(opt)
    check("all-reduce" in lowered.compile().as_text(),
          "no all-reduce in the compiled data-parallel step")
    check(all(math.isfinite(v) for v in losses + golden_losses),
          f"losses {losses} vs {golden_losses}")
    rel = float(np.max(np.abs(np.asarray(losses) - golden_losses)
                       / np.abs(golden_losses)))
    check(rel <= loss_rtol,
          f"{n}-device losses {losses} vs one-device {golden_losses}: "
          f"relative difference {rel} > {loss_rtol}")
    return _report(
        "train/data_parallel", n_devices=n, losses=losses,
        golden_losses=golden_losses, max_rel_diff=rel, kernels=kernels,
        golden_s=golden_s, dp_s=dp_s, **_memory(devices[0]))


# ---------------------------------------------------------------------------

def result_line(devices):
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the data-parallel path and its "
                         "one-chip reference, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    from chainermn_tpu.utils.compat import configure_persistent_cache
    print(json.dumps({"smoke": "start", "jax": jax.__version__,
                      "devices": len(devices),
                      "compile_cache": configure_persistent_cache()}),
          flush=True)
    t0 = time.perf_counter()
    with _watch_events() as ev:
        if args.chips == 4:
            check(len(devices) == 4, f"--chips 4 on {len(devices)} devices")
            data_parallel(devices)
        else:
            # each phase drops its arrays before the next starts
            for phase in (train_transformer, train_resnet, serve):
                phase()
                gc.collect()
    # a warm persistent cache shows as cache_hits with few backend compiles
    print(json.dumps({"smoke": "done", "wall_s": time.perf_counter() - t0,
                      "backend_compiles": ev.counts[_COMPILE],
                      "cache_hits": ev.counts[_CACHE_HIT],
                      "cache_misses": ev.counts[_CACHE_MISS]}), flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
