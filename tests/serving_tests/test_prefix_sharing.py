"""Round-14 serving scale-out gates (ISSUE 13).

The three tentpole legs, parity-pinned:

* **copy-on-write prefix sharing** — a prefix-shared request's decode
  trajectory is bit-identical to its unshared solo run, INCLUDING
  across a fork-on-write, and the provider's trajectory is untouched
  by the borrower's fork (the COW correctness fact).  The suffix
  prefill's logits match the one-shot forward at fp32 atol 1e-5.
* **disaggregated prefill/decode** — the disagg-on engine's trajectory
  equals the single-mesh hatch (``CHAINERMN_TPU_SERVE_DISAGG=off``)
  exactly, with ``transferred_page_bytes`` metering the ship.
* **tensor-parallel decode** — tp=2 logits match the single-chip
  decode at fp32 atol 1e-5 (trajectory pinned equal end to end).

Plus the satellites: the never-retrace pin over the new per-slice
bucket grids (joins/leaves/forks/transfers, disagg on AND off) and the
eviction-livelock guard (typed ``EvictionStalledError`` when no victim
would free a page).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.core.link import extract_state
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import (BlockAllocator, EvictionStalledError,
                                   PagedKVCache, Request, RequestScheduler,
                                   ServingEngine, copy_page, decode_program,
                                   prefill_program, prefix_prefill_program)

VOCAB = 101


def _model(**kw):
    return TransformerLM(n_vocab=VOCAB, d_model=32, n_heads=2,
                         n_layers=2, max_len=128, seed=0, **kw)


def _oneshot(model, seq):
    return np.asarray(model.logits(jnp.asarray(
        np.asarray(seq, np.int32)[None])))[0]


def _chat_prompts(rng, shared_len=20, tails=(0, 9, 3)):
    """A provider + borrowers sharing a NON-page-aligned system prompt
    (default 20 tokens at S=8: 2 full pages + a 4-slot partial tail).
    The provider's prompt is exactly the system prompt (tail 0), so its
    registered partial tail page sits AT the borrowers' divergence
    point — the borrower path exercises the fork."""
    base = rng.randint(0, VOCAB, shared_len).astype(np.int32)
    return [np.concatenate([base, rng.randint(0, VOCAB, n)
                            .astype(np.int32)]) for n in tails]


def _run_engine(model, prompts, max_new=6, stagger=False, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("page_dtype", jnp.float32)
    eng = ServingEngine(model, **kw)
    if stagger:
        # provider first, decoding alone for two steps, THEN the
        # borrowers join — the provider has already written generated
        # tokens into its (shared) partial tail page when the borrower
        # forks it: the hardest COW interleaving
        eng.submit(Request(prompts[0], max_new_tokens=max_new))
        eng.step(now=0.0)
        eng.step(now=0.0)
        for p in prompts[1:]:
            eng.submit(Request(p, max_new_tokens=max_new))
    else:
        for p in prompts:
            eng.submit(Request(p, max_new_tokens=max_new))
    eng.drain(now=0.0)
    toks = {r.request_id: r.tokens for r in eng.completed}
    return eng, [toks[k] for k in sorted(toks)]


def test_shared_trajectory_bit_identical_across_fork():
    """THE acceptance pin: prefix-shared trajectories (provider AND
    borrowers) equal the unshared run token-for-token, across a
    fork-on-write into a page the provider was actively writing."""
    model = _model()
    prompts = _chat_prompts(np.random.RandomState(1))
    e_off, t_off = _run_engine(model, prompts, stagger=True,
                               prefix_cache=False)
    e_on, t_on = _run_engine(model, prompts, stagger=True,
                             prefix_cache=True)
    assert e_off.prefix_hits == 0
    assert e_on.prefix_hits == 2          # both borrowers hit
    assert e_on.forks >= 1                # the partial tail forked
    assert e_on.prefix_tokens_matched > 0
    assert t_on == t_off                  # bit-identical trajectories
    assert e_on.allocator.check()
    assert len(e_on.completed) == 3


def test_page_aligned_share_no_fork_and_capacity_multiplier():
    """A page-aligned system prompt shares without forking (full pages
    are immutable), and the effective-capacity multiplier reflects the
    sharing while the borrowers are live."""
    model = _model()
    rng = np.random.RandomState(2)
    prompts = _chat_prompts(rng, shared_len=16, tails=(6, 7, 8))
    e_off, t_off = _run_engine(model, prompts, stagger=True,
                               prefix_cache=False, max_new=8)

    eng = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                        max_context=64, page_dtype=jnp.float32,
                        prefix_cache=True)
    eng.submit(Request(prompts[0], max_new_tokens=8))
    eng.step(now=0.0)
    eng.step(now=0.0)
    for p in prompts[1:]:
        eng.submit(Request(p, max_new_tokens=8))
    eng.step(now=0.0)                     # borrowers admitted, live
    assert eng.prefix_hits == 2 and eng.forks == 0
    assert eng.capacity_multiplier() > 1.0
    assert eng.allocator.check()
    eng.drain(now=0.0)
    toks = {r.request_id: r.tokens for r in eng.completed}
    assert [toks[k] for k in sorted(toks)] == t_off


def test_suffix_prefill_logits_match_oneshot():
    """Program-level parity: share + fork + suffix prefill produce the
    same first-token logits as the one-shot forward (fp32 atol 1e-5),
    and the following decode steps stay on parity too."""
    model = _model()
    state = extract_state(model)
    rng = np.random.RandomState(3)
    base = rng.randint(0, VOCAB, 20).astype(np.int32)
    pa = base                            # provider: partial tail at 20
    pb = np.concatenate([base, rng.randint(0, VOCAB, 9).astype(np.int32)])
    blk = model.blocks[0].attn
    kv = PagedKVCache(2, 64, 8, model.serve_cache_entry(),
                      dtype=jnp.float32)
    alloc = BlockAllocator(64, 8)
    N = 64 // 8

    def bt(sid):
        row = np.zeros(N, dtype=np.int32)
        t = alloc.block_table(sid)
        row[:len(t)] = t
        return jnp.asarray(row)

    # provider: full prefill, then register
    La = len(pa)
    alloc.ensure("a", La + 1)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :La] = pa
    kv.k_pool, kv.v_pool, _ = prefill_program(
        model, state, kv.k_pool, kv.v_pool, jnp.asarray(toks),
        jnp.int32(La), bt("a"))
    alloc.register_prefix("a", tuple(int(t) for t in pa))

    # borrower: match (20 = 2 full + 4 partial), share, fork, suffix
    Lb = len(pb)
    pages, matched, n_full, partial = alloc.match_prefix(
        tuple(int(t) for t in pb), Lb - 1)
    assert matched == 20 and n_full == 2 and partial == 4
    alloc.share("b", pages)
    old, new = alloc.fork("b", n_full)
    assert old != new
    kv.k_pool, kv.v_pool = copy_page(kv.k_pool, kv.v_pool,
                                     jnp.int32(old), jnp.int32(new))
    alloc.ensure("b", Lb + 1)
    Ts = Lb - matched
    stoks = np.zeros((1, 16), np.int32)
    stoks[0, :Ts] = pb[matched:]
    kv.k_pool, kv.v_pool, logits = prefix_prefill_program(
        model, state, kv.k_pool, kv.v_pool, jnp.asarray(stoks),
        jnp.int32(Ts), jnp.int32(matched), bt("b"))
    ref = _oneshot(model, pb)
    np.testing.assert_allclose(np.asarray(logits), ref[Lb - 1],
                               atol=1e-5)

    # decode continues on parity THROUGH the forked page
    full = np.concatenate([pb, rng.randint(0, VOCAB, 4)
                           .astype(np.int32)])
    ref = _oneshot(model, full)
    for n in range(4):
        pos = Lb + n
        alloc.ensure("b", pos + 1)
        kv.k_pool, kv.v_pool, lg, _ = decode_program(
            model, state, kv.k_pool, kv.v_pool,
            jnp.asarray([full[pos]], jnp.int32) * 0 + int(full[pos]),
            jnp.asarray([pos], jnp.int32), bt("b")[None], mode="paged")
        np.testing.assert_allclose(np.asarray(lg)[0], ref[pos],
                                   atol=1e-5, err_msg=f"step {n}")
    assert alloc.check()


def test_warmup_covers_sharing_grid_no_retraces():
    """Satellite 2 (single-mesh half): after warmup, a chat-shaped load
    with hits AND forks triggers zero additional traces of any program
    — prefill, suffix prefill, fork copy, decode."""
    model = _model()
    eng = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                        max_context=64, page_dtype=jnp.float32,
                        prefix_cache=True)
    eng.warmup()
    counts = (eng.prefill_traces, eng.prefix_prefill_traces,
              eng.decode_traces, eng.fork_traces)
    assert counts == (len(eng.prefill_buckets),
                      len(eng.prefill_buckets),
                      len(eng.batch_buckets), 1)
    rng = np.random.RandomState(4)
    prompts = _chat_prompts(rng) + _chat_prompts(rng, shared_len=16)
    for i, p in enumerate(prompts):
        eng.submit(Request(p, max_new_tokens=3 + i % 3,
                           arrival_time=float(i)))
    t = 0.0
    while eng.running or eng.scheduler.pending():
        eng.step(now=t)
        t += 1.0
    assert eng.prefix_hits > 0 and eng.forks > 0
    assert (eng.prefill_traces, eng.prefix_prefill_traces,
            eng.decode_traces, eng.fork_traces) == counts


def test_eviction_livelock_guard():
    """Satellite 1: the victim policy accounts only uniquely-owned
    pages (escalating past all-shared youngsters) and raises the typed
    error when NO victim would free anything."""
    sched = RequestScheduler()
    alloc = BlockAllocator(8, 4)
    t = alloc.ensure(0, 8)               # two pages, both shared below
    alloc.share(1, t)

    class R:
        def __init__(self, rid):
            self.request_id = rid
    r0, r1 = R(0), R(1)

    # legacy signature (no allocator): plain youngest
    assert sched.pick_victim([r0, r1]) is r1
    # all-shared: typed livelock error instead of a futile eviction
    with pytest.raises(EvictionStalledError) as ei:
        sched.pick_victim([r0, r1], alloc)
    assert ei.value.n_running == 2
    # escalation: youngest is all-shared, next-youngest owns a unique
    # page -> it is the victim
    alloc.ensure(0, 9)                   # r0 grows a unique page
    assert sched.pick_victim([r0, r1], alloc) is r0
    assert sched.pick_victim([r1, r0], alloc) is r0


def test_eviction_of_provider_keeps_borrower_correct():
    """End-to-end churn: a tiny pool forces eviction while pages are
    shared; trajectories still equal the uncontended (big-pool,
    no-sharing) run — recompute-on-readmit composes with refcounts."""
    model = _model()
    rng = np.random.RandomState(5)
    prompts = _chat_prompts(rng, shared_len=16, tails=(6, 5, 7))
    _, t_ref = _run_engine(model, prompts, max_new=6,
                           prefix_cache=False, num_pages=64)
    e_small, t_small = _run_engine(model, prompts, max_new=6,
                                   prefix_cache=True, num_pages=10)
    assert t_small == t_ref
    assert e_small.allocator.check()


# -- disaggregated prefill/decode -------------------------------------------


def test_disagg_trajectory_equals_single_mesh_hatch(monkeypatch):
    """Tentpole (b): the disagg-on engine's trajectory is identical to
    the single-mesh hatch, the ship is metered, and the env hatch
    CHAINERMN_TPU_SERVE_DISAGG=off forces single-mesh even when the
    constructor asks for the split."""
    model = _model()
    prompts = _chat_prompts(np.random.RandomState(6))
    e_off, t_off = _run_engine(model, prompts, stagger=True, disagg=False)
    e_on, t_on = _run_engine(model, prompts, stagger=True, disagg=True)
    assert e_on.disagg and not e_off.disagg
    assert t_on == t_off
    # only the prefix MISS prefill ships pages; hits run on the decode
    # pool (they must read the shared pages in place)
    assert e_on.transfers >= 1
    assert e_on.transferred_page_bytes > 0
    assert e_off.transferred_page_bytes == 0
    # the env hatch wins over the constructor
    monkeypatch.setenv("CHAINERMN_TPU_SERVE_DISAGG", "off")
    e_hatch, t_hatch = _run_engine(model, prompts, stagger=True,
                                   disagg=True)
    assert not e_hatch.disagg and e_hatch.transferred_page_bytes == 0
    assert t_hatch == t_off
    monkeypatch.setenv("CHAINERMN_TPU_SERVE_DISAGG", "on")
    assert ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                         max_context=32).disagg


def test_disagg_warmup_covers_transfer_grid_no_retraces():
    """Satellite 2 (disagg half): warmup pre-compiles the per-slice
    bucket grids — prefill on the prefill slice, extract+insert per
    transfer page bucket, suffix prefill + decode on the decode slice —
    and the full load then retraces NOTHING."""
    model = _model()
    eng = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                        max_context=64, page_dtype=jnp.float32,
                        prefix_cache=True, disagg=True)
    eng.warmup()
    counts = (eng.prefill_traces, eng.prefix_prefill_traces,
              eng.decode_traces, eng.fork_traces, eng.transfer_traces)
    assert counts == (len(eng.prefill_buckets),
                      len(eng.prefill_buckets),
                      len(eng.batch_buckets), 1,
                      2 * len(eng.transfer_buckets))
    rng = np.random.RandomState(7)
    prompts = _chat_prompts(rng)
    for i, p in enumerate(prompts):
        eng.submit(Request(p, max_new_tokens=4, arrival_time=float(i)))
    t = 0.0
    while eng.running or eng.scheduler.pending():
        eng.step(now=t)
        t += 1.0
    assert eng.transfers >= 1 and eng.prefix_hits > 0
    assert (eng.prefill_traces, eng.prefix_prefill_traces,
            eng.decode_traces, eng.fork_traces,
            eng.transfer_traces) == counts


# -- tensor-parallel decode --------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_tp_decode_matches_single_chip():
    """Tentpole (c): tp=2 head-sharded pools — the engine trajectory
    equals tp=1 end to end, and the decode logits match at fp32 atol
    1e-5 (program-level, sharded vs unsharded pools)."""
    model = _model()
    prompts = _chat_prompts(np.random.RandomState(8))
    e1, t1 = _run_engine(model, prompts, tp=1)
    e2, t2 = _run_engine(model, prompts, tp=2)
    assert e2.tp == 2 and t2 == t1

    # program-level logits parity through the sharded pools
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    state = extract_state(model)
    blk = model.blocks[0].attn
    rng = np.random.RandomState(9)
    kv = PagedKVCache(2, 16, 8, model.serve_cache_entry(),
                      dtype=jnp.float32)
    prompt = rng.randint(0, VOCAB, 11).astype(np.int32)
    toks = np.zeros((1, 16), np.int32)
    toks[0, :11] = prompt
    bt = jnp.asarray(np.arange(16 // 8 * 4, dtype=np.int32)[:8])
    k, v, _ = prefill_program(model, state, kv.k_pool, kv.v_pool,
                              jnp.asarray(toks), jnp.int32(11), bt)
    args = (jnp.asarray([int(prompt[-1])], jnp.int32),
            jnp.asarray([11], jnp.int32), bt[None])
    _, _, lg_ref, _ = decode_program(model, state, k, v, *args,
                                     mode="paged")
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sh = model.serve_pool_sharding(mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    k_sh, v_sh = jax.device_put(k, sh), jax.device_put(v, sh)
    state_sh = jax.device_put(state, repl)
    _, _, lg_tp, _ = jax.jit(
        lambda s, kk, vv, t, p, b: decode_program(
            model, s, kk, vv, t, p, b, mode="paged", tp_mesh=mesh))(
        state_sh, k_sh, v_sh, *args)
    np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_ref),
                               atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_tp_validates_head_divisibility():
    model = _model()   # 2 heads
    with pytest.raises(ValueError):
        ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                      max_context=32, tp=3)
