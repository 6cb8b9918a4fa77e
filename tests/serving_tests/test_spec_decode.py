"""Speculative decoding + chunked prefill (ISSUE 20 tentpole).

The correctness contract that makes both features safe to ship: greedy
speculative decoding is BIT-IDENTICAL to vanilla greedy decode on
every lane — the verify argmax row is exactly what one-token decode
would have produced, so rejection truncates but never alters the
trajectory — and chunked prefill is indistinguishable from a one-shot
prefill (same pages, logits equal atol 1e-5 at ragged chunk
boundaries).  Covered: solo / batched-ragged / mid-stream-join parity,
parity across a forced same-point eviction, the self-draft
dispatch-count reduction (the perf claim pinned STRUCTURALLY:
ceil(budget / (K+1)) verify dispatches at 100% acceptance), a separate
draft model, chunk-vs-one-shot trajectory and logit parity, prompts
longer than the largest prefill bucket (the ``_bucket`` ValueError
satellite), mid-chunk eviction accounting, and the never-retrace
contract with the spec/chunk programs in the warmup set.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from chainermn_tpu.core.link import extract_state
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import (BlockAllocator, PagedKVCache, Request,
                                   ServingEngine, ngram_propose,
                                   prefill_program, prefix_prefill_program)

VOCAB = 101


def _model(seed=0, **kw):
    # single layer keeps tier-1 compile time down; the combined
    # spec+chunk parity test below re-runs at n_layers=2 so per-layer
    # pool indexing stays covered
    kw.setdefault("n_layers", 1)
    return TransformerLM(n_vocab=VOCAB, d_model=32, n_heads=2,
                         max_len=128, seed=seed, **kw)


def _engine(model, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 8)
    # two lanes keeps the per-engine compile count down (batch buckets
    # (1, 2)); the ragged-batch and mid-stream-join tests pass
    # max_batch=4 explicitly for four-lane coverage
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_context", 64)
    return ServingEngine(model, **kw)


def _serve(model, prompts, max_new=8, arrivals=None, **kw):
    eng = _engine(model, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(p, max_new_tokens=max_new,
                           arrival_time=0.0 if arrivals is None
                           else arrivals[i]))
    t = 0.0
    while eng.running or eng.prefilling or eng.scheduler.pending():
        eng.step(now=t)
        t += 1.0
    return eng


def _seqs(eng):
    """Final full sequences (original prompt + every generated token),
    keyed by the first prompt token — stable across eviction folding,
    which appends to the prompt but never touches its head."""
    return {int(r.prompt[0]): list(int(x) for x in r.prompt) + r.tokens
            for r in eng.completed}


def _prompts(rng, lengths):
    out = []
    for i, L in enumerate(lengths):
        p = rng.randint(0, VOCAB, L).astype(np.int32)
        p[0] = i   # distinct keys for _seqs
        out.append(p)
    return out


# -- speculative decoding: bit-identity on every lane ------------------------


@pytest.mark.parametrize("spec_k", [1, 4])
def test_spec_solo_bit_identical(spec_k):
    """One lane, every K: the speculative trajectory equals vanilla
    greedy token for token — acceptance only shortens the step count,
    never bends the sequence."""
    model = _model()
    p = _prompts(np.random.RandomState(spec_k), [9])[0]
    van = _serve(model, [p], max_new=10)
    spec = _serve(model, [p], max_new=10, spec_k=spec_k)
    assert _seqs(spec) == _seqs(van)
    assert spec.spec_steps > 0
    assert spec.spec_emitted == 9   # prefill emits token 1 of 10


def test_spec_batched_ragged_bit_identical():
    """Four ragged lanes share the verify batch; per-lane ``n_valid``
    clips each near-budget lane's span and every lane still lands on
    its vanilla trajectory."""
    model = _model()
    prompts = _prompts(np.random.RandomState(0), (4, 9, 14, 19))
    van = _serve(model, prompts, max_new=8, max_batch=4)
    spec = _serve(model, prompts, max_new=8, max_batch=4, spec_k=4)
    assert _seqs(spec) == _seqs(van)
    assert spec.spec_lane_steps >= spec.spec_steps > 0


def test_spec_mid_stream_join_bit_identical():
    """Continuous batching's defining event under speculation: lanes
    join while others are mid-verify (idle lanes ride the bucket with
    start = -1, their span writes dropping); trajectories match the
    vanilla run with the same staggered arrivals."""
    model = _model()
    prompts = _prompts(np.random.RandomState(1), (5, 8, 12, 6))
    arrivals = [0.0, 0.0, 3.0, 5.0]
    van = _serve(model, prompts, max_new=8, arrivals=arrivals,
                 max_batch=4)
    spec = _serve(model, prompts, max_new=8, arrivals=arrivals,
                  max_batch=4, spec_k=3)
    assert _seqs(spec) == _seqs(van)


def test_spec_parity_across_forced_same_point_eviction():
    """Pressure-driven eviction timing is load-dependent (a spec run
    reaches pressure at different steps than a vanilla run), so the pin
    forces the SAME eviction point in both: after three steps the
    youngest running lane is evicted by hand, folds its tokens, and
    recomputes on re-admit — final sequences still match."""
    model = _model()
    prompts = _prompts(np.random.RandomState(2), (6, 10, 15))

    def run(**kw):
        eng = _engine(model, **kw)
        for p in prompts:
            eng.submit(Request(p, max_new_tokens=10))
        t = 0.0
        for _ in range(3):
            eng.step(now=t)
            t += 1.0
        if eng._flight is not None:
            # the vanilla engine keeps a decode run in flight (PR 46): it
            # lands before a victim is picked, as in the capacity pass,
            # so the victim's newest token folds with the rest
            eng._land(lambda: t)
        eng._evict(eng.running[-1], t)
        while eng.running or eng.prefilling or eng.scheduler.pending():
            eng.step(now=t)
            t += 1.0
        assert eng.evictions == 1
        assert any(r.preemptions > 0 for r in eng.completed)
        return _seqs(eng)

    assert run(spec_k=4) == run()


def test_self_draft_accepts_everything_and_cuts_dispatches():
    """The dispatch-per-token reduction, pinned structurally: with the
    target as its own draft every proposal verifies, so each dispatch
    emits its full K+1 window and an 8-token decode tail costs exactly
    ceil(8 / 3) = 3 verify dispatches where vanilla pays 8 decode
    steps — same tokens, one third the dispatches."""
    model = _model()
    p = _prompts(np.random.RandomState(3), [8])[0]
    van = _serve(model, [p], max_new=9)
    spec = _serve(model, [p], max_new=9, spec_k=2, draft_model=model)
    assert _seqs(spec) == _seqs(van)
    # prefill emits token 1; the remaining 8 arrive in 3,3,2 windows
    assert spec.spec_steps == 3
    assert spec.spec_proposed == spec.spec_accepted > 0
    assert spec.spec_emitted == 8


def test_separate_draft_model_parity():
    """A draft with DIFFERENT weights proposes junk relative to the
    target; acceptance drops but the emitted trajectory is still the
    target's vanilla greedy — the verify argmax, not the draft, decides
    every token."""
    model = _model()
    draft = _model(seed=1)
    prompts = _prompts(np.random.RandomState(4), (6, 11))
    van = _serve(model, prompts, max_new=8)
    spec = _serve(model, prompts, max_new=8, spec_k=3, draft_model=draft)
    assert _seqs(spec) == _seqs(van)
    assert spec.draft_dispatches > 0
    assert spec.spec_accepted <= spec.spec_proposed


def test_spec_counters_measure_dispatch_economics():
    """The bench columns' sources: every verify dispatch emits at least
    one token (the pending token's argmax is always recorded), so
    accepted_tokens_per_dispatch = emitted / lane_steps >= 1.0 exactly
    when speculation pays for itself and == 1.0 at zero accepts."""
    model = _model()
    prompts = _prompts(np.random.RandomState(5), (5, 9))
    spec = _serve(model, prompts, max_new=8, spec_k=4)
    assert spec.spec_lane_steps >= spec.spec_steps > 0
    assert 0 <= spec.spec_accepted <= spec.spec_proposed
    atpd = spec.spec_emitted / spec.spec_lane_steps
    assert atpd >= 1.0


def test_ngram_self_draft_is_pure_host_lookup():
    """The default draft never dispatches: it is an n-gram suffix match
    over the lane's own history, padded with the last token when the
    history is short or matchless."""
    hist = [1, 2, 3, 1, 2, 3, 1, 2]
    assert list(ngram_propose(hist, 3)) == [3, 1, 2]   # continues the match
    assert list(ngram_propose([7], 2)) == [7, 7]       # degenerate history
    eng = _serve(_model(), _prompts(np.random.RandomState(6), [7]),
                 max_new=6, spec_k=3)
    assert eng.draft_dispatches == 0


# -- chunked prefill ---------------------------------------------------------


def test_chunked_prefill_matches_unchunked_trajectory():
    """Mixed short/long load: prompts above the chunk threshold admit
    in page-multiple chunks interleaved with decode; every request
    lands on the one-shot-prefill trajectory."""
    model = _model()
    prompts = _prompts(np.random.RandomState(7), (5, 20, 50))
    van = _serve(model, prompts, max_new=6)
    chunked = _serve(model, prompts, max_new=6, chunk_tokens=16)
    assert chunked.chunked_admissions >= 2    # the 20- and 50-token prompts
    assert chunked.chunk_prefills > chunked.chunked_admissions
    assert _seqs(chunked) == _seqs(van)


def test_chunk_boundary_logits_match_oneshot():
    """Driving the offset writer directly: a 37-token prompt prefilled
    in 16+16+5 chunks produces, at EVERY chunk boundary, the same
    logits row the one-shot forward puts at that position — atol 1e-5,
    including the ragged 5-token tail."""
    model = _model()
    state = extract_state(model)
    blk = model.blocks[0].attn
    kv = PagedKVCache(len(list(model.blocks)), 64, 8,
                      model.serve_cache_entry(), dtype=jnp.float32)
    alloc = BlockAllocator(64, 8)
    L, chunk = 37, 16
    full = np.random.RandomState(8).randint(0, VOCAB, L).astype(np.int32)
    ref = np.asarray(model.logits(jnp.asarray(full[None])))[0]
    alloc.ensure(0, L + 1)
    row = np.zeros(8, np.int32)               # max_context 64 / page 8
    t = alloc.block_table(0)
    row[:len(t)] = t
    bt = jnp.asarray(row)
    start = 0
    while start < L:
        n = min(chunk, L - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = full[start:start + n]
        if start == 0:
            k, v, logits = prefill_program(
                model, state, kv.k_pool, kv.v_pool, jnp.asarray(toks),
                jnp.int32(n), bt)
        else:
            k, v, logits = prefix_prefill_program(
                model, state, kv.k_pool, kv.v_pool, jnp.asarray(toks),
                jnp.int32(n), jnp.int32(start), bt)
        kv.k_pool, kv.v_pool = k, v
        np.testing.assert_allclose(
            np.asarray(logits), ref[start + n - 1], atol=1e-5,
            err_msg=f"chunk boundary at {start + n}")
        start += n


def test_prompt_longer_than_largest_bucket_serves():
    """The satellite pin: with chunking on, the prefill bucket set
    collapses to (chunk_tokens,) and ``_bucket``'s ValueError is
    unreachable for chunk-admitted prompts — a 50-token prompt (>> the
    16-token bucket) serves to completion on the vanilla trajectory."""
    model = _model()
    prompts = _prompts(np.random.RandomState(9), [50])
    eng = _serve(model, prompts, max_new=6, chunk_tokens=16)
    assert tuple(eng.prefill_buckets) == (16,)
    assert eng.chunked_admissions == 1
    assert _seqs(eng) == _seqs(_serve(model, prompts, max_new=6))


def test_mid_chunk_eviction_frees_pages_and_resets_cursor():
    """A mid-chunk victim holds chunk pages but has produced nothing:
    eviction frees every page (the allocator conserves), the requeue
    resets the chunk cursor to zero, and re-admission replays the whole
    prompt to the vanilla trajectory."""
    model = _model()
    prompts = _prompts(np.random.RandomState(10), [50])
    van = _serve(model, prompts, max_new=6)
    eng = _engine(model, chunk_tokens=16)
    eng.submit(Request(prompts[0], max_new_tokens=6))
    t = 0.0
    for _ in range(3):   # 50 tokens / 16-chunks: prefilling for >= 2 steps
        if eng.prefilling and eng.prefilling[0]._chunk_pos > 0:
            break
        eng.step(now=t)
        t += 1.0
    req = eng.prefilling[0]
    assert 0 < req._chunk_pos < 50     # genuinely MID-chunk
    assert eng.allocator.used_pages > 0
    eng._evict(req, t)
    assert req._chunk_pos == 0
    assert req.preemptions == 1
    assert eng.allocator.used_pages == 0 and eng.allocator.check()
    while eng.running or eng.prefilling or eng.scheduler.pending():
        eng.step(now=t)
        t += 1.0
    assert _seqs(eng) == _seqs(van)


def test_spec_plus_chunk_combined_parity():
    """Both features on at once — chunks interleave with verify steps
    and a long prompt joins lanes already speculating — still the
    vanilla trajectory on every lane."""
    model = _model(n_layers=2)   # multi-layer pool indexing coverage
    prompts = _prompts(np.random.RandomState(11), (5, 40, 9))
    arrivals = [0.0, 1.0, 2.0]
    van = _serve(model, prompts, max_new=8, arrivals=arrivals)
    both = _serve(model, prompts, max_new=8, arrivals=arrivals,
                  spec_k=3, chunk_tokens=16)
    assert both.spec_steps > 0 and both.chunked_admissions == 1
    assert _seqs(both) == _seqs(van)


# -- never-retrace -----------------------------------------------------------


def test_spec_and_chunk_never_retrace_after_warmup():
    """The bucketed-shapes contract extends to the round-20 programs:
    after warmup() has compiled the verify grid per batch bucket and
    the chunk grid per prefill bucket, a staggered load with joins,
    long chunked prompts and a forced evict/rejoin triggers ZERO
    additional traces of any program."""
    model = _model()
    eng = _engine(model, spec_k=3, chunk_tokens=16)
    eng.warmup()
    assert eng.spec_traces > 0 and eng.chunk_traces > 0
    frozen = (eng.prefill_traces, eng.decode_traces, eng.spec_traces,
              eng.chunk_traces)
    rng = np.random.RandomState(12)
    for i in range(6):
        eng.submit(Request(rng.randint(0, VOCAB, int(rng.randint(3, 50))),
                           max_new_tokens=4 + i, arrival_time=float(i)))
    t, evicted = 0.0, False
    while eng.running or eng.prefilling or eng.scheduler.pending():
        eng.step(now=t)
        t += 1.0
        if not evicted and len(eng.running) >= 2:
            eng._evict(eng.running[-1], t)   # an evict/rejoin cycle
            evicted = True
    assert len(eng.completed) == 6 and evicted
    assert (eng.prefill_traces, eng.decode_traces, eng.spec_traces,
            eng.chunk_traces) == frozen


def test_spec_k_env_hatch_and_validation():
    """CHAINERMN_TPU_SERVE_SPEC=off is the operational kill switch —
    construction-time, like the attention hatch — and negative K is a
    construction error."""
    model = _model()
    with pytest.raises(ValueError):
        _engine(model, spec_k=-1)
    import os
    os.environ["CHAINERMN_TPU_SERVE_SPEC"] = "off"
    try:
        eng = _engine(model, spec_k=4)
        assert eng.spec_k == 0
    finally:
        del os.environ["CHAINERMN_TPU_SERVE_SPEC"]
    with pytest.raises(ValueError):   # non-page-multiple chunk
        _engine(model, chunk_tokens=12)
    with pytest.raises(ValueError):   # chunk above max_context
        _engine(model, chunk_tokens=128)
