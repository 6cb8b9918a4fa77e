"""Decode parity: prefill + N paged decode steps == one-shot forward.

The whole serving engine is only correct if the paged path is
indistinguishable from running the full sequence through the training
forward: prefill writes the prompt's K/V into pages, each decode step
appends one token's K/V and attends through the block table, and the
logits after N steps must equal ``model.logits(prompt + tokens)`` at
position ``prompt+N-1`` — fp32 atol 1e-5 (bf16 pages: the documented
band in docs/serving.md).  Covered here: ragged prompt lengths, a
batched ragged decode, a mid-stream join (continuous batching's
defining event), the ``CHAINERMN_TPU_PAGED_ATTN=dense`` escape hatch
(parity AND trajectory equality), and the engine-level never-retrace
contract.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from chainermn_tpu.core.link import extract_state
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import (BlockAllocator, PagedKVCache, Request,
                                   ServingEngine, decode_program,
                                   prefill_program)

VOCAB = 101


def _model(**kw):
    return TransformerLM(n_vocab=VOCAB, d_model=32, n_heads=2,
                         n_layers=2, max_len=128, seed=0, **kw)


class Harness:
    """Drives the pure prefill/decode programs with hand-held block
    tables — the engine's device semantics without its scheduling, so
    logits are observable at every step."""

    def __init__(self, model, page_size=8, num_pages=64, max_context=64,
                 mode="paged", dtype=jnp.float32):
        self.model = model
        self.state = extract_state(model)
        self.kv = PagedKVCache(len(list(model.blocks)), num_pages,
                               page_size, model.serve_cache_entry(),
                               dtype=dtype)
        self.alloc = BlockAllocator(num_pages, page_size)
        self.N = max_context // page_size
        self.mode = mode

    def _bt(self, sid):
        row = np.zeros(self.N, dtype=np.int32)
        t = self.alloc.block_table(sid)
        row[:len(t)] = t
        return jnp.asarray(row)

    def prefill(self, sid, prompt, bucket=None):
        L0 = len(prompt)
        self.alloc.ensure(sid, L0 + 1)
        Tb = bucket or max(8, 1 << (L0 - 1).bit_length())
        tokens = np.zeros((1, Tb), dtype=np.int32)
        tokens[0, :L0] = prompt
        k, v, logits = prefill_program(
            self.model, self.state, self.kv.k_pool, self.kv.v_pool,
            jnp.asarray(tokens), jnp.int32(L0), self._bt(sid))
        self.kv.k_pool, self.kv.v_pool = k, v
        return np.asarray(logits)

    def decode(self, sids, toks, poss):
        for sid, p in zip(sids, poss):
            self.alloc.ensure(sid, p + 1)
        bts = jnp.stack([self._bt(s) for s in sids])
        k, v, logits, nxt = decode_program(
            self.model, self.state, self.kv.k_pool, self.kv.v_pool,
            jnp.asarray(np.asarray(toks, np.int32)),
            jnp.asarray(np.asarray(poss, np.int32)), bts,
            mode=self.mode)
        self.kv.k_pool, self.kv.v_pool = k, v
        return np.asarray(logits)


def _oneshot(model, seq):
    return np.asarray(model.logits(jnp.asarray(
        np.asarray(seq, np.int32)[None])))[0]


@pytest.mark.parametrize("prompt_len", [5, 8, 13])
def test_prefill_then_n_decode_steps_match_oneshot(prompt_len):
    """fp32 pages: logits after prefill and after every decode step
    equal the one-shot forward at T = prompt + N, atol 1e-5 — across
    ragged (non-bucket-aligned) prompt lengths."""
    model = _model()
    rng = np.random.RandomState(prompt_len)
    full = rng.randint(0, VOCAB, prompt_len + 6).astype(np.int32)
    ref = _oneshot(model, full)
    h = Harness(model)
    logits = h.prefill(0, full[:prompt_len])
    np.testing.assert_allclose(logits, ref[prompt_len - 1], atol=1e-5)
    for n in range(6):
        pos = prompt_len + n
        logits = h.decode([0], [full[pos]], [pos])
        np.testing.assert_allclose(logits[0], ref[pos], atol=1e-5,
                                   err_msg=f"decode step {n}")


def test_batched_ragged_decode_matches_each_oneshot():
    """Two sequences of different lengths share one pool and one decode
    batch; each lane's logits match its own one-shot forward."""
    model = _model()
    rng = np.random.RandomState(0)
    full_a = rng.randint(0, VOCAB, 7 + 4).astype(np.int32)
    full_b = rng.randint(0, VOCAB, 12 + 4).astype(np.int32)
    ref_a, ref_b = _oneshot(model, full_a), _oneshot(model, full_b)
    h = Harness(model)
    la = h.prefill(0, full_a[:7])
    lb = h.prefill(1, full_b[:12])
    np.testing.assert_allclose(la, ref_a[6], atol=1e-5)
    np.testing.assert_allclose(lb, ref_b[11], atol=1e-5)
    for n in range(4):
        pa, pb = 7 + n, 12 + n
        logits = h.decode([0, 1], [full_a[pa], full_b[pb]], [pa, pb])
        np.testing.assert_allclose(logits[0], ref_a[pa], atol=1e-5)
        np.testing.assert_allclose(logits[1], ref_b[pb], atol=1e-5)


def test_mid_stream_join_preserves_running_sequence():
    """Continuous batching's defining event: B joins while A is mid-
    decode.  A's logits must be bit-identical to an A-alone run (the
    join touches disjoint pages), and B matches its one-shot."""
    model = _model()
    rng = np.random.RandomState(1)
    full_a = rng.randint(0, VOCAB, 6 + 6).astype(np.int32)
    full_b = rng.randint(0, VOCAB, 9 + 3).astype(np.int32)
    ref_b = _oneshot(model, full_b)

    # A alone, all six steps — the control trajectory
    h_solo = Harness(model)
    h_solo.prefill(0, full_a[:6])
    solo = [h_solo.decode([0], [full_a[6 + n]], [6 + n])[0]
            for n in range(6)]

    # A three steps, then B joins, then three more batched steps
    h = Harness(model)
    h.prefill(0, full_a[:6])
    joined = [h.decode([0], [full_a[6 + n]], [6 + n])[0]
              for n in range(3)]
    lb = h.prefill(1, full_b[:9])          # the join
    np.testing.assert_allclose(lb, ref_b[8], atol=1e-5)
    for n in range(3):
        pa, pb = 9 + n, 9 + n
        logits = h.decode([0, 1], [full_a[pa], full_b[pb]], [pa, pb])
        joined.append(logits[0])
        np.testing.assert_allclose(logits[1], ref_b[pb], atol=1e-5)
    for n, (s, j) in enumerate(zip(solo, joined)):
        if n < 3:
            # same compiled program (A alone) on both sides: bitwise
            np.testing.assert_array_equal(
                s, j, err_msg=f"A's step {n} disturbed by B's join")
        else:
            # after the join A rides the 2-lane bucket: a DIFFERENT
            # compiled program, whose codegen XLA does not promise is
            # bitwise-equal to the 1-lane program's (the tier-1 O0
            # backend makes the ulp-level divergence visible).  The
            # product contract is per-lane isolation — fp32-rounding
            # logits and the identical greedy token.
            np.testing.assert_allclose(
                s, j, atol=1e-5,
                err_msg=f"A's step {n} disturbed by B's join")
            assert np.argmax(s) == np.argmax(j), (
                f"A's step {n} token bent by B's join")


def test_dense_hatch_parity_and_trajectory():
    """CHAINERMN_TPU_PAGED_ATTN=dense: logits within fp32 rounding of
    the paged path (same gather, different softmax shape), and the
    engine-level greedy TRAJECTORY is equal — the acceptance pin."""
    model = _model()
    rng = np.random.RandomState(2)
    full = rng.randint(0, VOCAB, 10 + 5).astype(np.int32)
    hp = Harness(model, mode="paged")
    hd = Harness(model, mode="dense")
    lp = hp.prefill(0, full[:10])
    ld = hd.prefill(0, full[:10])
    np.testing.assert_allclose(lp, ld, atol=1e-5)
    for n in range(5):
        pos = 10 + n
        a = hp.decode([0], [full[pos]], [pos])
        b = hd.decode([0], [full[pos]], [pos])
        np.testing.assert_allclose(a, b, atol=1e-5)

    prompts = [rng.randint(0, VOCAB, rng.randint(4, 20)) for _ in range(4)]

    def run(env_mode, monkey=None):
        eng = ServingEngine(model, num_pages=64, page_size=8,
                            max_batch=4, max_context=64, mode=env_mode)
        for p in prompts:
            eng.submit(Request(p, max_new_tokens=8))
        eng.drain(now=0.0)
        return [r.tokens for r in eng.completed]

    assert run("paged") == run("dense")


def test_env_hatch_resolves_at_construction(monkeypatch):
    model = _model()
    monkeypatch.setenv("CHAINERMN_TPU_PAGED_ATTN", "dense")
    eng = ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                        max_context=32)
    assert eng.mode == "dense"
    monkeypatch.setenv("CHAINERMN_TPU_PAGED_ATTN", "bogus")
    with pytest.raises(ValueError):
        ServingEngine(model, num_pages=16, page_size=8, max_batch=2,
                      max_context=32)


def test_bf16_pages_within_documented_band():
    """bf16 pages (the serving default under bf16 compute): logits
    track the bf16 one-shot forward within the documented band — the
    page round-trip adds one bf16 quantization on K/V, nothing more.
    (docs/serving.md 'numerics'; the tight 1e-5 contract is fp32.)"""
    model = _model(compute_dtype=jnp.bfloat16)
    rng = np.random.RandomState(3)
    full = rng.randint(0, VOCAB, 8 + 4).astype(np.int32)
    ref = _oneshot(model, full)
    h = Harness(model, dtype=jnp.bfloat16)
    logits = h.prefill(0, full[:8])
    assert np.max(np.abs(logits - ref[7])) < 0.25
    for n in range(4):
        pos = 8 + n
        logits = h.decode([0], [full[pos]], [pos])
        assert np.max(np.abs(logits[0] - ref[pos])) < 0.25


def test_engine_greedy_matches_oneshot_trajectory():
    """End-to-end: the engine's greedy continuation equals argmax over
    the one-shot forward, request by request."""
    model = _model()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
               for n in (5, 11, 16)]
    eng = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                        max_context=64)
    for p in prompts:
        eng.submit(Request(p, max_new_tokens=6))
    eng.drain(now=0.0)
    assert len(eng.completed) == 3
    for req in eng.completed:
        seq = list(req.prompt)
        for n, tok in enumerate(req.tokens):
            ref = _oneshot(model, seq)
            assert tok == int(np.argmax(ref[-1])), f"token {n}"
            seq.append(tok)


def test_joins_and_leaves_never_retrace():
    """The bucketed-shapes contract: after warmup() has compiled every
    (prompt bucket × 1) prefill and (batch bucket) decode program, a
    full staggered load — joins, leaves, ragged prompts — triggers
    ZERO additional traces."""
    model = _model()
    eng = ServingEngine(model, num_pages=64, page_size=8, max_batch=4,
                        max_context=64)
    eng.warmup()
    p_traces, d_traces = eng.prefill_traces, eng.decode_traces
    assert p_traces == len(eng.prefill_buckets)
    assert d_traces == len(eng.batch_buckets)
    rng = np.random.RandomState(5)
    # staggered arrivals: the running batch sweeps sizes 1..4 and back
    for i in range(6):
        eng.submit(Request(rng.randint(0, VOCAB, rng.randint(3, 30)),
                           max_new_tokens=4 + i,
                           arrival_time=float(i)))
    t = 0.0
    while eng.running or eng.scheduler.pending():
        eng.step(now=t)
        t += 1.0
    assert len(eng.completed) == 6
    assert (eng.prefill_traces, eng.decode_traces) == (p_traces, d_traces)
