"""State slots beside the page pool in one manager (ISSUE 33): the
allocator's invariants for a state group under churn, a match cut back
to the deepest snapshot, the order in which the trie's snapshots are
given up, the engine's sizing rule, the stats by kind of group, and a
model with a full, a window AND a state group at once served through
the engine against a closed form."""

import numpy as np
import pytest

import jax.numpy as jnp

from chainermn_tpu import observability
from chainermn_tpu.serving import PerSequence, Request, ServingEngine
from chainermn_tpu.serving.errors import PagePoolExhaustedError
from chainermn_tpu.serving.kv_cache import (write_prompt_kv_at,
                                            write_token_kv)
from chainermn_tpu.serving.page_allocator import BlockAllocator

S, STRIDE = 4, 16           # page size, snapshot stride: 4 pages


def admit(a, written, sid, prompt):
    """What the engine's admission does, on the host alone; ``written``
    plays the device: what each slot holds, by the tokens it has seen.
    Returns the matched length."""
    L = len(prompt)
    pages, m, n_full, partial = a.match_prefix(prompt, L - 1)
    assert partial == 0 and m == n_full * S == len(pages) * S
    assert m % STRIDE == 0
    if m:
        (src,) = pages.snapshots
        # never a slot whose bytes are gone: it is held, and it holds
        # this very prefix's state
        assert a.states[0].refs[src] >= 1
        assert written[src] == tuple(prompt[:m])
        a.share(sid, pages)
    try:
        a.ensure(sid, L + 1)
        (snaps,) = a.reserve_snapshots(
            sid, [p for p in range(m + 1, L + 1)])
    except PagePoolExhaustedError:
        if sid in a.sequences():
            a.free(sid)
        raise
    live, source = a.state_slots(sid)
    assert source == (src if m else live)
    assert sorted(snaps) == list(range(m + STRIDE, L + 1, STRIDE))
    for p, slot in snaps.items():       # the prefill writes its snapshots
        written[slot] = tuple(prompt[:p])
    written[live] = tuple(prompt)
    a.restored(sid)
    assert a.state_slots(sid) == (live, live)
    a.register_prefix(sid, prompt)
    a.slide(sid, L)
    return m


@pytest.mark.parametrize("seed", range(6))
def test_invariants_hold_under_churn_and_a_hit_reads_a_live_snapshot(seed):
    rng = np.random.default_rng(seed)
    a = BlockAllocator(256, S, states=[(14, STRIDE)])
    prefixes = [tuple(rng.integers(0, 50, 40)) for _ in range(3)]
    written, live, next_id, hits, reclaimed, refused = {}, {}, 0, 0, 0, 0
    for _ in range(300):
        op = rng.integers(0, 4)
        if op == 0 and len(live) < 6:
            prompt = prefixes[rng.integers(0, 3)] \
                + tuple(rng.integers(0, 50, rng.integers(1, 30)))
            before = a.states[0].retained_alone
            try:
                hits += admit(a, written, next_id, prompt) > 0
                live[next_id] = len(prompt)
            except PagePoolExhaustedError:
                refused += 1
            reclaimed += a.states[0].retained_alone < before
            next_id += 1
        elif op in (1, 2) and live:
            sid = list(live)[rng.integers(0, len(live))]
            slot = a.state_slots(sid)
            a.ensure(sid, live[sid] + 1)        # the capacity pass: a
            assert a.state_slots(sid) == slot   # sequence keeps its slot
            live[sid] += 1
        elif op == 3 and live:
            sid = list(live)[rng.integers(0, len(live))]
            a.free(sid)
            del live[sid]
        assert a.check()
        stats = a.group_stats()
        assert stats["state_used_slots"] >= len(live)
        assert "window_used_pages" not in stats
    assert hits > 5 and reclaimed > 0
    for sid in list(live):
        a.free(sid)
    assert a.check()
    assert a.group_stats()["state_used_slots"] == 0 and a.used_pages == 0


def test_a_match_is_cut_back_to_the_deepest_snapshot():
    a = BlockAllocator(64, S, states=[(8, STRIDE)])
    written = {}
    prompt = tuple(range(41))
    assert admit(a, written, "holder", prompt) == 0
    stats = a.group_stats()
    # the holder's slot, and the trie's alone at 16 and at 32
    assert (stats["state_used_slots"], stats["state_retained_slots"]) \
        == (3, 2)
    # 36 tokens are shared, 9 whole pages: the state at 32 is the deepest
    pages, m, n_full, partial = a.match_prefix(prompt[:36] + (99,) * 5, 40)
    assert (m, n_full, partial, len(pages)) == (32, 8, 0, 8)
    # under a stride nothing is restored: a fresh prefill
    pages, m, _, _ = a.match_prefix(prompt[:15] + (99,) * 5, 19)
    assert m == 0 and len(pages) == 0 and not pages.snapshots
    assert admit(a, written, "hit", prompt[:36] + (99,) * 5) == 32
    # the holder ends: the nodes the hit still registers keep theirs
    a.free("holder")
    assert a.check()
    assert admit(a, written, "again", prompt[:33] + (7,)) == 32
    a.free("hit"), a.free("again")
    assert a.check() and a.group_stats()["state_used_slots"] == 0


def test_a_borrower_holds_its_snapshot_until_it_has_copied_it():
    a = BlockAllocator(64, S, states=[(4, STRIDE)])
    written = {}
    admit(a, written, "holder", tuple(range(20)))      # slots: live, 16
    pages, m, _, _ = a.match_prefix(tuple(range(20)), 19)
    assert m == 16
    (src,) = pages.snapshots
    a.share("hit", pages)
    a.free("holder")            # the node dies: the trie's hold ends
    assert a.states[0].refs[src] == 1 and a.check()
    a.ensure("hit", 21)
    assert a.state_slots("hit")[1] == src
    a.restored("hit")
    assert src not in a.states[0].refs and a.check()
    a.free("hit")
    assert a.check() and len(a.states[0].free) == 4


def test_snapshots_are_given_up_least_recently_matched_first():
    a = BlockAllocator(64, S, states=[(6, STRIDE)])
    written = {}
    first, second = tuple(range(20)), tuple(range(100, 120))
    admit(a, written, 0, first)
    admit(a, written, 1, second)        # 2 live + 2 snapshots of 6
    a.match_prefix(first, 19)           # first's is now the most recent
    admit(a, written, 2, tuple(range(200, 220)))        # 2 more: full
    assert len(a.states[0].free) == 0
    # one more sequence wants a slot: second's snapshot goes, first's stays
    admit(a, written, 3, tuple(range(300, 310)))
    assert a.check()
    assert a.match_prefix(second, 19)[1] == 0
    assert a.match_prefix(first, 19)[1] == 16
    # all that is left to want is held by sequences: typed, state kept
    for sid in (4, 5):
        a.ensure(sid, 5)
    with pytest.raises(PagePoolExhaustedError):
        a.ensure(6, 5)
    assert a.check() and 6 not in a.sequences()


def test_reserving_is_atomic_and_skips_what_is_off_the_stride():
    a = BlockAllocator(64, S, states=[(3, STRIDE)])
    a.ensure("s", 50)
    with pytest.raises(PagePoolExhaustedError):
        a.reserve_snapshots("s", [16, 32, 48])
    assert a.check() and len(a.states[0].free) == 2
    (held,) = a.reserve_snapshots("s", [0, 7, 16, 17, 32])
    assert sorted(held) == [16, 32]
    assert a.reserve_snapshots("s", [16]) == [held]     # idempotent
    a.free("s")
    assert a.check() and len(a.states[0].free) == 3


def test_an_allocator_without_state_groups_is_what_it_was():
    a = BlockAllocator(16, S)
    a.ensure("s", 9)
    assert a.reserve_snapshots("s", [4, 8]) == []
    a.restored("s")                               # nothing to do
    a.register_prefix("s", tuple(range(9)))
    pages, m, n_full, partial = a.match_prefix(tuple(range(9)), 8)
    assert (m, n_full, partial) == (8, 2, 0) and type(pages) is list
    assert a.group_stats() == {} and a.check()


def test_window_stats_of_no_window_group_are_zero_not_an_error():
    a = BlockAllocator(16, S, states=[(4, STRIDE)])
    assert a.window_used_pages == 0 and a.window_retained_pages == 0


def test_the_engine_sizes_the_state_group_from_what_it_is_given():
    # the cell: 16 lanes + 4 prompts x 9 snapshots = 52 -> 56
    assert ServingEngine.state_group_slots(2048, 16, 17920) == 56
    assert ServingEngine.state_group_slots(64, 4, 256) == 24
    assert ServingEngine.state_group_slots(64, 1, 64) == 8


# -- a full, a window and a state group at once ------------------------------

V, D, W, PAGE, SNAP = 32, 4, 16, 8, 32


class ThreeGroups:
    """A model of closed form with one layer in each kind of group: a
    token's embedding goes to the full group's page, the window group's
    page, and into the state group's running sum; the logits after
    ``n`` tokens are ``(mean of all n + mean of the last W + sum / 10)
    @ head``."""

    serve_param_dtype = None
    serve_max_context = 128
    serve_page_dtype = jnp.float32

    def __init__(self):
        rng = np.random.RandomState(0)
        self.E = jnp.asarray(rng.randn(V, D), jnp.float32)
        self.head = jnp.asarray(rng.randn(D, V), jnp.float32)

    def params(self):
        return ()

    def namedparams(self):
        return ()

    def namedlinks(self):
        return ()

    def serve_cache_groups(self):
        return (("full", 1, ((D,),), None), ("window", 1, ((D,),), W),
                ("state", 1, ((D,),), PerSequence(SNAP)))

    def reference(self, tokens):
        """Logits after each prefix of ``tokens``."""
        x = np.asarray(self.E)[np.asarray(tokens)]
        rows = [x[:n].mean(0) + x[max(0, n - W):n].mean(0)
                + x[:n].sum(0) / 10 for n in range(1, len(tokens) + 1)]
        return np.stack(rows) @ np.asarray(self.head)

    def _read(self, pool, bt, lo, hi):
        """Mean of the entries at positions ``[lo, hi)`` read back
        through one block-table row."""
        rows = pool[0, bt].reshape(-1, D)
        pos = jnp.arange(rows.shape[0])
        seen = (pos >= lo) & (pos < hi)
        return jnp.where(seen[:, None], rows, 0).sum(0) / (hi - lo)

    def serve_suffix_prefill(self, pools, tokens, true_len, start, bt_rows):
        full, win, st = pools
        x = self.E[tokens[0]]
        T = x.shape[0]
        full = write_prompt_kv_at(full, x, bt_rows[0], start, true_len,
                                  layer=0)
        win = write_prompt_kv_at(win, x, bt_rows[1], start, true_len,
                                 layer=0)
        slots = bt_rows[2]
        before = jnp.where(start > 0, st[0, slots[1]], 0.0)
        sums = before + jnp.cumsum(
            jnp.where((jnp.arange(T) < true_len)[:, None], x, 0), 0)
        n = -(-T // SNAP)
        at = jnp.minimum((jnp.arange(n) + 1) * SNAP, true_len)
        states = jnp.where(at[:, None] > 0,
                           sums[jnp.maximum(at - 1, 0)], before)
        into = jnp.where(true_len > 0, jnp.concatenate(
            [slots[2:2 + n], slots[:1]]), st.shape[1])
        st = st.at[0, into].set(jnp.concatenate([states, states[-1:]]),
                                mode="drop")
        L = start + true_len
        h = self._read(full, bt_rows[0], 0, L) \
            + self._read(win, bt_rows[1], jnp.maximum(L - W, 0), L) \
            + states[-1] / 10
        return (full, win, st), h @ self.head, ()

    def serve_prefill(self, pools, tokens, true_len, bt_rows):
        return self.serve_suffix_prefill(pools, tokens, true_len,
                                         jnp.int32(0), bt_rows)

    def serve_decode(self, pools, toks, pos, bts, mode=None, tp_mesh=None):
        full, win, st = pools
        x = self.E[toks]
        full = write_token_kv(full, x, bts[0], pos, layer=0)
        win = write_token_kv(win, x, bts[1], pos, layer=0)
        slot = bts[2][:, 0]
        state = st[0, slot] + x
        st = st.at[0, jnp.where(pos >= 0, slot, st.shape[1])].set(
            state, mode="drop")
        out = []
        for b in range(toks.shape[0]):
            n = jnp.maximum(pos[b] + 1, 1)
            out.append(self._read(full, bts[0][b], 0, n)
                       + self._read(win, bts[1][b], jnp.maximum(n - W, 0), n)
                       + state[b] / 10)
        return (full, win, st), jnp.stack(out) @ self.head, ()


@pytest.fixture()
def three(monkeypatch):
    from chainermn_tpu.serving import engine
    monkeypatch.setattr(engine, "extract_state", lambda model: {})
    monkeypatch.setattr(engine, "bind_state",
                        lambda model, state: __import__(
                            "contextlib").nullcontext())
    model = ThreeGroups()
    return model, ServingEngine(model, num_pages=48, page_size=PAGE,
                                max_batch=4, max_context=128)


def serve(model, e, requests, late=()):
    """Run ``requests`` (and ``late`` ones once the first have decoded
    a while) to the end, checking every emitted token against the closed
    form's greedy choice."""
    for r in requests:
        e.submit(r)
    for _ in range(12):
        e.step()
        assert e.allocator.check()
    for r in late:
        e.submit(r)
    while e.running or e.prefilling or e.scheduler.pending():
        e.step()
        assert e.allocator.check()
    for r in list(requests) + list(late):
        full = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        want = model.reference(full)[r.prompt.size - 1:-1].argmax(-1)
        assert list(want) == r.tokens


def test_a_model_with_full_window_and_state_groups_serves(three):
    model, e = three
    assert [p.shape for p in e.kv.pools] == [
        (1, 48, PAGE, D), (1, 128, PAGE, D), (1, 24, D)]
    assert len(e.allocator.windows) == len(e.allocator.states) == 1
    rng = np.random.RandomState(1)
    holder = Request(rng.randint(0, V, 70).astype(np.int32), 40,
                     tenant="a", request_id=1)
    other = Request(rng.randint(0, V, 21).astype(np.int32), 30,
                    tenant="b", request_id=2)
    # shares 67 tokens of the holder's prompt: 8 whole pages, cut to the
    # snapshot at 64, whose window pages the trie alone still holds
    hit = Request(np.concatenate([holder.prompt[:67],
                                  rng.randint(0, V, 9).astype(np.int32)]),
                  25, tenant="a", request_id=3)
    serve(model, e, [holder, other], late=[hit])
    assert e.prefix_hits == 1 and e.prefix_tokens_matched == 64
    stats = e.allocator.group_stats()
    assert stats == {"window_used_pages": 0, "window_num_pages": 128,
                     "window_retained_pages": 0, "state_used_slots": 0,
                     "state_num_slots": 24, "state_retained_slots": 0}


@pytest.fixture
def events_mode():
    prev = observability.set_mode("events")
    observability.reset_tracer()
    observability.reset_registry()
    yield
    observability.set_mode(prev)
    observability.reset_tracer()
    observability.reset_registry()


def test_the_spans_carry_each_kinds_stats(three, events_mode, tmp_path):
    model, e = three
    rng = np.random.RandomState(2)
    holder = Request(rng.randint(0, V, 70).astype(np.int32), 30,
                     tenant="a", request_id=1)
    hit = Request(np.concatenate([holder.prompt[:66],
                                  rng.randint(0, V, 5).astype(np.int32)]),
                  5, tenant="a", request_id=2)
    serve(model, e, [holder], late=[hit])
    shard = tmp_path / "trace.jsonl"
    observability.tracer().export(str(shard))
    by_name = {}
    for ev in observability.read_jsonl(str(shard)):
        if ev.get("args"):
            by_name.setdefault(ev["name"], []).append(ev["args"])
    step = next(a for a in by_name["serve/step"] if a.get("running"))
    assert {"window_used_pages", "window_num_pages",
            "window_retained_pages", "state_used_slots",
            "state_num_slots", "state_retained_slots"} <= set(step)
    window = by_name["serve/decode_window"][-1]
    assert window["state_lanes"] == window["batch"]
    assert window["ctx_tokens"] >= window["window_tokens"] > 0
    (suffix,) = [a for a in by_name["serve/suffix_prefill"]
                 if "restored" in a]
    assert suffix["restored"] == suffix["matched"] == 64
    assert all("restored" not in a for a in by_name["serve/prefill"])
