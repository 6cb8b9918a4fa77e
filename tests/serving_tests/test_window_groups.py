"""A second page pool for the layers that keep a window (ISSUE 31): the
allocator's invariants for two groups under churn, what a prefix hit may
take from the window group, what a live sequence holds there, and the
engine's sizing rule."""

import numpy as np
import pytest

from chainermn_tpu.serving import ServingEngine
from chainermn_tpu.serving.errors import PagePoolExhaustedError
from chainermn_tpu.serving.page_allocator import BlockAllocator

S, W = 4, 16            # page size, window: 4 pages and the one grown into


def admit(a, written, sid, prompt):
    """What the engine's admission does, on the host alone; ``written``
    plays the device: what each window page holds, by the tokens up to
    its last slot.  Returns the matched length."""
    L = len(prompt)
    pages, m, n_full, partial = a.match_prefix(prompt, L - 1)
    assert partial == 0 and m == n_full * S == len(pages) * S
    if m:
        table, low = pages.windows[0]
        assert low == max(0, m - W + 1) // S and len(table) == m // S
        for i in range(low, m // S):
            # never a page whose bytes are gone: it is held, and it holds
            # this very prefix's chunk
            assert a.windows[0].refs[table[i]] >= 1
            assert written[table[i]] == tuple(prompt[:(i + 1) * S])
        a.share(sid, pages)
    try:
        a.ensure(sid, L + 1)
    except PagePoolExhaustedError:
        if m:
            a.free(sid)
        raise
    table, low = a.window_table(sid)
    for i in range(m // S, L // S):         # the prefill writes its pages
        written[table[i]] = tuple(prompt[:(i + 1) * S])
    a.register_prefix(sid, prompt)
    a.slide(sid, L)
    return m


def held(a, sid):
    table, low = a.window_table(sid)
    return len(table) - low


@pytest.mark.parametrize("seed", range(6))
def test_invariants_hold_under_churn_and_a_hit_reads_live_pages(seed):
    rng = np.random.default_rng(seed)
    a = BlockAllocator(256, S, windows=[(48, W)])
    prefixes = [tuple(rng.integers(0, 50, 40)) for _ in range(3)]
    written, live, next_id, hits, reclaimed = {}, {}, 0, 0, 0
    for _ in range(300):
        op = rng.integers(0, 4)
        if op == 0 and len(live) < 6:
            prompt = prefixes[rng.integers(0, 3)] \
                + tuple(rng.integers(0, 50, rng.integers(1, 30)))
            before = a.windows[0].retained_alone
            try:
                hits += admit(a, written, next_id, prompt) > 0
                live[next_id] = len(prompt)
            except PagePoolExhaustedError:
                pass
            reclaimed += a.windows[0].retained_alone < before
            next_id += 1
        elif op in (1, 2) and live:
            sid = list(live)[rng.integers(0, len(live))]
            a.ensure(sid, live[sid] + 1)        # the capacity pass
            a.slide(sid, live[sid])
            live[sid] += 1
            # outside a prefill: its window and the page it grows into
            assert held(a, sid) <= W // S + 2
        elif op == 3 and live:
            sid = list(live)[rng.integers(0, len(live))]
            a.free(sid)
            del live[sid]
        assert a.check()
    assert hits > 5 and reclaimed > 0
    for sid in list(live):
        a.free(sid)
    assert a.check()
    assert a.window_used_pages == 0 and a.used_pages == 0


def test_the_trie_keeps_a_prompts_window_pages_after_the_window_moved_on():
    a = BlockAllocator(64, S, windows=[(32, W)])
    written = {}
    prompt = tuple(range(41))
    assert admit(a, written, "holder", prompt) == 0
    # the holder keeps its window; the trie alone the 6 pages below it
    assert held(a, "holder") == (41 + 1 + S - 1) // S - (41 - W + 1) // S
    assert a.window_retained_pages == (41 - W + 1) // S == 6
    assert a.window_used_pages == 11
    # a hit at 32 tokens takes the pages covering (32 - 16, 32) alone
    other = prompt[:32] + (99,) * 9
    assert admit(a, written, "hit", other) == 32
    # (its own prefill then slid it on: what it still shares of them are
    # the pages its window covers)
    table, low = a.window_table("hit")
    assert low == (41 - W + 1) // S == 6
    assert table[6:8] == a.window_table("holder")[0][6:8]
    # the holder ends: what the hit still registers stays, the rest goes
    a.free("holder")
    assert a.check()
    assert admit(a, written, "again", prompt[:36] + (7,)) == 32
    a.free("hit"), a.free("again")
    assert a.check() and a.window_used_pages == 0


def test_a_short_window_pool_gives_up_the_trie_s_pages_before_a_sequence():
    a = BlockAllocator(64, S, windows=[(14, W)])
    written = {}
    admit(a, written, 0, tuple(range(40)))       # 11 pages, 6 of them
    assert a.window_retained_pages == 6          # the trie's alone
    # 9 free pages wanted, 3 free: the least recently matched go first
    admit(a, written, 1, tuple(range(100, 133)))
    assert a.check()
    pages, m, _, _ = a.match_prefix(tuple(range(40)), 39)
    # the match is cut back to what both groups still serve, or to none
    assert m in (0, 36) and len(pages) == m // S
    # only the sequences' own pages are left to want: typed, state kept
    with pytest.raises(PagePoolExhaustedError):
        a.ensure(2, 60)
    assert a.check() and 2 not in a.sequences()


def test_an_allocator_without_window_groups_is_what_it_was():
    a = BlockAllocator(16, S)
    a.ensure("s", 9)
    a.register_prefix("s", tuple(range(9)))
    a.slide("s", 9)                               # nothing to do
    pages, m, n_full, partial = a.match_prefix(tuple(range(9)), 8)
    assert (m, n_full, partial) == (8, 2, 0) and type(pages) is list
    assert a.check()


def test_the_engine_sizes_the_window_group_from_what_it_is_given():
    # the cell: 32 lanes x 33 pages + 4 prompts of 672 = 3744 -> 3840
    pages = ServingEngine.window_group_pages(512, 16, 32, 10752)
    assert pages == 3840
    # a quarter at most of the same layers kept whole (21504 pages)
    assert pages * 4 <= 21504
    assert ServingEngine.window_group_pages(16, 8, 4, 128) == 128
