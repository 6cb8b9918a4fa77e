"""Span tracer + trace schema (ISSUE 14): the committed Chrome-trace
contract, the ring-buffer bounds, the off-path zero-cost pin, and the
rank-shard merge tool.

Host-only — no jit, no devices; tiny per the tier-1 budget."""

import json
import os
import sys

import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import tracing


@pytest.fixture
def events_mode():
    prev = obs.set_mode("events")
    obs.reset_tracer()
    yield
    obs.set_mode(prev)
    obs.reset_tracer()


def _tools():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", "tools"))
    import trace_merge
    return trace_merge


# -- schema validator ---------------------------------------------------------

def _ev(name="x", ph="B", ts=0, pid=0, tid=1, **kw):
    return dict({"name": name, "ph": ph, "ts": ts, "pid": pid,
                 "tid": tid}, **kw)


def test_validator_accepts_wellformed():
    events = [_ev("process_name", "M"),
              _ev("a", "B", 0), _ev("b", "B", 1), _ev("mark", "i", 2),
              _ev("b", "E", 3), _ev("a", "E", 4)]
    assert obs.validate_events(events) == 6


def test_validator_rejects_missing_key():
    bad = _ev()
    del bad["ts"]
    with pytest.raises(ValueError, match="missing key"):
        obs.validate_events([bad])


def test_validator_rejects_backwards_ts():
    with pytest.raises(ValueError, match="backwards"):
        obs.validate_events([_ev("a", "B", 5), _ev("a", "E", 3)])


def test_validator_rejects_unbalanced():
    with pytest.raises(ValueError, match="no open B"):
        obs.validate_events([_ev("a", "E", 0)])
    with pytest.raises(ValueError, match="unclosed"):
        obs.validate_events([_ev("a", "B", 0)])


def test_validator_rejects_bad_nesting():
    with pytest.raises(ValueError, match="innermost"):
        obs.validate_events([_ev("a", "B", 0), _ev("b", "B", 1),
                             _ev("a", "E", 2), _ev("b", "E", 3)])


def test_validator_separate_tracks_independent():
    events = [_ev("a", "B", 0, tid=1), _ev("b", "B", 1, tid=2),
              _ev("a", "E", 2, tid=1), _ev("b", "E", 3, tid=2)]
    assert obs.validate_events(events) == 4


# -- recording + export -------------------------------------------------------

def test_span_records_balanced_pair(events_mode):
    with obs.span("train/input_stall", tags={"k": 1}):
        pass
    evs = obs.tracer().events()
    assert [e["ph"] for e in evs] == ["B", "E"]
    assert evs[0]["name"] == evs[1]["name"] == "train/input_stall"
    assert evs[0]["args"] == {"k": 1}
    obs.validate_events(evs)


def test_instant_and_rank_epoch_tags(events_mode):
    obs.tracer().configure(rank=3, epoch=7)
    obs.instant("elastic/preempt_detect", tags={"exc": "X"})
    (ev,) = obs.tracer().events()
    assert ev["ph"] == "i" and ev["pid"] == 3
    assert ev["args"]["epoch"] == 7 and ev["args"]["exc"] == "X"


def test_complete_retroactive_span_is_valid(events_mode):
    obs.tracer().complete("serve/queue_wait", 0.001, tid=42)
    evs = obs.tracer().events()
    assert [e["ph"] for e in evs] == ["B", "E"]
    assert evs[0]["ts"] <= evs[1]["ts"]
    assert evs[0]["args"]["duration_ms"] == 1.0   # exact, un-clamped
    obs.validate_events(evs)


def test_complete_clamps_to_track_floor(events_mode):
    """A foreign-clock duration larger than the real elapsed tracer
    time (simulated engine clocks) must not reach back past earlier
    spans on the same lane — that would cross-pair B/E under LIFO
    pairing (the code-review finding).  The drawn interval clamps to
    the track's last event; the exact duration survives in args."""
    tr = obs.tracer()
    with tr.span("first", tid=7):
        pass
    tr.complete("second", duration_s=1e6, tid=7)   # "11 days waited"
    evs = tr.events()
    first_end = evs[1]["ts"]
    b2, e2 = evs[2], evs[3]
    assert b2["ts"] >= first_end                   # no overlap
    assert b2["args"]["duration_ms"] == 1e9        # truth preserved
    # ts-sorted export of the lane stays properly nested
    obs.validate_events(sorted(evs, key=lambda e: e["ts"]))


def test_ring_buffer_bounds_and_export_repair(events_mode, tmp_path):
    tr = tracing.SpanTracer(rank=0, capacity=8)
    # 6 nested B... then enough child spans to evict the outer Bs
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 8  # bounded
    path = tmp_path / "t.jsonl"
    n = tr.export(str(path))
    events = obs.read_jsonl(str(path))
    obs.validate_events(events)  # eviction damage repaired
    assert n == sum(1 for e in events if e["ph"] != "M")


def test_export_closes_unclosed_spans(events_mode, tmp_path):
    tr = obs.tracer()
    span = tr.span("left/open")
    tr.instant("mark")
    del span  # never closed
    path = tmp_path / "t.jsonl"
    tr.export(str(path))
    events = obs.read_jsonl(str(path))
    obs.validate_events(events)
    assert any(e["ph"] == "E" and e["name"] == "left/open"
               for e in events)


def test_export_writes_rank_metadata(events_mode, tmp_path):
    obs.tracer().configure(rank=2)
    obs.instant("x")
    path = tmp_path / "t.jsonl"
    obs.tracer().export(str(path))
    meta = [e for e in obs.read_jsonl(str(path)) if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "rank2"
    assert meta[0]["pid"] == 2


# -- the off-path cost contract ----------------------------------------------

def test_off_is_default_and_emits_nothing():
    assert obs.mode() == "off"          # the conftest env default
    assert not obs.enabled()
    obs.reset_tracer()
    with obs.span("anything", tags={"a": 1}):
        obs.instant("nothing")
    assert obs.tracer().events() == []


def test_off_span_returns_singleton_no_alloc():
    """The committed near-zero-cost contract: every disabled span call
    returns THE module singleton, and a hot loop of span call sites
    leaves no net allocations behind."""
    assert obs.mode() == "off"
    first = obs.span("a")
    assert obs.span("b") is first is tracing._NOOP
    # warm up any lazy caches, then measure net allocated blocks: a
    # per-call-site allocation would add >= 10_000 blocks; anything in
    # the noise floor (interpreter-internal caches) stays constant
    import gc
    for _ in range(64):
        with obs.span("warm"):
            pass
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(10_000):
        with obs.span("hot"):
            pass
    gc.collect()
    after = sys.getallocatedblocks()
    assert after - before < 100, (before, after)


def test_set_mode_rejects_unknown():
    with pytest.raises(ValueError, match="expected one of"):
        obs.set_mode("loud")


# -- trace_merge --------------------------------------------------------------

def test_trace_merge_lossless_and_sorted(events_mode, tmp_path):
    trace_merge = _tools()
    shards = []
    for rank in (0, 1):
        tr = tracing.SpanTracer(rank=rank)
        with tr.span("train/optimizer_update"):
            tr.instant("mark", tags={"rank": rank})
        p = tmp_path / f"trace-rank{rank}.jsonl"
        tr.export(str(p))
        shards.append(str(p))
    out = tmp_path / "merged.json"
    merged = trace_merge.merge_files(shards, str(out))
    obs.validate_events(merged)
    # lossless: every shard event survives the merge
    shard_events = [e for p in shards for e in obs.read_jsonl(p)]
    key = trace_merge._dedupe_key
    assert {key(e) for e in shard_events} == {key(e) for e in merged}
    assert {e["pid"] for e in merged} == {0, 1}
    # the written file is a Perfetto-loadable JSON array
    loaded = json.loads(out.read_text())
    assert loaded == merged
    # non-meta events are ts-sorted
    ts = [e["ts"] for e in merged if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_merge_preserves_same_key_events_within_shard(tmp_path):
    """Two DISTINCT back-to-back sub-microsecond spans can share the
    full (pid, tid, ts, ph, name) key inside one shard — dedupe is
    cross-shard only (review finding: intra-shard dedupe orphaned an E
    and refused a valid shard)."""
    trace_merge = _tools()
    shard = [_ev("s", "B", 100), _ev("s", "E", 100),
             _ev("s", "B", 100), _ev("s", "E", 101)]
    obs.validate_events(shard)                       # valid as written
    merged = trace_merge.merge_events([shard])
    assert len(merged) == 4                          # lossless
    obs.validate_events(merged)
    # and the cross-shard dedupe still collapses a double-read shard
    assert len(trace_merge.merge_events([shard, list(shard)])) == 4


def test_trace_merge_dedupes_reexported_shard(events_mode, tmp_path):
    trace_merge = _tools()
    tr = tracing.SpanTracer(rank=0)
    with tr.span("s"):
        pass
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    tr.export(str(a))
    tr.export(str(b))   # the same ring exported twice
    merged = trace_merge.merge_events([obs.read_jsonl(str(a)),
                                       obs.read_jsonl(str(b))])
    assert len(merged) == len(obs.read_jsonl(str(a)))


def test_trace_merge_cli_refuses_invalid(tmp_path):
    trace_merge = _tools()
    bad = tmp_path / "bad.jsonl"
    ev = _ev("a", "B", 0)
    del ev["ts"]   # genuinely malformed — repair cannot fix this
    bad.write_text(json.dumps(ev) + "\n")
    rc = trace_merge.main([str(bad), "-o", str(tmp_path / "out.json")])
    assert rc == 1
    assert not (tmp_path / "out.json").exists()


def test_trace_merge_checkpoint_plus_exit_export(events_mode, tmp_path):
    """A mid-run export (open span closed with a synthetic E) merged
    with the exit export (the real E, later ts) must succeed — the
    orphaned synthetic-vs-real E pair is repaired, not refused (the
    code-review repro)."""
    trace_merge = _tools()
    tr = obs.tracer()
    span = tr.span("train/run")
    p1 = tmp_path / "ckpt.jsonl"
    tr.export(str(p1))           # closes train/run synthetically
    span.close()                 # the real E, later ts
    p2 = tmp_path / "exit.jsonl"
    tr.export(str(p2))
    merged = trace_merge.merge_files([str(p1), str(p2)],
                                     str(tmp_path / "m.json"))
    obs.validate_events(merged)
    pairs = [e for e in merged if e["name"] == "train/run"]
    assert [e["ph"] for e in pairs] == ["B", "E"]
