"""PR 42: the serve driver's two ways to count a window's tokens, and a
traced window that opens on the plateau.

The counts on a stand-in engine whose clock the test owns, so every
stamp is known: requests finished, in flight and queued, one stamp
exactly at the close, one inside the step that crosses it, more after
it.  Then the three traffic files that carry the keys, end to end on the
CPU at the tiny widths of the cells they are variants of, a traced run
included: the profiler starts after ``trace_after_s`` and what the
per-layer readers see starts after it.
"""

import types

import pytest

from benchmark import harness, program_spans, trace_reduce, traffic_gen
from benchmark.drivers import serve

from . import _tiny
from . import test_rehearsal_serve_looped as looped
from . import test_rehearsal_serve_window as window

PEAKS = _tiny.VARIANTS              # cell -> the cell it is a variant of
RATES = {"laguna-s-2.1-serve-repo-peak": 6.0,
         "ouro-2.6b-serve-chat-peak": 5.0}
SIBLINGS = {**PEAKS, "gpt2m-serve-saturated": "gpt2m-serve-chat"}
KEYED = sorted(SIBLINGS)


# -- the two counts, on known stamps -----------------------------------------

class _Clock:
    """Stands in for the ``time`` module inside the driver."""

    def __init__(self):
        self.now = 64.0         # every stamp below is an exact binary float

    def monotonic(self):
        return self.now

    def sleep(self, s):
        self.now += s


class _Engine:
    """Two lanes.  A step decodes every running request 0.5 s in (one
    stamp each), admits what is queued 0.125 s later (an admission's
    first token is stamped then) and returns after 0.125 s more."""

    def __init__(self, clock, lanes=2):
        self.clock, self.lanes = clock, lanes
        self.running, self.queue, self.completed = [], [], []
        self.decode_steps = self.prefix_hits = 0
        self.scheduler = types.SimpleNamespace(
            pending=lambda: len(self.queue))

    def submit(self, req):
        self.queue.append(req)

    def _stamp(self, req):
        req.token_times.append(self.clock.now)
        if req.first_token_time is None:
            req.first_token_time = self.clock.now
        if len(req.token_times) == req.max_new_tokens:
            self.running.remove(req)
            self.completed.append(req)

    def step(self):
        self.clock.now += 0.5
        decoded = len(self.running)
        self.decode_steps += decoded > 0
        for req in list(self.running):
            self._stamp(req)
        self.clock.now += 0.125
        admitted = 0
        while self.queue and len(self.running) < self.lanes:
            req = self.queue.pop(0)
            self.running.append(req)
            self._stamp(req)
            admitted += 1
        self.clock.now += 0.125
        return {"decoded": decoded, "admitted": admitted}


class _Prog:
    def __init__(self, clock):
        self.engine = _Engine(clock)

    def request(self, prompt, max_new, tenant="warm", arrival=None):
        return types.SimpleNamespace(
            prompt=prompt, max_new_tokens=max_new, tenant=tenant,
            token_times=[], first_token_time=None)

    def drain(self):
        while self.engine.running or self.engine.queue:
            self.engine.step()


# name: (due, tokens asked for).  With the engine above and a window of
# 2 s the stamps are, in seconds after the window opens:
#   A 0.625 1.25 2.0          finished, its last stamp exactly at the close
#   B 0.625 1.25 2.0 | 2.75 ...             in flight at the close
#   C 2.125   finished inside the step that crosses the close (ends 2.25)
#   D 2.125 | 2.75 ...                      in flight, nothing in the window
#   E | ...                                 queued at the close
ARRIVALS = {"A": (0.0, 3), "B": (0.0, 6), "C": (1.0, 1), "D": (1.4, 4),
            "E": (1.45, 2)}


def _stand_in(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(serve, "time", clock)
    arrivals = [traffic_gen.Arrival(due, i, name, n)
                for i, (name, (due, n)) in enumerate(ARRIVALS.items())]
    return clock, _Prog(clock), arrivals


def _drive(monkeypatch, **keys):
    _, prog, arrivals = _stand_in(monkeypatch)
    w = serve.drive(prog, arrivals, 2.0, **keys)
    stamps = {r.prompt: [t - 64.0 for t in r.token_times]
              for r in w["requests"]}
    return w, stamps


def test_the_stamps_are_the_ones_the_table_says(monkeypatch):
    w, stamps = _drive(monkeypatch)
    assert stamps["A"] == [0.625, 1.25, 2.0]
    assert stamps["B"][:4] == [0.625, 1.25, 2.0, 2.75]
    assert stamps["C"] == [2.125] and stamps["D"][:2] == [2.125, 2.75]
    assert stamps["E"][0] > 2.75
    assert w["window_s"] == 2.25 and w["failed"] == 0 and w["missing"] == 0
    assert len(w["finished"]) == 5


def test_without_the_key_the_count_is_todays_expression(monkeypatch):
    """Tokens of the requests that finished before the step crossing the
    close returned, over the time at which it returned."""
    w, _ = _drive(monkeypatch)
    assert w["count"] == "requests"
    done = [r for r in w["requests"] if r.prompt in "AC"]
    assert w["tokens_in_window"] == sum(len(r.token_times) for r in done) == 4
    assert w["counted_s"] == w["window_s"] == 2.25
    assert w["tokens_in_window"] / w["counted_s"] == 4 / 2.25
    # what a reader sees beside it, whichever way the cell counts
    assert (w["finished_in_window"], w["in_flight_at_close"],
            w["queued_at_close"]) == (2, 3, 1)
    assert w["tokens_of_finished"] == 4 and w["tokens_stamped"] == 6
    assert w["decode_steps_at_close"] == 2 and w["prefix_hits_at_close"] == 0


def test_with_the_key_the_count_is_the_stamps_over_the_clock(monkeypatch):
    """Every stamp up to ``base + seconds``, the one exactly at the close
    with them, over every request, finished or in flight; C's, inside
    the step that crossed the close, is not; the divisor is
    ``seconds``."""
    w, _ = _drive(monkeypatch, count="tokens")
    assert w["count"] == "tokens"
    assert w["tokens_in_window"] == w["tokens_stamped"] == 3 + 3
    assert w["counted_s"] == 2.0 and w["window_s"] == 2.25
    assert w["tokens_in_window"] / w["counted_s"] == 3.0
    assert w["tokens_of_finished"] == 4
    assert (w["finished_in_window"], w["in_flight_at_close"],
            w["queued_at_close"]) == (2, 3, 1)
    # the drain goes on stamping: none of it is counted
    assert sum(len(r.token_times) for r in w["requests"]) == 16
    # one slice of 5 s holds the window: A's and B's gaps of 0.625 s; the
    # gaps that end at the close or in the drain are left out
    assert w["gap_p50_ms_by_slice"] == [625.0]


class _Tracer:
    """A profiler that takes a quarter of a second to start."""

    def __init__(self, clock):
        self.clock, self.entered, self.left = clock, [], []

    def __enter__(self):
        self.entered.append(self.clock.now - 64.0)
        self.clock.now += 0.25

    def __exit__(self, *exc):
        self.left.append(self.clock.now - 64.0)


def test_the_tracer_opens_after_trace_after_s(monkeypatch):
    """Entered once, at the first look at the clock past
    ``trace_after_s``; the window that is counted opens when it has
    started and is ``seconds - trace_after_s`` long."""
    clock, prog, arrivals = _stand_in(monkeypatch)
    tracer = _Tracer(clock)
    w = serve.drive(prog, arrivals, 2.0, tracer, count="tokens",
                    trace_after_s=1.0)
    assert tracer.entered == [1.5] and tracer.left == [3.25]
    assert w["opened_s"] == 1.75 and w["counted_s"] == 1.0
    # stamps in [1.75, 2.75]: A and B at 2.25, C and D at 2.375
    assert w["tokens_in_window"] == 4
    assert w["window_s"] == 3.25


def test_a_tracer_without_the_key_wraps_the_whole_window(monkeypatch):
    clock, prog, arrivals = _stand_in(monkeypatch)
    tracer = _Tracer(clock)
    w = serve.drive(prog, arrivals[:1], 2.0, tracer)
    # the window's clock starts once the profiler has
    assert tracer.entered == [0.0] and w["opened_s"] == 0.0
    assert w["window_s"] == 2.25 and tracer.left == [2.5]
    assert w["tokens_in_window"] == w["tokens_of_finished"] == 3


def test_an_unknown_count_is_refused():
    run = looped.tiny_run(cell="ouro-2.6b-serve-chat-peak")
    run.traffic["count"] = "requests_and_tokens"
    with pytest.raises(harness.BenchmarkError, match="count"):
        serve.run(run)


# -- the files that carry the keys ------------------------------------------

@pytest.mark.parametrize("cell", sorted(PEAKS))
def test_a_peak_file_is_its_siblings_but_for_the_rate_and_the_keys(cell):
    sibling, rate = PEAKS[cell], RATES[cell]
    peak, base = (harness.load_traffic({"traffic": name})
                  for name in (cell, sibling))
    assert peak["count"] == "tokens" and peak["trace_after_s"] == 20
    assert peak["mix"] == dict(base["mix"], rate=rate)
    assert peak["mix"]["rate"] > 1.4 * base["mix"]["rate"] / 0.8
    rest = {k: v for k, v in peak.items()
            if k not in ("why", "mix", "count", "trace_after_s")}
    assert rest == {k: v for k, v in base.items() if k not in ("why", "mix")}


@pytest.mark.parametrize("name", [
    "gpt2m-serve-chat", "kimi-k2.6-serve-agent", "laguna-s-2.1-serve-repo",
    "olmo-hybrid-7b-serve-docs", "ouro-2.6b-serve-chat"])
def test_the_cells_accepted_before_keep_the_count_by_requests(name):
    traffic = harness.load_traffic({"traffic": name})
    assert "count" not in traffic and "trace_after_s" not in traffic


def test_the_saturated_file_takes_the_keys_and_stays_unlisted():
    traffic = harness.load_traffic({"traffic": "gpt2m-serve-saturated"})
    assert traffic["count"] == "tokens" and traffic["trace_after_s"] == 20
    assert "gpt2m-serve-saturated" not in {
        w["name"] for w in harness.load_manifest()["workloads"]}


def _manifest_with_the_keyed_cells():
    return _tiny.listed_beside(harness.load_manifest(), SIBLINGS)


def _tiny_run(cell, **kw):
    kw.setdefault("seconds", 2.0)
    if cell in PEAKS:
        module = window if cell.startswith("laguna") else looped
        run = module.tiny_run(cell=cell, **kw)
    else:
        run = _tiny.tiny_run(cell, limits={"served_logit_gap": 0.02}, **kw)
    # the keys as the file has them, but for a ramp a test can wait for
    full = harness.load_traffic({"traffic": cell})
    assert run.traffic["count"] == full["count"] == "tokens"
    run.traffic["trace_after_s"] = 1.0
    return run


@pytest.fixture
def keyed(monkeypatch):
    monkeypatch.setattr(_tiny, "manifest", _manifest_with_the_keyed_cells)
    calls = []
    drive = serve.drive

    def spy(prog, arrivals, seconds, tracer=None, **keys):
        before = prog.engine.decode_steps
        w = drive(prog, arrivals, seconds, tracer, **keys)
        calls.append(dict(w, seconds=seconds, keys=keys,
                          schedule_s=arrivals[-1].due,
                          base=w["requests"][0].due - arrivals[0].due,
                          decode_steps=prog.engine.decode_steps - before))
        return w
    monkeypatch.setattr(serve, "drive", spy)
    return calls


@pytest.mark.parametrize("cell", KEYED)
def test_a_keyed_cell_runs_and_its_rate_is_the_stamps(keyed, cell):
    line = _tiny.result(_tiny_run(cell))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 40
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    w, = keyed
    assert w["keys"] == {"count": "tokens", "trace_after_s": None}
    assert w["count"] == "tokens" and w["opened_s"] == 0.0
    assert w["counted_s"] == 2.0 <= w["window_s"]
    stamps = [t for r in w["requests"] for t in r.token_times]
    assert w["tokens_in_window"] == w["tokens_stamped"] <= len(stamps)
    assert line["metrics"]["serve_tokens_per_s"]["value"] \
        == w["tokens_stamped"] / 2.0
    assert w["tokens_of_finished"] == sum(
        len(r.token_times) for r in w["finished"][:w["finished_in_window"]])


@pytest.mark.parametrize("cell", KEYED)
def test_a_traced_keyed_cell_reads_what_comes_after_the_ramp(keyed, cell):
    """``trace_after_s + trace_seconds`` of schedule in one go; the
    profiler starts once ``trace_after_s`` have passed, so the spans the
    per-layer readers get are fewer than the steps driven and none is
    from the ramp."""
    run = _tiny_run(cell, trace=True)
    result = serve.run(run)
    assert result["correct"]
    w, = keyed
    assert w["keys"] == {"count": "tokens", "trace_after_s": 1.0}
    assert w["seconds"] == 2.0 and 1.0 < w["schedule_s"] < 2.0
    assert w["opened_s"] >= 1.0 and w["counted_s"] == 1.0
    assert w["window_s"] >= w["opened_s"] + 1.0
    opened = w["base"] + w["opened_s"]
    assert [t for r in w["requests"] for t in r.token_times if t < opened], \
        "the ramp was driven"
    spans = program_spans.load(
        trace_reduce.find_xplane(result["tracing"].dir))
    lo, hi = trace_reduce.window(result["tracing"].load(),
                                 "bench/window")
    steps = [s for s in spans if s.name == "serve/decode_window"]
    assert 0 < len(steps) < w["decode_steps"]
    assert min(s.start for s in spans) >= lo
    assert 0.9 < hi - lo
    view = {"trace": result["tracing"].load(), "lo": lo, "hi": hi,
            "result": result, "run": run}
    seen = program_spans.spans(view)
    assert seen and all(lo <= s.start and s.end <= hi for s in seen)
    reader = harness.load_module("layer_metrics", "serve.lanes_in_use")
    assert reader.read(view) > 0


def _check(rows, name):
    return next(r for r in rows if r["check"] == name)


@pytest.mark.parametrize("cell", sorted(PEAKS))
def test_the_control_in_float8_is_not_correct_in_a_peak_cell(keyed, cell):
    """The reference itself in float8, over the sample of a window
    counted by stamps: over the cell's own limit, the sound run under
    it."""
    run = _tiny_run(cell)
    result = serve.run(run)
    assert result["correct"]
    limit = run.traffic["limits"]["served_logit_gap"]
    sound = _check(result["checks"], "served_logit_gap")["value"]
    gap, n = serve.reference_gap(run, result["spec"], result["sample"],
                                 control="fp8")
    assert n >= 40
    assert gap > limit > sound


@pytest.mark.parametrize("cell", KEYED)
def test_a_token_altered_where_it_is_produced_is_not_correct(
        keyed, monkeypatch, cell):
    from chainermn_tpu.serving import ServingEngine
    record = ServingEngine._record_token

    def altered(self, req, tok, now):
        record(self, req, (int(tok) + 1) % 128, now)
    monkeypatch.setattr(ServingEngine, "_record_token", altered)
    line = _tiny.result(_tiny_run(cell))
    assert line["correct"] is False


@pytest.mark.parametrize("cell", KEYED)
def test_a_request_that_never_finishes_is_not_correct(
        keyed, monkeypatch, cell):
    """Counting by stamps takes in requests that have not finished; the
    check that every due request finishes after the drain holds all the
    same."""
    drive = serve.drive   # the fixture's spy

    def loses_one(*args, **keys):
        w = drive(*args, **keys)
        w["finished"].pop()
        w["failed"] += 1
        return w
    monkeypatch.setattr(serve, "drive", loses_one)
    line = _tiny.result(_tiny_run(cell))
    assert line["correct"] is False and line["failed"] == 1


# -- the manifest -------------------------------------------------------------

def _listed():
    m = harness.load_manifest()
    return [c for c in sorted(PEAKS)
            if any(w["name"] == c for w in m["workloads"])]


@pytest.mark.parametrize("cell", sorted(PEAKS))
def test_a_listed_peak_cell_is_listed_beside_its_sibling(cell):
    """A peak cell is either out of the manifest altogether (its runs on
    the chip spread wider than 0.4 %: PERF.md section 7) or listed with
    one chip, under the rate, and under every per-layer metric of the
    cell beside it but the full prefill's window kernel, which a traced
    plateau does not run."""
    m = harness.load_manifest()
    named = {e["name"] for e in m["end_to_end"] + m["per_layer"]
             if cell in e.get("workloads", ())}
    if cell not in _listed():
        assert not named
        return
    sibling = PEAKS[cell]
    w = harness.find_workload(m, cell)
    assert w == {"name": cell, "traffic": cell, "chips": 1,
                 "config": harness.find_workload(m, sibling)["config"],
                 "why": w["why"]} and len(w["why"]) <= 200
    theirs = {e["name"] for e in m["end_to_end"] + m["per_layer"]
              if sibling in e.get("workloads", ())}
    assert named == theirs - {"flash.window_fwd_roofline"}
    assert [e["name"] for e in harness.metrics_for(m, cell, "end_to_end")] \
        == ["serve_tokens_per_s", "setup_s"]
    rooflines = {e["name"] for e in harness.metrics_for(m, cell, "per_layer")
                 if e["name"].endswith("_roofline")}
    assert rooflines == ({"serve.decode_hbm_roofline"}
                         if cell.startswith("laguna")
                         else {"loop.decode_hbm_roofline"})
