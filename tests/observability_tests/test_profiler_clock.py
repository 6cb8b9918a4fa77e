"""The program's spans on the profiler's clock (ISSUE 24): whenever a
``jax.profiler`` session records, ``observability.span`` opens a
``TraceAnnotation`` that comes back from the session's own
``.xplane.pb`` (plane ``/host:CPU``) with its tags as stats; with no
session and the ring off it is still the no-op singleton; ``instant``
and ``complete`` never reach the profiler; and the two entry points,
``StandardUpdater.update`` and ``ServingEngine.step``, yield exactly the
span names docs/observability.md lists, children inside parents.

CPU only: a profiler session on the CPU backend records the host plane.
"""

import contextlib
import glob
import os

import numpy as np
import pytest

import jax

import chainermn_tpu as ct
from chainermn_tpu import observability as obs
from chainermn_tpu.core.optimizer import MomentumSGD
from chainermn_tpu.dataset import SerialIterator, TupleDataset
from chainermn_tpu.models import MLP, Classifier, TransformerLM
from chainermn_tpu.observability import tracing
from chainermn_tpu.training import StandardUpdater

PREFIXES = ("train/", "serve/", "test/")


class Session:
    """A profiler session whose ``train/``, ``serve/`` and ``test/``
    host events are read back after it closes: ``[(name, start_ns,
    end_ns, stats)]`` by start, per thread."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "profile")
        self.events = None

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.events = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        self.events.append(
                            (e.name, e.start_ns, e.end_ns, dict(e.stats)))
        self.events.sort(key=lambda e: (e[1], -e[2]))

    def names(self):
        return [e[0] for e in self.events]

    def one(self, name):
        found = [e for e in self.events if e[0] == name]
        assert len(found) == 1, (name, self.names())
        return found[0]

    def inside(self, child, parent):
        _, s, e, _ = self.one(child) if isinstance(child, str) else child
        _, ps, pe, _ = self.one(parent)
        return ps <= s and e <= pe


@pytest.fixture
def ring():
    prev = obs.set_mode("events")
    obs.reset_tracer()
    yield
    obs.set_mode(prev)
    obs.reset_tracer()


def test_two_modes_and_no_named_scope_switch():
    assert obs.MODES == ("off", "events")
    with pytest.raises(ValueError, match="expected one of"):
        obs.set_mode("full")
    assert not hasattr(obs, "named_scopes_enabled")
    assert not hasattr(tracing, "_full_span")


def test_a_span_under_a_session_comes_back_with_its_stats(tmp_path):
    assert obs.mode() == "off" and not obs.enabled()
    with Session(tmp_path) as session:
        assert obs.enabled() and not obs.ring_enabled()
        with obs.span("test/outer", tags={"batch": 3, "occupancy": 0.25,
                                          "iterator": "SerialIterator",
                                          "flag": True}):
            with obs.span("test/inner"):
                pass
    assert not obs.enabled()
    name, _, _, stats = session.one("test/outer")
    assert stats == {"batch": 3, "occupancy": 0.25,
                     "iterator": "SerialIterator", "flag": 1}
    assert session.inside("test/inner", "test/outer")
    assert obs.tracer().events() == []      # the ring stayed off


def test_counts_set_before_the_span_closes_are_its_stats(tmp_path, ring):
    with Session(tmp_path) as session:
        with obs.span("test/step", tags={"step": 7}) as sp:
            sp.set(running=2, admitted=1)
    assert session.one("test/step")[3] == {"step": 7, "running": 2,
                                           "admitted": 1}
    begin, end = obs.tracer().events()
    assert (begin["ph"], begin["args"]) == ("B", {"step": 7})
    assert (end["ph"], end["args"]) == ("E", {"running": 2, "admitted": 1})


def test_with_no_session_and_the_ring_off_span_is_the_singleton():
    assert obs.mode() == "off" and not obs.enabled()
    sp = obs.span("test/quiet", tags=None)
    assert sp is tracing._NOOP
    sp.set(anything=1)      # the call sites' counts cost a no-op


def test_ring_and_profiler_together_give_both(tmp_path, ring):
    with Session(tmp_path) as session:
        with obs.span("test/both", tags={"n": 1}, tid=77):
            pass
    with obs.span("test/ring_only"):
        pass
    assert session.names() == ["test/both"]
    events = obs.tracer().events()
    obs.validate_events(events)
    assert [(e["name"], e["ph"]) for e in events] == [
        ("test/both", "B"), ("test/both", "E"),
        ("test/ring_only", "B"), ("test/ring_only", "E")]
    assert events[0]["tid"] == 77       # the synthetic track is the ring's


def test_instants_and_retroactive_spans_never_reach_the_profiler(
        tmp_path, ring):
    with Session(tmp_path) as session:
        obs.instant("test/instant", tags={"a": 1})
        obs.complete("test/complete", 0.5, tags={"a": 1})
    assert session.names() == []
    assert {e["name"] for e in obs.tracer().events()} == {
        "test/instant", "test/complete"}


def test_instants_and_retroactive_spans_are_nothing_with_the_ring_off(
        tmp_path):
    obs.reset_tracer()
    with Session(tmp_path) as session:
        obs.instant("test/instant")
        obs.complete("test/complete", 0.5)
    assert session.names() == [] and obs.tracer().events() == []


# -- the two entry points ---------------------------------------------------

def _updater():
    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (32, 12)).astype(np.float32)
    t = rng.randint(0, 3, 32).astype(np.int32)
    comm = ct.create_communicator("flat")
    model = Classifier(MLP(n_units=16, n_out=3, seed=0))
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.05), comm).setup(model)
    it = SerialIterator(TupleDataset(x, t), 8, shuffle=False)
    return StandardUpdater(it, opt), opt


def test_one_update_yields_exactly_the_training_spans(tmp_path):
    updater, _ = _updater()
    updater.update()        # compiles
    with Session(tmp_path) as session:
        updater.update()
    assert session.names() == [
        "train/input_stall", "train/convert", "train/optimizer_update",
        "train/step_dispatch"]
    assert session.one("train/input_stall")[3] == {
        "iterator": "SerialIterator"}
    assert session.inside("train/step_dispatch", "train/optimizer_update")
    assert not session.inside("train/convert", "train/optimizer_update")
    assert session.one("train/input_stall")[2] \
        <= session.one("train/convert")[1]


def _under(fn, context):
    with context or contextlib.nullcontext():
        return fn()


def test_the_step_program_is_the_same_with_and_without_a_listener(
        tmp_path):
    """The span layer opens no ``jax.named_scope``: the program traced
    under a profiler session is letter for letter the one traced with
    nobody listening, its scope names (``debug_info``) included."""
    def texts():
        updater, opt = _updater()
        updater.update()
        lowered = opt.actual_optimizer.traced_step().lower()
        return lowered.as_text(), lowered.as_text(debug_info=True)
    # one call site for both, so the locations in the text agree
    quiet, heard = [_under(texts, Session(tmp_path) if listen else None)
                    for listen in (False, True)]
    assert heard == quiet
    assert "mn_bucket_pmean" in quiet[1]    # the default scope names stay
    assert "train.grad_exchange" not in quiet[1]
    assert "train/" not in heard[1]


def _engine():
    from chainermn_tpu.serving import ServingEngine
    lm = TransformerLM(n_vocab=64, d_model=32, n_heads=2, n_layers=1,
                       max_len=64, seed=0)
    return ServingEngine(lm, num_pages=16, page_size=8, max_batch=4,
                         max_context=32, prefix_cache=True)


def test_one_step_yields_exactly_the_serving_spans(tmp_path):
    from chainermn_tpu.serving import Request
    rng = np.random.RandomState(0)
    eng = _engine()
    shared = rng.randint(0, 64, 8)
    first = Request(np.concatenate([shared, rng.randint(0, 64, 5)]),
                    max_new_tokens=8, arrival_time=0.0, request_id=5)
    eng.submit(first)
    eng.step(now=1.0)       # compiles prefill and decode
    second = Request(np.concatenate([shared, rng.randint(0, 64, 4)]),
                     max_new_tokens=8, arrival_time=1.0, request_id=6)
    eng.submit(second)
    eng.step(now=1.5)       # compiles the prefix-hit prefill
    third = Request(rng.randint(0, 64, 9), max_new_tokens=1,
                    arrival_time=1.75, request_id=7)
    eng.submit(third)
    with Session(tmp_path) as session:
        eng.step(now=2.0)   # admits the third beside two that decode
    assert eng.prefix_hits == 1
    assert session.names() == [
        "serve/step", "serve/capacity", "serve/admission",
        "serve/prefill", "serve/decode_window", "serve/decode_build",
        "serve/decode_dispatch", "serve/decode_fetch", "serve/record"]
    for child, parent in [("serve/capacity", "serve/step"),
                          ("serve/admission", "serve/step"),
                          ("serve/prefill", "serve/admission"),
                          ("serve/decode_window", "serve/step"),
                          ("serve/decode_build", "serve/decode_window"),
                          ("serve/decode_dispatch", "serve/decode_window"),
                          ("serve/decode_fetch", "serve/decode_window"),
                          ("serve/record", "serve/step")]:
        assert session.inside(child, parent), (child, parent)
    # only the stats a reader under benchmark/layer_metrics/ takes
    step = session.one("serve/step")[3]
    assert step == {"running": 2,
                    "used_pages": eng.allocator.used_pages,
                    "num_pages": 16}
    prefill = session.one("serve/prefill")[3]
    assert prefill == {"request": 7, "prompt": 9, "matched": 0,
                       "wait_ms": 250.0}
    window = session.one("serve/decode_window")[3]
    assert (window["batch"], window["bucket"]) == (2, 2)
    for bare in ("serve/capacity", "serve/admission", "serve/record"):
        assert session.one(bare)[3] == {}


def test_a_session_alone_pays_for_spans_and_nothing_else(tmp_path,
                                                         monkeypatch):
    """What only the ring or the registry would take (instants, the
    retroactive queue wait, a request's lane, the counters) is not
    computed under a profiler session alone: the spans that time the
    host would otherwise time their own instrumentation."""
    from chainermn_tpu.serving import Request
    from chainermn_tpu.serving.engine import ServingEngine
    obs.reset_registry()
    lanes = []
    monkeypatch.setattr(ServingEngine, "_req_tid",
                        staticmethod(lambda req: lanes.append(req) or 1))
    eng = _engine()
    upd, _ = _updater()
    upd.update()            # compiles the step
    with Session(tmp_path) as session:
        eng.submit(Request(np.arange(9), max_new_tokens=1,
                           arrival_time=0.0, request_id=3))
        eng.step(now=0.5)   # admits, and retires at the first token
        upd.update()
    assert len(eng.completed) == 1
    assert "serve/prefill" in session.names()
    assert "train/step_dispatch" in session.names()
    assert lanes == []
    assert obs.registry().to_dict() == obs.MetricsRegistry().to_dict()
    assert obs.tracer().events() == []


def test_one_requests_spans_share_its_request_id(tmp_path):
    from chainermn_tpu.serving import Request
    rng = np.random.RandomState(1)
    eng = _engine()
    shared = rng.randint(0, 64, 8)
    with Session(tmp_path) as session:
        eng.submit(Request(np.concatenate([shared, rng.randint(0, 64, 5)]),
                           max_new_tokens=4, arrival_time=0.0,
                           request_id=11))
        eng.step(now=0.0)
        eng.submit(Request(np.concatenate([shared, rng.randint(0, 64, 3)]),
                           max_new_tokens=4, arrival_time=0.0,
                           request_id=12))
        eng.step(now=0.5)
    miss = session.one("serve/prefill")[3]
    hit = session.one("serve/suffix_prefill")[3]
    assert (miss["request"], miss["matched"]) == (11, 0)
    assert (hit["request"], hit["prompt"], hit["matched"]) == (12, 11, 8)
    assert hit["wait_ms"] == 500.0
    # both on the real thread, inside an admission pass
    admissions = [e for e in session.events if e[0] == "serve/admission"]
    for e in (session.one("serve/prefill"),
              session.one("serve/suffix_prefill")):
        assert any(a[1] <= e[1] and e[2] <= a[2] for a in admissions)
