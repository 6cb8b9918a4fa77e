"""End-to-end observability acceptance (ISSUE 14): seeded runs of the
three subsystems each produce a schema-valid Chrome-trace shard whose
span names cover the committed classification, the rank shards merge
losslessly, the metrics registry carries the committed scheduler/
trainer/supervisor metrics — and with the DEFAULT off mode the same
runs emit nothing.

Kept tiny (tier-1 budget): one MLP trainer compile shared across the
iterator-contract grid, one 1-layer transformer engine, and the
scripted-membership elastic arc at MLP scale."""

import os
import sys

import numpy as np
import pytest

import jax

import chainermn_tpu as ct
from chainermn_tpu import observability as obs
from chainermn_tpu.core.optimizer import MomentumSGD
from chainermn_tpu.dataset import (MultithreadIterator, SerialIterator,
                                   TupleDataset)
from chainermn_tpu.models import MLP, Classifier, TransformerLM
from chainermn_tpu.training import FusedUpdater, StandardUpdater, Trainer


@pytest.fixture
def events_mode():
    prev = obs.set_mode("events")
    obs.reset_tracer()
    obs.reset_registry()
    yield
    obs.set_mode(prev)
    obs.reset_tracer()
    obs.reset_registry()


def _span_names(events):
    return {e["name"] for e in events if e["ph"] in ("B", "i")}


def _data(n=32, d=12, k=3, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.normal(0, 1, (n, d)).astype(np.float32),
            rng.randint(0, k, n).astype(np.int32))


def _trainer(tmp_path, iterator, n_iter=3, with_checkpoint=True,
             updater_cls=StandardUpdater, devices=None, **updater_kw):
    comm = ct.create_communicator("flat", devices=devices)
    model = Classifier(MLP(n_units=16, n_out=3, seed=0))
    opt = ct.create_multi_node_optimizer(
        MomentumSGD(lr=0.05), comm).setup(model)
    trainer = Trainer(updater_cls(iterator, opt, **updater_kw),
                      (n_iter, "iteration"), out=str(tmp_path))
    if with_checkpoint:
        cp = ct.create_multi_node_checkpointer(comm, name="obs",
                                               path=str(tmp_path))
        trainer.extend(cp, trigger=(2, "iteration"))
    return trainer


# -- acceptance: the 3-step trainer run --------------------------------------

def test_trainer_run_produces_schema_valid_trace(events_mode, tmp_path):
    x, t = _data()
    it = SerialIterator(TupleDataset(x, t), 8, shuffle=False)
    _trainer(tmp_path / "out", it).run()
    shard = tmp_path / "out" / "trace-rank0.jsonl"
    assert shard.exists()   # auto-exported by Trainer.run
    events = obs.read_jsonl(str(shard))
    obs.validate_events(events)
    names = _span_names(events)
    # the committed trainer-phase classification (docs/observability.md)
    assert {"train/input_stall", "train/optimizer_update",
            "train/grad_exchange/bucket0",
            "train/checkpoint_serialize"} <= names, names
    # rank-tagged: every event carries the communicator's rank lane
    assert {e["pid"] for e in events} == {0}
    # and the registry carries the per-bucket exchange counter
    c = obs.registry().get(
        "chainermn_tpu_grad_exchange_payload_bytes_total")
    assert c is not None and c.value(bucket="0", exchange="flat") > 0


@pytest.mark.parametrize("updater_cls,updater_kw",
                         [(StandardUpdater, {}),
                          (FusedUpdater, {"n_fused": 2})])
def test_one_device_run_reports_no_gradient_bytes_on_a_wire(
        events_mode, tmp_path, updater_cls, updater_kw):
    """Over a one-device axis the exchange packs and exchanges nothing
    (ISSUE 25), and the telemetry says so: no
    ``train/grad_exchange/bucketK`` instant, no payload counter."""
    x, t = _data()
    it = SerialIterator(TupleDataset(x, t), 8, shuffle=False)
    _trainer(tmp_path / "out", it, with_checkpoint=False,
             updater_cls=updater_cls, devices=jax.devices()[:1],
             **updater_kw).run()
    events = obs.read_jsonl(str(tmp_path / "out" / "trace-rank0.jsonl"))
    names = _span_names(events)
    assert "train/optimizer_update" in names
    assert not [n for n in names if n.startswith("train/grad_exchange")]
    assert obs.registry().get(
        "chainermn_tpu_grad_exchange_payload_bytes_total") is None


def test_trainer_run_off_emits_nothing(tmp_path):
    assert obs.mode() == "off"
    obs.reset_tracer()
    obs.reset_registry()
    x, t = _data()
    it = SerialIterator(TupleDataset(x, t), 8, shuffle=False)
    _trainer(tmp_path / "out", it).run()
    assert not (tmp_path / "out" / "trace-rank0.jsonl").exists()
    assert obs.tracer().events() == []
    assert obs.registry().metrics() == {}


# -- satellite: the universal input-stall counter ----------------------------

def test_input_stall_counter_every_iterator_kind_both_updaters(
        events_mode, tmp_path):
    """The contract the satellite pins: EVERY iterator kind, on BOTH
    updater paths, feeds chainermn_tpu_input_stall_ms_total — the
    accounting iterator (DevicePrefetchIterator) through its own
    stall meter, the rest through the next() wall clock."""
    from chainermn_tpu.dataset.iterators import DevicePrefetchIterator
    from chainermn_tpu.dataset.multiprocess_iterator import \
        MultiprocessIterator
    x, t = _data()

    def kinds():
        ds = TupleDataset(x, t)
        yield "SerialIterator", SerialIterator(ds, 8, shuffle=False)
        yield "MultithreadIterator", MultithreadIterator(
            ds, 8, shuffle=False, n_threads=2)
        yield "MultiprocessIterator", MultiprocessIterator(
            ds, 8, shuffle=False, n_processes=2)
        yield "DevicePrefetchIterator", DevicePrefetchIterator(
            SerialIterator(ds, 8, shuffle=False))

    for name, it in kinds():
        _trainer(tmp_path / f"std-{name}", it, n_iter=2,
                 with_checkpoint=False).run()
    # the fused path (update_scan) once — a second compile, so one kind
    it = SerialIterator(TupleDataset(x, t), 8, shuffle=False)
    _trainer(tmp_path / "fused", it, n_iter=2, with_checkpoint=False,
             updater_cls=FusedUpdater, n_fused=2).run()

    counter = obs.registry().get("chainermn_tpu_input_stall_ms_total")
    assert counter is not None
    labels = [dict(k) for k in counter.labels()]
    kinds_seen = {(l["iterator"], l["updater"]) for l in labels}
    assert {("SerialIterator", "StandardUpdater"),
            ("MultithreadIterator", "StandardUpdater"),
            ("MultiprocessIterator", "StandardUpdater"),
            ("DevicePrefetchIterator", "StandardUpdater"),
            ("SerialIterator", "FusedUpdater")} <= kinds_seen, kinds_seen
    for l in labels:
        assert counter.value(**l) >= 0


# -- acceptance: the serving request lifecycle -------------------------------

def _engine(prefix_cache=False, num_pages=16, **kw):
    from chainermn_tpu.serving import ServingEngine
    lm = TransformerLM(n_vocab=64, d_model=32, n_heads=2, n_layers=1,
                       max_len=64, seed=0)
    return ServingEngine(lm, num_pages=num_pages, page_size=8,
                         max_batch=2, max_context=32,
                         prefix_cache=prefix_cache, **kw)


def test_serving_request_lifecycle_trace(events_mode, tmp_path):
    from chainermn_tpu.serving import Request
    eng = _engine()
    rng = np.random.RandomState(0)
    req = Request(rng.randint(0, 64, 6), max_new_tokens=3,
                  arrival_time=0.0)
    eng.submit(req)
    step = 0
    while eng.running or eng.scheduler.pending():
        eng.step(now=float(step))
        step += 1
    assert len(req.tokens) == 3   # admit -> prefill -> 2 decode steps
    shard = tmp_path / "trace-rank0.jsonl"
    obs.tracer().export(str(shard))
    events = obs.read_jsonl(str(shard))
    obs.validate_events(events)
    names = _span_names(events)
    assert {"serve/queue_wait", "serve/prefill", "serve/decode_window",
            "serve/finish"} <= names, names
    # lifecycle spans ride the request's own lane; decode windows the
    # engine thread's
    req_lane = [e for e in events
                if e.get("tid") == 1 + req.request_id]
    assert {"serve/queue_wait", "serve/prefill", "serve/finish"} <= \
        _span_names(req_lane)
    # scheduler health metrics (satellite)
    reg = obs.registry()
    h = reg.get("chainermn_tpu_serving_queue_wait_ms")
    assert h is not None and h.value(tenant="default")[2] == 1
    g = reg.get("chainermn_tpu_serving_queue_depth")
    assert g is not None and g.value(tenant="default") == 0
    assert req.admit_time is not None


def test_serving_non_int_request_id_safe():
    """Request ids are caller-supplied and only ever dict keys — a
    string id must not crash the engine (the code-review finding:
    `_req_tid` used int()), trace off or on."""
    from chainermn_tpu.serving import Request
    prev = obs.set_mode("off")
    obs.reset_tracer()
    try:
        for mode in ("off", "events"):
            obs.set_mode(mode)
            eng = _engine()
            req = Request(np.arange(1, 7, dtype=np.int32),
                          max_new_tokens=2,
                          request_id=f"req-{mode}", arrival_time=0.0)
            eng.submit(req)
            step = 0
            while eng.running or eng.scheduler.pending():
                eng.step(now=float(step))
                step += 1
            assert len(req.tokens) == 2
        # deterministic synthetic lane for the string id
        assert eng._req_tid(req) == eng._req_tid(req) > 0
    finally:
        obs.set_mode(prev)
        obs.reset_tracer()
        obs.reset_registry()


@pytest.mark.parametrize("ahead, dwell_ms", [(False, 3000), (True, 4000)])
def test_readmitted_request_queue_wait_measured_from_last_admission(
        events_mode, ahead, dwell_ms):
    """Eviction + re-admit emits a SECOND queue_wait span measured from
    the previous admission (tagged readmit), never a re-span of the
    original arrival window overlapping the first (review finding).
    The dwell is pinned to the step, under both orders of a decode step:
    dispatch, fetch, record (the order an engine keeps where it cannot
    run ahead) and one run in flight (PR 46), which costs the victim one
    step more."""
    from chainermn_tpu.serving import Request
    eng = _engine(num_pages=4)   # 4 pages of 8: forces eviction at 2 seqs
    if not ahead:
        eng._may_run_ahead = lambda: False
    a = Request(np.arange(1, 9, dtype=np.int32), max_new_tokens=12,
                arrival_time=0.0)
    b = Request(np.arange(11, 19, dtype=np.int32), max_new_tokens=12,
                arrival_time=0.0)
    eng.submit(a)
    eng.submit(b)
    step = 0
    while (eng.running or eng.scheduler.pending()) and step < 80:
        eng.step(now=float(step))
        step += 1
    assert eng.evictions >= 1
    waits = [e for e in obs.tracer().events()
             if e["ph"] == "B" and e["name"] == "serve/queue_wait"]
    readmits = [e for e in waits if e["args"].get("readmit")]
    assert readmits, "re-admission emitted no tagged queue_wait span"
    # measured from the EVICTION's requeue stamp, not the original
    # arrival / prior admission: the step clock ticks 1s per step, so
    # a wait spanning the victim's whole running period would be many
    # seconds — the true re-queue dwell is three steps, and four with a
    # run in flight: the page a run needs is secured in the call that
    # dispatches it, a call before the one that lands it, so the victim
    # leaves a call earlier and comes back in the same call as before
    assert eng.evictions == 1 and len(readmits) == 1
    assert readmits[0]["args"]["duration_ms"] == dwell_ms, readmits[0]["args"]
    # the whole ring still exports schema-valid
    obs.validate_events(sorted(obs.tracer().events(),
                               key=lambda e: e["ts"]))


def test_serving_eviction_and_suffix_prefill_metrics(events_mode):
    """Eviction counters + the prefix-hit suffix-prefill span: two
    same-prefix requests on a pool sized to force an eviction."""
    from chainermn_tpu.serving import Request
    eng = _engine(prefix_cache=True, num_pages=6)
    rng = np.random.RandomState(0)
    prefix = rng.randint(0, 64, 8)
    a = Request(np.concatenate([prefix, rng.randint(0, 64, 4)]),
                max_new_tokens=4, arrival_time=0.0)
    b = Request(np.concatenate([prefix, rng.randint(0, 64, 4)]),
                max_new_tokens=4, arrival_time=0.0)
    eng.submit(a)
    eng.submit(b)
    step = 0
    while (eng.running or eng.scheduler.pending()) and step < 60:
        eng.step(now=float(step))
        step += 1
    names = _span_names(obs.tracer().events())
    assert eng.prefix_hits >= 1
    assert "serve/suffix_prefill" in names, names
    reg = obs.registry()
    if eng.evictions:
        assert "serve/evict" in names
        assert reg.get("chainermn_tpu_serving_evictions_total") \
            .value(tenant="default") == eng.evictions
    if eng.forks:
        assert reg.get("chainermn_tpu_serving_forks_total").value() \
            == eng.forks


# -- acceptance: the elastic shrink/regrow timeline --------------------------

def test_elastic_shrink_regrow_timeline(events_mode, tmp_path):
    """The scripted-membership supervisor arc (the ISSUE 10 harness)
    with tracing on: preempt detect -> resolve -> rebuild -> snapshot
    sync all appear, rank/epoch tags follow the resizes, and
    FailureRecovery.stats lands in the registry as gauges."""
    from tests.resilience_tests.test_elastic import (
        _elastic_trainer, _ScriptedMembership, _subset_factory)
    from chainermn_tpu.communicators import FaultSchedule

    split = {(0,): 2, (0, 1): 4}
    sched = FaultSchedule([dict(op="bcast_obj", nth=7)], seed=0)
    membership = _ScriptedMembership(views=[(0,), (0, 1)])
    trainer, model, opt, rec = _elastic_trainer(
        tmp_path / "el", sched, membership, _subset_factory(split))
    orig_resolve = membership.resolve

    def resolve(expect=None, timeout_ms=None):
        v = orig_resolve(expect, timeout_ms)
        if v.members == (0,):
            membership.joins = (1,)
        return v
    membership.resolve = resolve

    trainer.run()
    assert rec.stats["resizes"] == 2

    shard = tmp_path / "el" / "trace-rank0.jsonl"
    assert shard.exists()
    events = obs.read_jsonl(str(shard))
    obs.validate_events(events)
    names = _span_names(events)
    assert {"elastic/preempt_detect", "elastic/resolve",
            "elastic/rebuild", "elastic/snapshot_sync",
            "recover/consensus_load", "recover/quiesce",
            "train/optimizer_update"} <= names, names
    # epoch tags advance with the rebuilt incarnations
    epochs = {e["args"]["epoch"] for e in events
              if e.get("args", {}).get("epoch") is not None}
    assert {1, 2} <= epochs, epochs
    # FailureRecovery.stats folded into the registry (tentpole item c)
    reg = obs.registry()
    assert reg.get("chainermn_tpu_recovery_resizes").value() == 2
    assert reg.get("chainermn_tpu_recovery_ranks_lost").value() == 1
    assert reg.get("chainermn_tpu_recovery_ranks_joined").value() == 1
    assert reg.get("chainermn_tpu_recovery_recoveries").value() >= 1
