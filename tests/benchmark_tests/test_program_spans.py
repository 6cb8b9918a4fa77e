"""The program's spans read back from the profiler's trace
(``benchmark/program_spans.py``) and the per-layer readers over them:
self time and idle attribution on made-up events, every new reader on a
traced CPU rehearsal of a training and a serving cell and on the trace
recorded before the program had spans (where each finds nothing), the
device-side readers on made-up events with a device line, and the two
cells PR 24 prepared through the tiny rehearsal."""

import os
import shutil

import pytest

from benchmark import harness, program_spans, trace_reduce, tracing
from benchmark.program_spans import Span
from benchmark.trace_reduce import Event, Trace

from . import _tiny
from .test_rehearsal_serve import SERVE_LIMIT
from .test_rehearsal_train import LIMITS

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_train.xplane.pb")
TRAIN_LIMITS = LIMITS["gpt2m-train-1chip"]

SPAN_READERS = {
    "train": ["train.input_ms", "train.step_dispatch_ms",
              "train.update_self_ms"],
    "serve": ["serve.queue_wait_ms", "serve.lanes_in_use",
              "serve.bucket_fill", "serve.pool_occupancy",
              "serve.prefix_hit_share", "serve.schedule_ms",
              "serve.fetch_wait_ms", "serve.step_host_ms"],
}
DEVICE_READERS = ["train.input_exposed_ms", "dp.collective_ms",
                  "dp.collective_exposed_ms"]
ALL_READERS = SPAN_READERS["train"] + SPAN_READERS["serve"] + DEVICE_READERS


def _span(name, start, dur, thread="main", **stats):
    return Span(name, thread, start, dur, stats)


def _view(spans, ops=(), modules=(), lo=0.0, hi=10.0, traffic=None):
    class Run:
        pass
    Run.traffic = traffic or {"programs": {"step": ["rank_step"]}}
    trace = Trace({"/device:TPU:0": list(modules)},
                  {"/device:TPU:0": list(ops)}, [])
    linked = program_spans.link(spans)
    return {"trace": trace, "lo": lo, "hi": hi, "window_s": hi - lo,
            "result": {}, "run": Run,
            "program_spans": [s for s in linked
                              if s.start >= lo and s.end <= hi]}


def _read(metric, view):
    return harness.load_module("layer_metrics", metric).read(view)


# -- the tree, self time, idle under spans ----------------------------------

def test_self_time_is_duration_less_the_children_on_the_same_thread():
    view = _view([
        _span("serve/step", 0.0, 1.0),
        _span("serve/admission", 0.1, 0.5),
        _span("serve/prefill", 0.2, 0.3),          # a grandchild
        _span("serve/record", 0.7, 0.1),
        _span("serve/step", 0.2, 0.3, thread="other"),  # not a child
        _span("serve/step", 2.0, 0.5)])
    assert program_spans.self_time(view, "serve/step") == \
        pytest.approx([0.4, 0.3, 0.5])
    assert program_spans.self_time(view, "serve/admission") == \
        pytest.approx([0.2])
    prefill, = program_spans.named(view, "serve/prefill")
    assert prefill.parent.name == "serve/admission"
    assert prefill.ancestor("serve/step").start == 0.0
    assert prefill.ancestor("train/convert") is None
    assert program_spans.durations(view, "serve/nothing") == []
    assert program_spans.median_ms([]) is None


def test_stats_and_per_step_sums():
    view = _view([
        # a step cut at the window's start: its convert alone lies
        # inside, and is lent to no other step
        _span("bench/update", -0.5, 0.9),
        _span("train/input_stall", -0.5, 0.5),
        _span("train/convert", 0.0, 0.3),
        _span("bench/update", 0.5, 0.4),
        _span("train/input_stall", 0.5, 0.010),
        _span("train/convert", 0.510, 0.030),
        _span("bench/update", 1.0, 0.5),
        _span("train/input_stall", 1.0, 0.020),
        _span("train/convert", 1.020, 0.040),
        _span("bench/update", 2.0, 0.5),
        _span("train/input_stall", 2.0, 0.030),
        _span("train/convert", 2.030, 0.050),
        _span("serve/decode_window", 3.0, 0.1, batch=3, bucket=4),
        _span("serve/decode_window", 4.0, 0.1, batch=5, bucket=8),
        _span("serve/decode_window", 5.0, 0.1)])
    assert program_spans.stat(view, "serve/decode_window", "batch") == [3, 5]
    assert _read("train.input_ms", view) == pytest.approx(60.0)
    assert _read("serve.lanes_in_use", view) == pytest.approx(4.0)
    assert _read("serve.bucket_fill", view) == pytest.approx(100 * 8 / 12)
    assert _read("serve.schedule_ms", view) is None


def test_idle_under_counts_the_idle_seconds_inside_the_spans_named():
    ops = [Event("%fusion.1 = f32[8]", 0.0, 1.0),
           Event("%fusion.2 = f32[8]", 2.0, 1.0),
           Event("%fusion.3 = f32[8]", 5.0, 1.0)]
    view = _view([_span("train/input_stall", 0.5, 1.0),     # idle 1.0 .. 1.5
                  _span("train/convert", 1.5, 0.25),        # idle, all of it
                  _span("train/optimizer_update", 3.5, 1.0),    # not named
                  _span("train/input_stall", 5.5, 1.0)],    # idle 6.0 .. 6.5
                 ops=ops, lo=0.0, hi=8.0)
    assert program_spans.device_idle(view) == \
        [(1.0, 2.0), (3.0, 5.0), (6.0, 8.0)]
    names = ("train/input_stall", "train/convert")
    assert program_spans.idle_under(view, names) == pytest.approx(1.25)
    assert program_spans.idle_under(view, ("train/none",)) is None
    # two steps in the window
    assert _read("train.input_exposed_ms", view) == pytest.approx(625.0)
    # a convert alone in the window: idle under it, and no step to count
    alone = _view([_span("train/convert", 1.5, 0.25)], ops=ops, hi=8.0)
    assert _read("train.input_exposed_ms", alone) is None
    assert _read("train.input_ms", alone) is None


def test_idle_seconds_go_to_the_innermost_span_and_the_rest_outside():
    idle = [(1.0, 2.0), (3.0, 5.0), (6.0, 8.0)]
    spans = program_spans.link([
        _span("bench/window", 0.0, 7.0),
        _span("bench/update", 0.5, 3.0),                # 0.5 .. 3.5
        _span("train/optimizer_update", 1.25, 2.0),     # 1.25 .. 3.25
        _span("train/step_dispatch", 1.5, 1.0)])        # 1.5 .. 2.5
    rows = program_spans.idle_by_innermost(spans, idle)
    assert rows["train/step_dispatch"] == pytest.approx(0.5)    # 1.5 .. 2
    assert rows["train/optimizer_update"] == pytest.approx(0.5)  # 2 x .25
    assert rows["bench/update"] == pytest.approx(0.5)   # 1..1.25, 3.25..3.5
    assert rows["bench/window"] == pytest.approx(2.5)   # 3.5 .. 5, 6 .. 7
    assert rows["outside"] == pytest.approx(1.0)        # 7 .. 8
    assert sum(rows.values()) == pytest.approx(trace_reduce.covered(idle))


def test_the_hosts_own_work_a_step_leaves_out_what_it_waited_for():
    view = _view([
        _span("serve/step", 0.0, 0.100, running=2, used_pages=10,
              num_pages=40),
        _span("serve/capacity", 0.001, 0.002),
        _span("serve/admission", 0.010, 0.040),
        _span("serve/prefill", 0.015, 0.030, wait_ms=4.0, prompt=100,
              matched=0),
        _span("serve/decode_fetch", 0.060, 0.030),
        _span("serve/step", 1.0, 0.050),
        _span("serve/decode_fetch", 1.010, 0.030),
        _span("serve/suffix_prefill", 2.0, 0.01, wait_ms=8.0, prompt=100,
              matched=50),
        _span("serve/step", 3.0, 0.001, running=0, used_pages=0,
              num_pages=40),                    # an idle spin: left out
        _span("serve/admission", 3.0002, 0.0005)])
    assert _read("serve.schedule_ms", view) == pytest.approx(12.0)
    assert _read("serve.pool_occupancy", view) == pytest.approx(25.0)
    assert _read("serve.step_host_ms", view) == pytest.approx(30.0)
    assert _read("serve.fetch_wait_ms", view) == pytest.approx(30.0)
    assert _read("serve.queue_wait_ms", view) == pytest.approx(6.0)
    assert _read("serve.prefix_hit_share", view) == pytest.approx(25.0)


def test_collective_time_a_step_and_its_exposed_part():
    """Names as the chip gave them: the all-reduce is ``psum.14``, known
    as a collective by its opcode alone."""
    ops = [Event("%fusion.1 = bf16[8]{0:T(1024)} fusion(bf16[8] %p)", 0, 2.0),
           Event("%psum.14 = f32[16]{0:T(1024)} all-reduce(f32[16]{0} "
                 "%pad_maximum_fusion), channel_id=1", 1.0, 3.0),
           Event("%while.2 = (f32[8]) while((f32[8]) %tuple)", 0.0, 5.0),
           Event("%fusion.9 = f32[16] fusion(f32[16]{0} %psum.14)", 4.5, 0.5)]
    modules = [Event("jit_rank_step(1)", 0.0, 2.4),
               Event("jit_rank_step(1)", 2.5, 2.5),
               Event("jit_other(2)", 5.0, 0.5)]
    view = _view([], ops=ops, modules=modules, lo=0.0, hi=6.0)
    assert trace_reduce.collective_seconds(view["trace"], 0.0, 6.0) == \
        (0.0, 0.0)      # by name it finds none
    assert _read("dp.collective_ms", view) == pytest.approx(1500.0)
    assert _read("dp.collective_exposed_ms", view) == pytest.approx(1000.0)
    clipped = _view([], ops=ops, modules=modules[:1], lo=0.0, hi=3.0)
    assert _read("dp.collective_ms", clipped) == pytest.approx(2000.0)
    quiet = _view([], ops=ops[:1], modules=modules, lo=0.0, hi=6.0)
    assert _read("dp.collective_ms", quiet) is None
    assert _read("dp.collective_exposed_ms", quiet) is None


# -- on real traces ---------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One traced CPU rehearsal of a training and of a serving cell, in
    trace directories of their own: another test file's traced run of
    the same cell, on another worker, empties the shared one."""
    root = tmp_path_factory.mktemp("traces")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tracing, "trace_dir",
                      lambda run: str(root / run.workload["name"]))
        return {
            "train": _tiny.result(_tiny.tiny_run(
                "gpt2m-train-1chip", trace=True, limits=TRAIN_LIMITS)),
            "serve": _tiny.result(_tiny.tiny_run(
                "gpt2m-serve-chat", seconds=2.0, trace=True,
                limits=SERVE_LIMIT))}


@pytest.mark.parametrize("kind,metric", [
    (kind, m) for kind in sorted(SPAN_READERS) for m in SPAN_READERS[kind]])
def test_reader_gives_a_number_on_a_traced_cpu_rehearsal(
        rehearsed, kind, metric):
    line = rehearsed[kind]
    assert line["correct"] is True
    value = line["metrics"][metric]["value"]
    assert value >= 0
    if metric.endswith("_ms"):
        assert value < 1000.0       # a span of a tiny step, in ms
    # no operation ran on a device: the device-side readers say nothing
    assert not set(DEVICE_READERS) & set(line["metrics"])


@pytest.fixture(scope="module")
def recorded_view(tmp_path_factory):
    """The view of the trace recorded on a v5e by PR 23, whose program
    had no spans on the profiler's clock."""
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded TPU trace")
    root = tmp_path_factory.mktemp("recorded")
    where = root / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "tiny_train.xplane.pb")

    class Kept:
        dir = str(root)

    class Run:
        traffic = {"programs": {"step": ["rank_step"]}}
    trace = trace_reduce.load(RECORDED)
    lo, hi = trace_reduce.window(trace, "bench/window")
    return {"trace": trace, "lo": lo, "hi": hi, "window_s": hi - lo,
            "result": {"tracing": Kept}, "run": Run}


def test_the_recorded_trace_holds_the_drivers_spans_and_no_program_span(
        recorded_view):
    names = {s.name for s in program_spans.spans(recorded_view)}
    assert "bench/update" in names
    assert not any(n.startswith(program_spans.PROGRAM) for n in names)
    update = program_spans.named(recorded_view, "bench/update")[0]
    assert update.parent.name == "bench/window"


@pytest.mark.parametrize("metric", ALL_READERS)
def test_reader_finds_nothing_in_a_trace_without_program_spans(
        recorded_view, metric):
    assert _read(metric, recorded_view) is None


# -- the cells PR 24 prepared -----------------------------------------------
# ``gpt2m-train-dp4`` and ``gpt2m-serve-saturated`` are files without
# entries, as ResNet's cell is: on the chip their rates spread more widely
# than a new cell may (PERF.md sections 6 and 7).  Added here by entries
# alone, as a later PR would.

PREPARED = {"gpt2m-train-dp4": "gpt2m-train-1chip",
            "gpt2m-serve-saturated": "gpt2m-serve-chat"}


def _manifest_with_the_prepared_cells():
    """Each cell beside the one it is a variant of: an entry in
    ``workloads`` and its name wherever its sibling is listed."""
    m = harness.load_manifest()
    assert not set(PREPARED) & {w["name"] for w in m["workloads"]}
    return _tiny.listed_beside(m, PREPARED)


@pytest.fixture
def prepared(monkeypatch):
    monkeypatch.setattr(_tiny, "manifest",
                        _manifest_with_the_prepared_cells)


def test_the_four_chip_cell_runs_on_four_devices(prepared):
    run = _tiny.tiny_run("gpt2m-train-dp4", n_devices=4,
                         limits=TRAIN_LIMITS)
    assert run.traffic["per_chip_batch"] * len(run.devices) == 8
    line = _tiny.result(run)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert set(line["metrics"]) == {"train_samples_per_s_per_chip",
                                    "setup_s"}


def test_the_saturated_cell_runs_and_offers_more_than_the_chat_cell(
        prepared):
    chat, saturated = (harness.load_traffic({"traffic": name})
                       for name in ("gpt2m-serve-chat",
                                    "gpt2m-serve-saturated"))
    assert saturated["mix"]["rate"] == pytest.approx(1.5 * 3.0)
    assert saturated["mix"]["rate"] > chat["mix"]["rate"]
    assert saturated["engine"] == chat["engine"]
    line = _tiny.result(_tiny.tiny_run(
        "gpt2m-serve-saturated", seconds=2.0, limits=SERVE_LIMIT))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_every_cell_lists_the_new_readers_of_its_kind():
    manifest = harness.load_manifest()
    for cell, kind in (("gpt2m-train-1chip", "train"),
                       ("gpt2m-serve-chat", "serve")):
        mine = {m["name"] for m in harness.metrics_for(
            manifest, cell, "per_layer")}
        assert set(SPAN_READERS[kind]) <= mine
        assert ("train.input_exposed_ms" in mine) == (kind == "train")
    # the exchange's readers wait, with their cell, outside the manifest
    assert not [m for m in manifest["per_layer"]
                if m["name"].startswith("dp.")]
