"""``benchmark/xplane_wire.py``: the few ``xplane.proto`` messages decoded
from the wire format in plain Python, on the trace recorded on a TPU v5e
(``data/tiny_train.xplane.pb``).  The numbers are what ISSUE 38 read from
the same file through ``xplane_pb2``; where that module loads (this CPU
box; the machine with the chip need not have it) the two readers are
held equal event by event."""

import importlib.util
import os

import pytest

from benchmark import trace_reduce, xplane_wire

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tiny_train.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_serve_scoped.xplane.pb")


@pytest.fixture(scope="module")
def ops():
    return xplane_wire.device_ops(RECORDED)


def _xplane_pb2():
    """TensorFlow's generated ``xplane_pb2``, loaded from its file: the
    module needs ``google.protobuf`` alone, and importing it through the
    ``tensorflow`` package takes half a minute."""
    try:
        package = importlib.util.find_spec("tensorflow")
        path = os.path.join(package.submodule_search_locations[0], "tsl",
                            "profiler", "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    except Exception as e:  # noqa: BLE001 — any failure to load it
        pytest.skip(f"xplane_pb2 does not load here: {e}")


def _us(ops, keep):
    return sum(op.dur for op in ops if keep(op)) * 1e6


def test_the_reader_needs_neither_tensorflow_nor_protobuf():
    with open(xplane_wire.__file__) as f:
        source = f.read()
    for module in ("tensorflow", "google.protobuf", "xplane_pb2"):
        assert f"import {module}" not in source
        assert f"from {module}" not in source


@pytest.mark.parametrize("needle,us", [
    ("mn_forward_backward", 802.5), ("mn_optimizer_update", 128.9),
    ("mn_allreduce_grad", 7.6)])
def test_device_time_under_each_phase_of_the_recorded_step(ops, needle, us):
    assert _us(ops, lambda op: needle in op.tf_op) \
        == pytest.approx(us, abs=0.1)


def test_every_operation_of_the_first_chip_with_its_metadata(ops):
    assert len(ops) == 5510
    bare = [op for op in ops if not op.tf_op]
    assert len(bare) == 3640
    assert _us(bare, lambda op: True) == pytest.approx(126.3, abs=0.1)
    # what the compiler made itself: prefetches and layout copies
    by_category = {}
    for op in bare:
        by_category[op.category] = by_category.get(op.category, 0) + 1
    assert by_category["copy-start"] == by_category["copy-done"] == 1650
    assert "data formatting" in by_category
    scoped = next(op for op in ops if "mn_forward_backward" in op.tf_op)
    assert scoped.tf_op.startswith("jit(rank_step)/mn_forward_backward/")
    assert scoped.name.startswith("%") and " = " in scoped.name
    assert all(op.dur >= 0 and op.end >= op.start for op in ops)


def test_names_and_times_are_profile_datas(ops):
    """Same events, same order, the same clock as ``trace_reduce.load``
    (``ProfileData`` rounds to whole nanoseconds)."""
    trace = trace_reduce.load(RECORDED)
    theirs = trace.ops[trace.devices[0]]
    assert len(theirs) == len(ops)
    for mine, other in zip(ops, theirs):
        assert mine.name == other.name
        assert mine.start == pytest.approx(other.start, abs=1.5e-9)
        assert mine.dur == pytest.approx(other.dur, abs=1.5e-9)
    assert xplane_wire.first_device(
        xplane_wire.planes(open(RECORDED, "rb").read())) == trace.devices[0]


@pytest.mark.parametrize("path", [RECORDED, SCOPED],
                         ids=["tiny_train", "tiny_serve_scoped"])
def test_agrees_with_xplane_pb2_where_that_imports(path):
    if not os.path.exists(path):
        pytest.skip("no such recorded trace")
    xplane_pb2 = _xplane_pb2()
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    plane = min((p for p in space.planes
                 if p.name.startswith(xplane_wire.DEVICE_PLANE)),
                key=lambda p: p.name)
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}

    def stat(meta, key):
        for s in meta.stats:
            if stat_names[s.metadata_id] == key:
                return s.str_value or stat_names.get(s.ref_value, "")
        return ""
    line = next(li for li in plane.lines if li.name == xplane_wire.OP_LINE)
    mine = xplane_wire.device_ops(path)
    assert len(mine) == len(line.events) > 0
    for op, e in zip(mine, line.events):
        meta = plane.event_metadata[e.metadata_id]
        assert op.name == meta.name
        assert op.tf_op == stat(meta, "tf_op")
        assert op.category == stat(meta, "hlo_category")
        assert op.dur == pytest.approx(e.duration_ps * 1e-12, abs=1e-15)
        assert op.start == pytest.approx(
            (line.timestamp_ns + e.offset_ps * 1e-3) * 1e-9, abs=1e-12)
    # every stat of an operation's metadata is readable, not only those
    _, metadata, _ = xplane_wire.plane_tables(dict(xplane_wire.planes(
        open(path, "rb").read()))[plane.name])
    keys = {k for _, stats in metadata.values() for k in stats}
    assert {"tf_op", "hlo_category", "flops", "bytes_accessed",
            "program_id"} <= keys


def test_a_file_with_no_device_plane_gives_nothing(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert xplane_wire.device_ops(str(empty)) == []
    # one host plane alone: field 1 (planes), a plane whose name is field 2
    name = b"/host:CPU"
    plane = bytes([0x12, len(name)]) + name
    empty.write_bytes(bytes([0x0A, len(plane)]) + plane)
    assert [n for n, _ in xplane_wire.planes(empty.read_bytes())] \
        == ["/host:CPU"]
    assert xplane_wire.device_ops(str(empty)) == []


def test_the_recorders_slimmed_copy_reads_the_same(tmp_path, ops):
    """``benchmark/tools/record_tiny_serve.py`` keeps its trace small by
    cutting plane ``/host:metadata`` and each device event's own stats:
    every reader gives from the copy what it gives from the file."""
    from benchmark.tools import record_tiny_serve
    with open(RECORDED, "rb") as f:
        whole = f.read()
    slim = record_tiny_serve.slimmed(whole)
    assert len(slim) < len(whole) / 2
    assert "/host:metadata" in dict(xplane_wire.planes(whole))
    assert "/host:metadata" not in dict(xplane_wire.planes(slim))
    copy = tmp_path / "slim.xplane.pb"
    copy.write_bytes(slim)
    assert xplane_wire.device_ops(str(copy)) == ops
    a, b = trace_reduce.load(RECORDED), trace_reduce.load(str(copy))
    assert (a.modules, a.ops, a.spans) == (b.modules, b.ops, b.spans)
    assert record_tiny_serve.slimmed(slim) == slim
