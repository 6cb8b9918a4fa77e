"""What ``jax.profiler.ProfileData`` leaves out of an ``.xplane.pb``:
each device operation's METADATA.

An ``XLA Ops`` event carries its own stats only (offset, duration), and
those are what ``ProfileData`` hands out.  What the profiler knows of
the OPERATION hangs on the plane's ``event_metadata`` entry that the
event's ``metadata_id`` names: its whole HLO text as ``name``, and as
stats ``tf_op`` (the instruction's ``op_name``: the program's
``jax.named_scope`` path with the primitive last and a colon after it,
e.g. ``jit(rank_step)/mn_forward_backward/jvp(blocks)/3/~mlp/tanh:``),
``hlo_category``, ``flops``, ``bytes_accessed``, ``source`` and
``program_id``.  A stat's value is a number, a string, or a REFERENCE
into the plane's ``stat_metadata`` names.

This file decodes the few messages of ``xplane.proto`` that takes
(``XSpace.planes``; ``XPlane`` name, lines, ``event_metadata``,
``stat_metadata``; ``XLine`` name, ``timestamp_ns``, events; ``XEvent``
``metadata_id``, ``offset_ps``, ``duration_ps``; ``XEventMetadata`` id,
name, stats; ``XStat``; ``XStatMetadata`` id, name) from the protobuf
wire format in plain Python: no TensorFlow, no ``protobuf`` package,
which the machine with the chip need not have.  Planes and lines that
are not asked for are skipped by their length, unparsed.  Times are
seconds on ``ProfileData``'s clock (a line's ``timestamp_ns`` plus the
event's offset).
"""

from __future__ import annotations

import dataclasses
import struct

OP_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
STRING_STATS = ("tf_op", "hlo_category")


@dataclasses.dataclass
class Op:
    """One ``XLA Ops`` event with what its metadata says of it."""
    name: str            # the whole HLO text, as ``trace_reduce.Event.name``
    start: float
    dur: float
    tf_op: str           # "" where the compiler made the operation itself
    category: str        # ``hlo_category``, "" where the plane has none

    @property
    def end(self):
        return self.start + self.dur


# -- the wire format ------------------------------------------------

def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message: a varint or
    fixed-width field's value is its integer, a length-delimited one's
    a ``memoryview`` of its bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            value = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 5:
            value = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an "
                             "xplane.proto message")
        yield number, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _signed(value):
    """An ``int64`` field's varint is its two's complement."""
    return value - (1 << 64) if value >= 1 << 63 else value


def name_of(buf):
    """The ``name`` (field 2) of a plane or a line: it is written ahead
    of what the message holds, so this stops early."""
    for number, _, value in fields(buf):
        if number == 2:
            return _text(value)
    return ""


def _map_entry(buf):
    """The value message of one ``map<int64, Message>`` entry."""
    for number, _, value in fields(buf):
        if number == 2:
            return value
    return memoryview(b"")


def _stat(buf, stat_names):
    """``(stat name, value)`` of one ``XStat``."""
    name, value = None, None
    for number, _, v in fields(buf):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


# -- planes ------------------------------------------------

def planes(data):
    """``[(plane name, the plane's bytes)]`` of an ``XSpace``."""
    return [(name_of(plane), plane)
            for number, wire, plane in fields(memoryview(data))
            if number == 1 and wire == 2]


def first_device(named_planes):
    """The device plane that sorts first, as ``Trace.devices[0]``."""
    names = sorted(n for n, _ in named_planes if n.startswith(DEVICE_PLANE))
    return names[0] if names else None


def plane_tables(plane, keep=None):
    """``(stat names by id, event metadata by id, lines)`` of one plane:
    an event metadata is ``(name, {stat name: value})``, holding the
    stats named in ``keep`` (all, where ``keep`` is None); a line is its
    bytes, unparsed."""
    stat_names, raw_meta, lines = {}, [], []
    for number, _, value in fields(plane):
        if number == 5:
            sid, sname = 0, ""
            for n, _, v in fields(_map_entry(value)):
                if n == 1:
                    sid = v
                elif n == 2:
                    sname = _text(v)
            stat_names[sid] = sname
        elif number == 4:
            raw_meta.append(_map_entry(value))
        elif number == 3:
            lines.append(value)
    metadata = {}
    for buf in raw_meta:
        mid, name, stats = 0, "", {}
        for n, _, v in fields(buf):
            if n == 1:
                mid = v
            elif n == 2:
                name = _text(v)
            elif n == 5:
                key, value = _stat(v, stat_names)
                if keep is None or key in keep:
                    stats[key] = value
        metadata[mid] = (name, stats)
    return stat_names, metadata, lines


def line_events(line):
    """``[(metadata id, start s, duration s)]`` of one ``XLine``; the
    events' own stats are skipped.  A traced serving
    window holds over a million events, so an event's three varints are
    read in place (no generator, no slice a field)."""
    timestamp_ns, events = 0, []
    add = events.append
    for number, _, value in fields(line):
        if number == 3:
            timestamp_ns = _signed(value)
        elif number == 4:
            buf = bytes(value)
            pos, end, got = 0, len(buf), [0, 0, 0, 0]
            while pos < end:
                key = buf[pos]
                pos += 1
                if key & 7 == 0 and key < 0x20:   # fields 1-3, a varint
                    result = shift = 0
                    while True:
                        b = buf[pos]
                        pos += 1
                        result |= (b & 0x7F) << shift
                        if b < 0x80:
                            break
                        shift += 7
                    got[key >> 3] = result
                elif key == 0x22:                 # field 4: a stat, skipped
                    size = buf[pos]
                    pos += 1
                    if size >= 0x80:
                        size, pos = _varint(buf, pos - 1)
                    pos += size
                else:                             # anything else, properly
                    got = None
                    break
            if got is None:
                got = [0, 0, 0, 0]
                for n, _, v in fields(value):
                    if n in (1, 2, 3):
                        got[n] = v
            add((got[1], got[2], got[3]))
    base = timestamp_ns * 1e-9
    return [(mid, base + offset_ps * 1e-12, duration_ps * 1e-12)
            for mid, offset_ps, duration_ps in events]


def device_ops(path, device=None):
    """Every ``XLA Ops`` event of one device plane (the first, unless
    ``device`` names another) as an :class:`Op`, in the file's order.
    A file with no such plane or line gives an empty list."""
    with open(path, "rb") as f:
        data = f.read()
    named = planes(data)
    device = device or first_device(named)
    for name, plane in named:
        if name != device:
            continue
        _, metadata, lines = plane_tables(plane, keep=STRING_STATS)
        for line in lines:
            if name_of(line) != OP_LINE:
                continue
            out = []
            for mid, start, dur in line_events(line):
                text, stats = metadata.get(mid, ("", {}))
                out.append(Op(text, start, dur, stats.get("tf_op") or "",
                              stats.get("hlo_category") or ""))
            return out
    return []
