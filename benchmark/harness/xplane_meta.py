"""The part of an ``.xplane.pb`` that ``jax.profiler.ProfileData`` does
not show: each device operation's metadata stats, where the
``jax.named_scope`` path arrives (stat ``tf_op``; PERF.md section 3).

A reader of the protobuf wire format for just these messages (tsl
``xplane.proto``), so that the benchmark imports no second framework:

    XSpace.planes = 1
    XPlane: name = 2, event_metadata = 4 (map), stat_metadata = 5 (map)
    map entry: key = 1, value = 2
    XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5,
           bytes = 6, ref = 7 (the id of a stat metadata whose name is the value)
"""

from __future__ import annotations

import struct


def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos, end):
    """``(field, wire_type, value)`` of one message; a length-delimited
    value is its ``(start, end)`` in ``buf``."""
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wire == 1:
            val, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            val, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield field, wire, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    key, value = None, None
    for field, _, val in _fields(buf, *span):
        if field == 1:
            key = val
        elif field == 2:
            value = val
    return key, value


def _stat(buf, span):
    sid, value = None, None
    for field, wire, val in _fields(buf, *span):
        if field == 1:
            sid = val
        elif field == 2:
            value = struct.unpack("<d", bytes(val))[0]
        elif field in (3, 4):
            value = val
        elif field in (5, 6):
            value = _text(buf, val)
        elif field == 7:
            value = ("ref", val)
    return sid, value


def plane_metadata(path: str, plane_prefix: str) -> dict:
    """``{plane name: {operation name: {stat name: value}}}`` for the
    planes whose name starts with ``plane_prefix``.  An operation is
    listed under its metadata's name and its display name."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for field, wire, span in _fields(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        name, events, stats = "", [], {}
        for f, w, val in _fields(buf, *span):
            if f == 2:
                name = _text(buf, val)
                if not name.startswith(plane_prefix):
                    break
            elif f == 4:
                events.append(_map_value(buf, val)[1])
            elif f == 5:
                sid, meta = _map_value(buf, val)
                stats[sid] = next((_text(buf, v) for g, _, v in
                                   _fields(buf, *meta) if g == 2), "")
        if not name.startswith(plane_prefix):
            continue
        table = {}
        for meta in events:
            names, found = [], {}
            for f, w, val in _fields(buf, *meta):
                if f in (2, 4):
                    names.append(_text(buf, val))
                elif f == 5:
                    sid, value = _stat(buf, val)
                    if isinstance(value, tuple):
                        value = stats.get(value[1], "")
                    found[stats.get(sid, str(sid))] = value
            for n in names:
                table[n] = found
        out[name] = table
    return out
