"""The event metadata of a profiler trace file (``*.xplane.pb``), read from
its protobuf wire format.

``jax.profiler.ProfileData`` gives each event's name, times and own stats,
but not the stats of its event metadata, which is where the TPU profiler
writes what XLA knows of an operation: the ``program_id`` of the program
that holds it and the ``tf_op`` that is its HLO ``op_name``.  This module
decodes the messages of ``tsl/profiler/protobuf/xplane.proto`` that hold
them (XSpace > XPlane > XEventMetadata > XStat) and skips the lines of
events, which ``ProfileData`` reads.
"""
from __future__ import annotations

import struct

# field numbers of xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
ENTRY_KEY, ENTRY_VALUE = 1, 2
EVENT_META_NAME, EVENT_META_STATS = 2, 5
STAT_META_NAME = 2
STAT_ID, STAT_DOUBLE, STAT_UINT, STAT_INT, STAT_STR, STAT_BYTES, STAT_REF = range(1, 8)


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, start: int = 0, end: int | None = None):
    """``(field number, value)`` of each field of one message in
    ``buf[start:end]``: an int for a varint, ``(start, end)`` for a
    length-delimited field, bytes for a fixed-width one."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, spans):
    """The (key, value span) of each entry of a protobuf map field."""
    for s, e in spans:
        key, value = 0, (s, s)
        for num, v in fields(buf, s, e):
            if num == ENTRY_KEY:
                key = v
            elif num == ENTRY_VALUE:
                value = v
        yield key, value


def _stat(buf, span, names: dict):
    """(name, value) of one XStat; a ``ref_value`` reads the string it
    refers to (the name of a stat metadata entry)."""
    sid, value = 0, None
    for num, v in fields(buf, *span):
        if num == STAT_ID:
            sid = v
        elif num == STAT_DOUBLE:
            value = struct.unpack("<d", v)[0]
        elif num in (STAT_UINT, STAT_INT):
            value = v
        elif num in (STAT_STR, STAT_BYTES):
            value = _text(buf, v)
        elif num == STAT_REF:
            value = names.get(v)
    return names.get(sid, str(sid)), value


def event_metadata(path, plane_prefix: str) -> dict:
    """``{plane name: {metadata id: (name, {stat name: value})}}`` for the
    planes whose name starts with ``plane_prefix``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in fields(buf):
        if num != SPACE_PLANES:
            continue
        name, metas, stat_metas = "", [], []
        for pnum, v in fields(buf, *plane):
            if pnum == PLANE_NAME:
                name = _text(buf, v)
            elif pnum == PLANE_EVENT_METADATA:
                metas.append(v)
            elif pnum == PLANE_STAT_METADATA:
                stat_metas.append(v)
        if not name.startswith(plane_prefix):
            continue
        stat_names = {}
        for sid, span in _map_entries(buf, stat_metas):
            stat_names[sid] = next((_text(buf, v) for n, v in fields(buf, *span)
                                    if n == STAT_META_NAME), "")
        table = {}
        for mid, span in _map_entries(buf, metas):
            ev_name, stats = "", {}
            for n, v in fields(buf, *span):
                if n == EVENT_META_NAME:
                    ev_name = _text(buf, v)
                elif n == EVENT_META_STATS:
                    k, value = _stat(buf, v, stat_names)
                    stats[k] = value
            table[mid] = (ev_name, stats)
        out[name] = table
    return out
