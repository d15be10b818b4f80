"""Reads a profiler's ``.xplane.pb`` (an ``XSpace`` protocol buffer) from its
bytes: planes, lines, events, and for each event the stats of its metadata.

``jax.profiler.ProfileData`` gives an event's own stats (offsets, durations)
and not those of its metadata, and the name the program gave an operation
(``tf_op``: the HLO metadata's ``op_name``, with every ``jax.named_scope`` and
``pallas_call(name=)`` on the way to it) is one of the latter.  The field
numbers are those of ``tsl/profiler/protobuf/xplane.proto``; a field that is
not listed here is stepped over.
"""

from __future__ import annotations

import struct


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    the eight or four bytes of a fixed field, a memoryview of a
    length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for num, _, val in _fields(buf):
        if num == 1:
            key = _signed(val)
        elif num == 2:
            value = val
    return key, value


def _stat(buf, stat_names):
    """(name, value) of one ``XStat``; a reference is followed to its text."""
    name, value = None, None
    for num, _, val in _fields(buf):
        if num == 1:
            name = stat_names.get(_signed(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num in (5, 6):
            value = _text(val)
        elif num == 7:
            value = stat_names.get(val, "")
    return name, value


def _event_metadata(buf, stat_names):
    name, stats = "", {}
    for num, _, val in _fields(buf):
        if num == 2:
            name = _text(val)
        elif num == 5:
            k, v = _stat(val, stat_names)
            stats[k] = v
    return name, stats


def _line(buf, metadata):
    """(line name, [(event name, metadata stats, start_s, end_s)]), in whole
    nanoseconds as ``jax.profiler.ProfileData`` gives them."""
    name, t0_ns, raw = "", 0, []
    for num, _, val in _fields(buf):
        if num == 2:
            name = _text(val)
        elif num == 3:
            t0_ns = _signed(val)
        elif num == 4:
            mid = off_ps = dur_ps = 0
            for n2, _, v2 in _fields(val):
                if n2 == 1:
                    mid = _signed(v2)
                elif n2 == 2:
                    off_ps = _signed(v2)
                elif n2 == 3:
                    dur_ps = _signed(v2)
            raw.append((mid, off_ps, dur_ps))
    events = []
    for mid, off_ps, dur_ps in raw:
        ev_name, stats = metadata.get(mid, ("", {}))
        start_ns = float(t0_ns + off_ps // 1000)
        events.append((ev_name, stats, start_ns * 1e-9,
                       (start_ns + float(dur_ps // 1000)) * 1e-9))
    return name, events


def read(path, want_plane=lambda name: True):
    """[(plane name, [(line name, events)])] of the planes ``want_plane``
    takes; the others are stepped over unread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, st_meta = "", [], [], []
        for n2, _, val in _fields(plane):
            if n2 == 2:
                name = _text(val)
            elif n2 == 3:
                lines.append(val)
            elif n2 == 4:
                ev_meta.append(val)
            elif n2 == 5:
                st_meta.append(val)
        if not want_plane(name):
            continue
        stat_names = {}
        for entry in st_meta:
            key, value = _map_entry(entry)
            for n3, _, v3 in _fields(value):
                if n3 == 2:
                    stat_names[key] = _text(v3)
        metadata = {}
        for entry in ev_meta:
            key, value = _map_entry(entry)
            metadata[key] = _event_metadata(value, stat_names)
        planes.append((name, [_line(buf, metadata) for buf in lines]))
    return planes
