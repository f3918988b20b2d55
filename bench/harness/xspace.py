"""A reader of the profiler's ``.xplane.pb`` (an ``XSpace`` protobuf) that
keeps what ``jax.profiler.ProfileData`` leaves out: the stats of each
event's metadata, where a TPU trace keeps an operation's JAX name path.

Only the fields used here are decoded (tsl/profiler/protobuf/xplane.proto):
XSpace.planes(1); XPlane name(2) lines(3) event_metadata(4)
stat_metadata(5); XLine name(2) timestamp_ns(3) events(4); XEvent
metadata_id(1) offset_ps(2) duration_ps(3) stats(4); XEventMetadata id(1)
name(2) display_name(4) stats(5); XStatMetadata id(1) name(2); XStat
metadata_id(1) double(2) uint64(3) int64(4) str(5) bytes(6) ref(7).

The generated ``xplane_pb2`` is not used: the one installed copy sits
inside the ``tensorflow`` package, which the repo's requirements do not
pin and whose import would load TensorFlow into the run beside JAX; this
reader needs only the standard library.  It runs after the window closes.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Tuple


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def fields(b: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message's bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclass
class Event:
    metadata_id: int
    offset_ps: int
    duration_ps: int


@dataclass
class Line:
    name: str
    timestamp_ns: int
    events: List[Event] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)
    # id -> (name, display name, {stat name: value})
    event_metadata: Dict[int, Tuple[str, str, Dict[str, object]]] = \
        field(default_factory=dict)


def _stat(b: bytes, names: Dict[int, str]):
    mid, val = 0, None
    for num, wt, v in fields(b):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 7):
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
    return names.get(mid, str(mid)), val


def _event(b: bytes) -> Event:
    mid = off = dur = 0
    for num, wt, v in fields(b):
        if num == 1:
            mid = v
        elif num == 2:
            off = v
        elif num == 3:
            dur = v
    return Event(mid, off, dur)


def _line(b: bytes) -> Line:
    line = Line("", 0)
    for num, wt, v in fields(b):
        if num == 2:
            line.name = bytes(v).decode()
        elif num == 3:
            line.timestamp_ns = _signed(v)
        elif num == 4:
            line.events.append(_event(v))
    return line


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, wt, v in fields(b):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(b: bytes) -> Plane:
    plane = Plane("")
    stat_names: Dict[int, str] = {}
    raw_meta: List[bytes] = []
    for num, wt, v in fields(b):
        if num == 2:
            plane.name = bytes(v).decode()
        elif num == 3:
            plane.lines.append(_line(v))
        elif num == 4:
            raw_meta.append(_map_entry(v)[1])
        elif num == 5:
            sid, sm = _map_entry(v)
            for n2, _, v2 in fields(sm):
                if n2 == 2:
                    stat_names[sid] = bytes(v2).decode()
    for m in raw_meta:
        mid, name, disp, st = 0, "", "", {}
        for num, wt, v in fields(m):
            if num == 1:
                mid = v
            elif num == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif num == 4:
                disp = bytes(v).decode("utf-8", "replace")
            elif num == 5:
                k, val = _stat(v, stat_names)
                st[k] = val
        plane.event_metadata[mid] = (name, disp, st)
    return plane


def load(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        data = f.read()
    return [_plane(v) for num, _, v in fields(data) if num == 1]
