"""A reader for the profiler's ``.xplane.pb`` that needs nothing but the
standard library.

``jax.profiler.ProfileData`` gives events and their own stats but not the
stats of an event's *metadata*, and that is where the profiler keeps the
jax name of a device operation (``tf_op``: ``jit(step)/jvp(gpt/attn)/
attn/pack2/pallas_call``).  The XSpace schema is small and stable
(tsl/profiler/protobuf/xplane.proto), so the few fields the reduction
needs are decoded here from the protobuf wire format directly.

Times are picoseconds on the profiler's one clock: host threads and
device lines of one trace can be compared.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import struct
from typing import Dict, Iterator, List, Optional, Tuple


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a
    length-delimited value comes back as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Event:
    name: str                   # the metadata's name (HLO text for an op)
    display: str                # its short name (``fusion.12``)
    start_ps: int
    dur_ps: int
    stats: Dict[str, object]    # metadata stats, overridden by event stats

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    stats: Dict[str, object]

    def line(self, name: str) -> Optional[Line]:
        return next((ln for ln in self.lines if ln.name == name), None)


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    key, val = 0, None
    for field, _wire, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            val = struct.unpack("<d", v)[0]
        elif field == 3:
            val = v
        elif field == 4:
            val = _signed(v)
        elif field == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif field == 6:
            val = bytes(v)
        elif field == 7:
            val = stat_names.get(v, str(v))
    return stat_names.get(key, str(key)), val


def _map_entry(buf) -> Tuple[int, object]:
    key, val = 0, b""
    for field, _wire, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            val = v
    return key, val


def _plane(buf, want_lines) -> Plane:
    name, raw_lines, raw_meta, raw_stats = "", [], [], []
    stat_names: Dict[int, str] = {}
    for field, _wire, v in _fields(buf):
        if field == 2:
            name = bytes(v).decode()
        elif field == 3:
            raw_lines.append(v)
        elif field == 4:
            raw_meta.append(v)
        elif field == 5:
            key, msg = _map_entry(v)
            for f2, _w2, v2 in _fields(msg):
                if f2 == 2:
                    stat_names[key] = bytes(v2).decode()
        elif field == 6:
            raw_stats.append(v)
    plane = Plane(name, [], dict(_stat(s, stat_names) for s in raw_stats))
    if want_lines is not None and not want_lines(name):
        return plane
    meta: Dict[int, Tuple[str, str, Dict[str, object]]] = {}
    for entry in raw_meta:
        key, msg = _map_entry(entry)
        mname, display, stats = "", "", {}
        for f2, _w2, v2 in _fields(msg):
            if f2 == 2:
                mname = bytes(v2).decode("utf-8", "replace")
            elif f2 == 4:
                display = bytes(v2).decode("utf-8", "replace")
            elif f2 == 5:
                k, val = _stat(v2, stat_names)
                stats[k] = val
        meta[key] = (mname, display, stats)
    for raw in raw_lines:
        lname, t0_ns, raw_events = "", 0, []
        for f2, _w2, v2 in _fields(raw):
            if f2 == 2:
                lname = bytes(v2).decode()
            elif f2 == 3:
                t0_ns = _signed(v2)
            elif f2 == 4:
                raw_events.append(v2)
        events = []
        for ev in raw_events:
            mid = offset = dur = 0
            own = {}
            for f3, _w3, v3 in _fields(ev):
                if f3 == 1:
                    mid = v3
                elif f3 == 2:
                    offset = _signed(v3)
                elif f3 == 3:
                    dur = _signed(v3)
                elif f3 == 4:
                    k, val = _stat(v3, stat_names)
                    own[k] = val
            mname, display, mstats = meta.get(mid, ("", "", {}))
            events.append(Event(mname, display or mname,
                                t0_ns * 1000 + offset, dur,
                                {**mstats, **own} if own else mstats))
        plane.lines.append(Line(lname, events))
    return plane


def read_xplane(path: str, want_lines=None) -> List[Plane]:
    """Every plane of one ``.xplane.pb``.  ``want_lines(plane_name)``
    false skips that plane's lines (its name and stats still come)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v, want_lines) for field, _wire, v in _fields(buf)
            if field == 1]


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under a ``jax.profiler.start_trace``
    directory (the newest, if a directory was reused)."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
