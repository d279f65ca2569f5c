"""The few fields of an ``.xplane.pb`` that ``program.py`` reads, decoded
from the protobuf wire format.

Why not ``jax.profiler.ProfileData``: the TPU profiler puts what it knows
of an HLO instruction (``tf_op``, which is the instruction's ``op_name``
and so JAX's name stack; ``hlo_category``, ``flops``, ``bytes_accessed``,
``source``) on the event's METADATA, once an instruction, and
``ProfileData`` hands out an event's own stats only (offset, duration).
Read by hand on the chip in PR 25, PERF.md section 3. The schema is
``tsl/profiler/protobuf/xplane.proto``; the field numbers below are its.
Times come out as ``ProfileData`` gives them, so that both readers of a
trace see the same numbers: an event starts at its line's ``timestamp_ns``
plus its ``offset_ps`` in whole ns, and lasts its ``duration_ps`` in whole
ns (checked event by event against ``ProfileData`` on the chip's traces).

    XSpace          1 planes
    XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    XLine           2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats
    XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                    6 bytes, 7 ref (a stat_metadata id whose name is the value)
    XEventMetadata  1 id, 2 name, 5 stats
    XStatMetadata   1 id, 2 name
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Callable, Dict, Iterator, List, Optional, Tuple


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of each field of one message:
    an int for a varint, bytes of 8 or 4 for the fixed types, a
    ``memoryview`` for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = bytes(buf[i:i + size])
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    key, value = 0, None
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", v)[0]
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
    return stat_names.get(key, str(key)), value


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    metadata_id: int
    stats: Dict[str, object]        # the event's own


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    metadata_stats: Dict[int, Dict[str, object]]    # by event metadata id


def _map_entry(buf) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for number, _, v in fields(buf):
        if number == 1:
            key = _signed(v)
        elif number == 2:
            value = v
    return key, value


def plane(buf, want_line: Callable[[str], bool] = lambda name: True
          ) -> Plane:
    """One plane decoded, with the lines whose name ``want_line`` accepts
    (the events of the others are skipped undecoded)."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, _, v in fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((_text(x) for n, _, x in fields(value)
                                    if n == 2), "")
    names, metadata_stats = {}, {}
    for key, value in event_meta.items():
        stats = []
        for number, _, v in fields(value):
            if number == 2:
                names[key] = _text(v)
            elif number == 5:
                stats.append(v)
        metadata_stats[key] = dict(_stat(s, stat_names) for s in stats)
    out = []
    for raw in lines:
        line_name, t0, events = "", 0, []
        for number, _, v in fields(raw):
            if number == 2:
                line_name = _text(v)
            elif number == 3:
                t0 = _signed(v)
            elif number == 4:
                events.append(v)
        if not want_line(line_name):
            continue
        decoded = []
        for raw_event in events:
            meta = offset = dur = 0
            stats = {}
            for number, _, v in fields(raw_event):
                if number == 1:
                    meta = _signed(v)
                elif number == 2:
                    offset = _signed(v)
                elif number == 3:
                    dur = _signed(v)
                elif number == 4:
                    key, value = _stat(v, stat_names)
                    stats[key] = value
            decoded.append(Event(names.get(meta, ""),
                                 float(t0 + offset // 1000),
                                 float(dur // 1000), meta, stats))
        out.append(Line(line_name, decoded))
    return Plane(name, out, metadata_stats)


def planes(path: str) -> Dict[str, memoryview]:
    """The planes of an ``.xplane.pb`` by name, not yet decoded: hand the
    ones that are wanted to ``plane``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return {next((_text(x) for n, _, x in fields(v) if n == 2), ""): v
            for number, _, v in fields(space) if number == 1}
