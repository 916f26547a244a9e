"""What ``jax.profiler.ProfileData`` leaves out of an ``.xplane.pb``: the
stats of each event's METADATA.  XLA:TPU's profiler writes an op's
op-name path there (``tf_op``: ``jit(_superstep_program)/while/body/
sg.drain.solve/while/body/sg.lmm.update/scatter-add``, the
``jax.named_scope`` names in it), its ``bytes_accessed`` and the like;
``ProfileData`` shows an event's own stats only and names it by the
bare HLO text.

A decoder of the protobuf wire format, stdlib only, for just the
fields the reduction needs (tsl/profiler/protobuf/xplane.proto):

    XSpace          1 planes
    XPlane          2 name, 3 lines, 4 event_metadata (map: 1 key,
                    2 value), 5 stat_metadata (map)
    XLine           2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
    XEventMetadata  1 id, 2 name, 5 stats
    XStatMetadata   1 id, 2 name
    XStat           1 metadata_id, 2 double, 3 uint64, 4 int64,
                    5 str_value, 6 bytes, 7 ref_value (the id of a
                    stat_metadata whose NAME is the string)

Times come out as ``lib/trace.py`` takes them from ``ProfileData``
(``start_ns = timestamp_ns + offset_ps / 1000`` and the duration, each
cut to whole nanoseconds), so an event found here is the event read
there, at the same nanosecond.
"""

from __future__ import annotations

import struct
from typing import Any, Dict, Iterator, List, NamedTuple, Tuple

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed field, a memoryview for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, at = varint(buf, at)
        elif wire == BYTES:
            size, at = varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == FIXED64:
            value, at = bytes(buf[at:at + 8]), at + 8
        elif wire == FIXED32:
            value, at = bytes(buf[at:at + 4]), at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an "
                             f"xplane.pb, or a newer encoding")
        yield number, wire, value


def varint(buf: memoryview, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def signed(value: int) -> int:
    """An int64 field as protobuf writes it (two's complement)."""
    return value - (1 << 64) if value >= 1 << 63 else value


class Op(NamedTuple):
    """One event of a line: which metadata it points at, and when."""
    metadata_id: int
    start_ns: int
    end_ns: int


class Plane:
    """One XPlane: its event metadata, and its lines' events decoded
    when a line is first asked for (a host plane's lines hold most of
    a trace's events and the reduction reads none of them)."""

    def __init__(self, buf: memoryview):
        self.name = ""
        #: event metadata id -> HLO text (what ProfileData calls name)
        self.names: Dict[int, str] = {}
        #: event metadata id -> {stat name: value}
        self.stats: Dict[int, Dict[str, Any]] = {}
        #: line name -> [(timestamp_ns, event buffers)], undecoded
        self._raw: Dict[str, List[Tuple[int, List[memoryview]]]] = {}
        self._ops: Dict[str, List[Op]] = {}
        lines, metadata, stat_names = [], [], {}
        for number, _wire, value in fields(buf):
            if number == 2:
                self.name = str(value, "utf-8")
            elif number == 3:
                lines.append(value)
            elif number == 4:
                metadata.append(map_value(value))
            elif number == 5:
                entry = dict_of(map_value(value))
                stat_names[entry.get(1, 0)] = str(entry.get(2, b""),
                                                  "utf-8")
        for buf_m in metadata:
            self._metadata(buf_m, stat_names)
        for buf_l in lines:
            self._line(buf_l)

    def _metadata(self, buf: memoryview, stat_names: Dict[int, str]):
        ident, name, stats = 0, "", {}
        for number, _wire, value in fields(buf):
            if number == 1:
                ident = value
            elif number == 2:
                name = str(value, "utf-8")
            elif number == 5:
                key, got = stat(value, stat_names)
                stats[key] = got
        self.names[ident] = name
        self.stats[ident] = stats

    def _line(self, buf: memoryview):
        name, timestamp_ns, events = "", 0, []
        for number, _wire, value in fields(buf):
            if number == 2:
                name = str(value, "utf-8")
            elif number == 3:
                timestamp_ns = signed(value)
            elif number == 4:
                events.append(value)
        self._raw.setdefault(name, []).append((timestamp_ns, events))

    @property
    def line_names(self) -> List[str]:
        return list(self._raw)

    def ops(self, line: str) -> List[Op]:
        """The events of the lines named ``line``, in file order ([]
        when the plane has none)."""
        if line not in self._ops:
            out: List[Op] = []
            for timestamp_ns, events in self._raw.get(line, ()):
                for buf_e in events:
                    ident, offset_ps, duration_ps = event(buf_e)
                    start = int(timestamp_ns + signed(offset_ps) / 1000.0)
                    out.append(Op(ident, start, start + int(
                        signed(duration_ps) / 1000.0)))
            self._ops[line] = out
        return self._ops[line]

    def stat_of(self, metadata_id: int, name: str, default=None):
        return self.stats.get(metadata_id, {}).get(name, default)


def event(buf: memoryview) -> Tuple[int, int, int]:
    """(metadata_id, offset_ps, duration_ps) of one XEvent, as
    ``dict_of`` would give fields 1 to 3 (the last value of each, 0
    for one left out).  A window's trace holds millions of events and
    this is all the reduction reads of them, so it is decoded in one
    loop over the bytes, not through ``fields``: a third of the time
    (the pairwise cell's 220 MB trace took 31.5 s the slow way)."""
    raw = bytes(buf)
    got = [0, 0, 0, 0]
    at, end = 0, len(raw)
    while at < end:
        key = raw[at]
        at += 1
        if key >= 0x80:                 # a field number over 15
            return tuple(dict_of(buf).get(n, 0) for n in (1, 2, 3))
        wire = key & 7
        if wire == VARINT or wire == BYTES:
            value = raw[at]
            at += 1
            if value >= 0x80:
                value &= 0x7F
                shift = 7
                while True:
                    byte = raw[at]
                    at += 1
                    value |= (byte & 0x7F) << shift
                    if byte < 0x80:
                        break
                    shift += 7
            if wire == BYTES:
                at += value
            elif key >> 3 <= 3:
                got[key >> 3] = value
        elif wire == FIXED64:
            at += 8
        elif wire == FIXED32:
            at += 4
        else:
            raise ValueError(f"wire type {wire} in an XEvent: not an "
                             f"xplane.pb, or a newer encoding")
    return got[1], got[2], got[3]


def map_value(entry: memoryview) -> memoryview:
    """The value of one ``map<int64, Message>`` entry."""
    for number, _wire, value in fields(entry):
        if number == 2:
            return value
    return memoryview(b"")


def dict_of(buf: memoryview) -> Dict[int, Any]:
    """Field number -> last value, for a message with no repeats."""
    return {number: value for number, _wire, value in fields(buf)}


def stat(buf: memoryview, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """(stat name, value) of one XStat."""
    name, value = "", None
    for number, _wire, got in fields(buf):
        if number == 1:
            name = stat_names.get(got, str(got))
        elif number == 2:
            value = struct.unpack("<d", got)[0]
        elif number == 3:
            value = got
        elif number == 4:
            value = signed(got)
        elif number == 5:
            value = str(got, "utf-8")
        elif number == 6:
            value = bytes(got)
        elif number == 7:
            value = stat_names.get(got, "")
    return name, value


def read(path: str) -> Dict[str, Plane]:
    """plane name -> Plane, for every plane of the file that has a
    name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = (Plane(value) for number, _wire, value in fields(space)
              if number == 1)
    return {p.name: p for p in planes if p.name}
