"""A small reader of the protobuf wire format of an ``.xplane.pb`` trace,
for what ``jax.profiler.ProfileData`` does not expose: each event's own
``metadata_id``, and the stats attached to that metadata, where the profiler
keeps an op's source path of ``jax.named_scope``s.

An op is resolved through its event's ``metadata_id``, never by its name:
every compiled program numbers its own HLO instructions (``fusion.3``,
``copy.7``), so one name can stand for different ops, with different scope
paths, in two programs of one trace.

Only the fields needed are decoded (tsl/profiler/protobuf/xplane.proto):

* XSpace:         planes = 1
* XPlane:         name = 2, lines = 3,
                  event_metadata = 4 (map<int64, XEventMetadata>),
                  stat_metadata = 5 (map<int64, XStatMetadata>)
* XLine:          name = 2, events = 4
* XEvent:         metadata_id = 1
* XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5
* XStatMetadata:  id = 1, name = 2
* XStat:          metadata_id = 1, str_value = 5, ref_value = 7
"""
from __future__ import annotations

import dataclasses


def _varint(buf, i: int) -> tuple:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def fields(buf):
    """Yield (field number, wire type, value) of one message; a
    length-delimited value is the bytes, a fixed one its raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, v


def _map_entry(entry) -> tuple:
    key, value = 0, b""
    for num, _, v in fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _event_metadata_id(event) -> int:
    if len(event) and event[0] == 0x08:       # field 1, varint: the usual
        return _varint(event, 1)[0]
    for num, wt, v in fields(event):
        if num == 1 and wt == 0:
            return v
    return 0                                  # proto3 default, omitted


@dataclasses.dataclass(frozen=True)
class OpMeta:
    name: str
    display_name: str
    stats: dict        # stat name -> string value


def _op_meta(raw, stat_names: dict) -> OpMeta:
    name = display = ""
    stats = {}
    for num, _, v in fields(raw):
        if num == 2:
            name = bytes(v).decode()
        elif num == 4:
            display = bytes(v).decode()
        elif num == 5:
            key = val = None
            for sn, _, sv in fields(v):
                if sn == 1:
                    key = stat_names.get(sv, str(sv))
                elif sn == 5:
                    val = bytes(sv).decode()
                elif sn == 7:
                    val = stat_names.get(sv)
            if key is not None and isinstance(val, str):
                stats[key] = val
    return OpMeta(name, display, stats)


def line_events(path: str, plane_prefix: str, line_name: str) -> dict:
    """For every plane whose name starts with ``plane_prefix``: the
    metadata of each event on its lines named ``line_name``, in the order
    the file stores them, each resolved through the event's own
    ``metadata_id``.  ``{plane name: [OpMeta, ...]}``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for num, _, plane in fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, stat_names = "", [], {}, {}
        for pn, _, v in fields(plane):
            if pn == 2:
                name = bytes(v).decode()
            elif pn == 3:
                lines.append(v)
            elif pn == 4:
                key, raw = _map_entry(v)
                ev_meta[key] = raw
            elif pn == 5:
                _, raw = _map_entry(v)
                sid, sname = 0, ""
                for sn, _, sv in fields(raw):
                    if sn == 1:
                        sid = sv
                    elif sn == 2:
                        sname = bytes(sv).decode()
                stat_names[sid] = sname
        if not name.startswith(plane_prefix):
            continue
        resolved: dict = {}
        events = out.setdefault(name, [])
        for line in lines:
            lname, evs = "", []
            for ln, _, v in fields(line):
                if ln == 2:
                    lname = bytes(v).decode()
                elif ln == 4:
                    evs.append(v)
            if lname != line_name:
                continue
            for ev in evs:
                mid = _event_metadata_id(ev)
                if mid not in resolved:
                    if mid not in ev_meta:
                        raise ValueError(f"{path}: plane {name!r} has no "
                                         f"event metadata {mid}")
                    resolved[mid] = _op_meta(ev_meta[mid], stat_names)
                events.append(resolved[mid])
    return out
