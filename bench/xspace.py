"""A minimal reader of the profiler's XSpace protobuf (``*.xplane.pb``)
for what `jax.profiler.ProfileData` does not show: each plane's event
metadata and its stats, where the TPU profiler keeps the HLO modules
(``hlo_proto``) and the per-op metadata.

Only the wire format is needed (tsl/profiler/protobuf/xplane.proto):

    XSpace   { repeated XPlane planes = 1; }
    XPlane   { int64 id = 1; string name = 2; repeated XLine lines = 3;
               map<int64, XEventMetadata> event_metadata = 4;
               map<int64, XStatMetadata> stat_metadata = 5;
               repeated XStat stats = 6; }
    XEventMetadata { int64 id = 1; string name = 2; bytes metadata = 3;
                     string display_name = 4; repeated XStat stats = 5; }
    XStatMetadata  { int64 id = 1; string name = 2; }
    XStat    { int64 metadata_id = 1; double double_value = 2;
               uint64 uint64_value = 3; int64 int64_value = 4;
               string str_value = 5; bytes bytes_value = 6;
               uint64 ref_value = 7; }

Lines (the events themselves) are skipped, so reading is quick however
long the trace.
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


def _fields(buf, start=0, end=None):
    """Yield ``(field number, wire type, value)``; length-delimited
    values come as memoryview slices."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val = buf[i:i + 8]
            i += 8
        elif wt == 2:
            n, i = _varint(buf, i)
            val = buf[i:i + n]
            i += n
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, val


def _stat(buf):
    mid, value = 0, None
    for num, wt, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 2:
            value = struct.unpack("<d", bytes(val))[0]
        elif num in (3, 4, 7):
            value = val
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(val)
    return mid, value


def planes(path):
    """``[{"name", "stats", "event_metadata": {id: {"name",
    "display_name", "stats": {stat name: value}}}}]`` for every plane."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = []
    for num, wt, pbuf in _fields(buf):
        if num != 1:
            continue
        name = ""
        raw_events, raw_stats, stat_names = [], [], {}
        for pn, pw, pv in _fields(pbuf):
            if pn == 2:
                name = bytes(pv).decode("utf-8", "replace")
            elif pn == 4:
                raw_events.append(pv)
            elif pn == 5:
                for en, ew, ev in _fields(pv):
                    if en == 2:
                        sid, sname = 0, ""
                        for sn, sw, sv in _fields(ev):
                            if sn == 1:
                                sid = sv
                            elif sn == 2:
                                sname = bytes(sv).decode("utf-8", "replace")
                        stat_names[sid] = sname
            elif pn == 6:
                raw_stats.append(pv)
        events = {}
        for entry in raw_events:
            for en, ew, ev in _fields(entry):
                if en != 2:
                    continue
                md = {"name": "", "display_name": "", "stats": {}}
                eid = 0
                for mn, mw, mv in _fields(ev):
                    if mn == 1:
                        eid = mv
                    elif mn == 2:
                        md["name"] = bytes(mv).decode("utf-8", "replace")
                    elif mn == 4:
                        md["display_name"] = bytes(mv).decode("utf-8",
                                                              "replace")
                    elif mn == 5:
                        sid, val = _stat(mv)
                        md["stats"][stat_names.get(sid, str(sid))] = val
                events[eid] = md
        stats = {}
        for s in raw_stats:
            sid, val = _stat(s)
            stats[stat_names.get(sid, str(sid))] = val
        out.append({"name": name, "stats": stats, "event_metadata": events})
    return out
