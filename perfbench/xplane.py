"""The profiler's ``.xplane.pb`` read whole, with nothing but Python.

``jax.profiler.ProfileData`` (what ``trace_reduce.load_xplane`` uses) shows
an event's own stats and hides the stats of its METADATA, and on a TPU that
is where the profiler keeps what an operation is: its ``op_name`` path
(``tf_op``), category, program. So this module decodes the file's protobuf
wire format itself (``tsl/profiler/protobuf/xplane.proto``: XSpace > XPlane
> XLine > XEvent, names and stat names by id in the plane's metadata maps)
and hands back plain dicts. An event's ``stats`` are its metadata's stats
overlaid with its own. Times are nanoseconds on the trace's one clock, as
``ProfileData`` gives them (``line.timestamp_ns + offset_ps / 1000``)."""

from __future__ import annotations

import glob
import os
import struct


def newest(root: str) -> str | None:
    """The newest ``.xplane.pb`` under ``root`` (any depth), or None."""
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            val = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """(name, value) of one XStat."""
    name, value = None, None
    for num, _wt, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(v)
        elif num == 6:
            value = bytes(v)
        elif num == 7:           # a reference to a stat metadata's name
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for num, _wt, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf, keep_line) -> dict:
    name, lines, event_meta, stat_meta, stats = "", [], [], [], []
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            event_meta.append(v)
        elif num == 5:
            stat_meta.append(v)
        elif num == 6:
            stats.append(v)
    stat_names = {}
    for entry in stat_meta:
        key, body = _map_entry(entry)
        for num, _wt, v in _fields(body):
            if num == 2:
                stat_names[key] = _text(v)
    events_by_id = {}
    for entry in event_meta:
        key, body = _map_entry(entry)
        ename, mstats = "", {}
        for num, _wt, v in _fields(body):
            if num == 2:
                ename = _text(v)
            elif num == 5:
                k, val = _stat(v, stat_names)
                mstats[k] = val
        events_by_id[key] = (ename, mstats)
    out_lines = []
    for lbuf in lines:
        lname, t0, raw = "", 0, []
        for num, _wt, v in _fields(lbuf):
            if num == 2:
                lname = _text(v)
            elif num == 3:
                t0 = _signed(v)
            elif num == 4:
                raw.append(v)
        if not keep_line(name, lname):
            out_lines.append({"name": lname, "events": [],
                              "skipped": len(raw)})
            continue
        events = []
        for ebuf in raw:
            mid = off = dur = 0
            own = None
            for num, _wt, v in _fields(ebuf):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = _signed(v)
                elif num == 3:
                    dur = _signed(v)
                elif num == 4:
                    k, val = _stat(v, stat_names)
                    own = own if own is not None else {}
                    own[k] = val
            ename, mstats = events_by_id.get(mid, (str(mid), {}))
            events.append({"name": ename, "start": t0 + off / 1e3,
                           "dur": dur / 1e3,
                           "stats": {**mstats, **own} if own else mstats})
        out_lines.append({"name": lname, "events": events})
    return {"name": name, "lines": out_lines,
            "stats": dict(_stat(s, stat_names) for s in stats)}


def load(path: str, keep_line=lambda plane, line: True) -> list:
    """Every plane of the file: ``{"name", "stats", "lines": [{"name",
    "events": [{"name", "start", "dur", "stats"}]}]}``. ``keep_line(plane
    name, line name)`` false leaves a line's events undecoded (the line
    then says how many it ``skipped``)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v, keep_line) for num, _wt, v in _fields(buf)
            if num == 1]
