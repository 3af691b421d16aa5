"""Idle time of the first device of the slice that falls inside the host
spans named ``span`` on the thread that dispatches, over the traced slice,
in %. The dispatching thread is the host thread that holds most of the
spans named in ``owners`` (the program's spans of this cell,
``obs/tracing.py``). A gap is the time between two device operations with
none running; the slice's own edges are no gaps.

It also prints the ten longest gaps with what the dispatching thread was
doing in each: the innermost open span of ``owners`` with most of the gap,
and the gap's time by span (``unowned``: no such span open).

A program whose spans do not reach the profile gives no dispatching thread
and the reader returns None. Source: program_span (on the device's
clock)."""

from __future__ import annotations

from perfbench import host_plane


def gaps(ops) -> list:
    """(start, end) of every interval between the first and the last
    operation in which none runs."""
    out, end = [], None
    for o in sorted(ops, key=lambda o: o["start"]):
        if end is not None and o["start"] > end:
            out.append((end, o["start"]))
        end = max(end or 0.0, o["start"] + o["dur"])
    return out


def dispatch_thread(events, owners):
    """The thread with most events named in ``owners``, or None."""
    count = {}
    for e in events:
        if e["name"] in owners:
            count[e["thread"]] = count.get(e["thread"], 0) + 1
    return max(count, key=count.get) if count else None


def owned(gap, spans) -> dict:
    """The gap's time by innermost open span (``unowned`` where none is):
    ``spans`` are the owner events of one thread, properly nested."""
    a, b = gap
    live = [s for s in spans
            if s["start"] < b and s["start"] + s["dur"] > a]
    cuts = sorted({a, b, *(t for s in live
                           for t in (s["start"], s["start"] + s["dur"])
                           if a < t < b)})
    out = {}
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        open_ = [s for s in live
                 if s["start"] <= mid < s["start"] + s["dur"]]
        name = (max(open_, key=lambda s: s["start"])["name"]
                if open_ else "unowned")
        out[name] = out.get(name, 0.0) + hi - lo
    return out


def share(ops, events, spec, window_ns, report=print):
    owners = set(spec["owners"])
    thread = dispatch_thread(events, owners)
    if thread is None or window_ns <= 0:
        return None
    spans = [e for e in events
             if e["thread"] == thread and e["name"] in owners]
    idle = gaps(ops)
    inside = sum(owned(g, spans).get(spec["span"], 0.0) for g in idle)
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        by = sorted(owned(g, spans).items(), key=lambda kv: -kv[1])
        report(f"INFO idle gap {(g[1] - g[0]) / 1e6:.3f} ms in {by[0][0]} ("
               + ", ".join(f"{k} {v / 1e6:.3f}" for k, v in by) + ")")
    return 100.0 * inside / window_ns


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    lines = host_plane.device_lines()
    if not lines:
        return None
    ops = lines[sorted(lines)[0]]["ops"]
    return share(ops, host_plane.host_events(), spec, tr.window_s * 1e9)
