"""From a profiler trace to numbers: device busy/idle, time per XLA module,
time per named kernel or operation, collective time. Works on a plain list
of events so that it can be checked on a small recorded trace
(``tests/data/*.json``); ``load_xplane`` turns the profiler's
``.xplane.pb`` into that list with nothing but JAX.

An event is ``{"plane", "line", "name", "start", "dur", "stats"}`` with
times in nanoseconds. Device planes are those named ``/device:TPU:<n>``;
on them the line ``XLA Ops`` holds one event per executed HLO operation
and ``XLA Modules`` one per executed program. (On the CPU backend, used
only to rehearse, operations are the host-plane events that carry an
``hlo_op`` stat; nothing read from such a trace is ever reported.)"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


def load_xplane(trace_dir: str) -> list:
    import jax
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                stats = None
                if device or line.name.startswith("tf_XLA"):
                    stats = {k: v for k, v in ev.stats
                             if isinstance(v, (str, int, float))}
                    if not device and "hlo_op" not in stats:
                        continue
                elif not device:
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name, "start": float(ev.start_ns),
                               "dur": float(ev.duration_ns),
                               "stats": stats or {}})
    return events


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _text(ev) -> str:
    """What a pattern is matched against: the operation's OWN name. On the
    TPU an event's name is the whole HLO instruction (``%name = type
    op(operands)``); the operands name other operations, so only the part
    before `` = `` identifies this one."""
    return ev["name"].split(" = ", 1)[0]


class TraceSummary:
    """Per-device operation and module events of one traced window."""

    def __init__(self, events: list, window_s: float | None = None):
        planes = sorted({e["plane"] for e in events
                         if e["plane"].startswith("/device:TPU")})
        self.rehearsal = not planes
        if self.rehearsal:           # CPU backend: one pseudo-device
            planes = ["cpu"]
            self.ops = {"cpu": [e for e in events if "hlo_op" in e["stats"]]}
            self.modules = {"cpu": []}
        else:
            self.ops = {p: [e for e in events if e["plane"] == p
                            and e["line"] == OPS_LINE] for p in planes}
            self.modules = {p: [e for e in events if e["plane"] == p
                                and e["line"] == MODULES_LINE]
                            for p in planes}
            # a chip that ran nothing in the window is not one the cell used
            planes = [p for p in planes if self.ops[p]] or planes
        self.planes = planes
        # the window is the host's start-to-stop of the profiler, or the
        # span the device events cover where that is longer (work in flight
        # at the stop still lands in the trace)
        every = [e for p in planes for e in self.ops[p]]
        span = ((max(e["start"] + e["dur"] for e in every)
                 - min(e["start"] for e in every)) / 1e9 if every else 0.0)
        self.window_s = max(window_s or 0.0, span)

    def _avg(self, per_plane) -> float:
        return sum(per_plane) / max(len(per_plane), 1)

    def busy_s(self) -> float:
        return self._avg([_union((e["start"], e["start"] + e["dur"])
                                 for e in self.ops[p]) / 1e9
                          for p in self.planes])

    def op_time_s(self, pattern: str) -> float:
        """Summed duration of operations whose name or string stats match
        ``pattern``, averaged over the devices used."""
        rx = re.compile(pattern)
        return self._avg([sum(e["dur"] for e in self.ops[p]
                              if rx.search(_text(e))) / 1e9
                          for p in self.planes])

    def op_count(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return self._avg([sum(1 for e in self.ops[p] if rx.search(_text(e)))
                          for p in self.planes])

    def op_union_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return self._avg([_union((e["start"], e["start"] + e["dur"])
                                 for e in self.ops[p] if rx.search(_text(e)))
                          / 1e9 for p in self.planes])

    def module_time_s(self, pattern: str, trim_edges: bool = False):
        """(seconds, executions) of the XLA modules matching ``pattern``,
        averaged over the devices used. ``trim_edges`` leaves out each
        device's first and last matching execution, which the edges of the
        traced window may have cut short."""
        rx = re.compile(pattern)
        secs, runs = [], []
        for p in self.planes:
            evs = sorted((e for e in self.modules[p] if rx.search(e["name"])),
                         key=lambda e: e["start"])
            if trim_edges and len(evs) >= 4:
                evs = evs[1:-1]
            secs.append(sum(e["dur"] for e in evs) / 1e9)
            runs.append(len(evs))
        return self._avg(secs), self._avg(runs)

    def module_names(self) -> dict:
        out = {}
        for p in self.planes[:1]:
            for e in self.modules[p]:
                key = re.sub(r"\(\d+\)$", "", e["name"])
                t = out.setdefault(key, [0.0, 0])
                t[0] += e["dur"] / 1e9
                t[1] += 1
        return out

    def top_ops(self, n: int = 10) -> list:
        """[name, seconds] of the operations that took most device time on
        the first device, grouped by name with trailing numbers removed."""
        acc = {}
        for e in self.ops[self.planes[0]]:
            key = _op_key(e)
            acc[key] = acc.get(key, 0.0) + e["dur"] / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[label, seconds] of the longest idle gaps on the first device,
        labelled by the operation that ended the gap."""
        evs = sorted(self.ops[self.planes[0]], key=lambda e: e["start"])
        gaps, end = [], None
        for e in evs:
            if end is not None and e["start"] > end:
                gaps.append([f"before {_op_key(e)}", (e["start"] - end) / 1e9])
            end = max(end or 0.0, e["start"] + e["dur"])
        return sorted(gaps, key=lambda g: -g[1])[:n]


_KERNEL = re.compile(r"dcp_[a-z0-9_]+")


def _op_key(ev) -> str:
    m = _KERNEL.search(_text(ev))
    if m:
        return m.group(0)
    name = _text(ev).lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name
