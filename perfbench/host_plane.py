"""The traced slice of THIS run, read whole once: the host plane's TraceMe
events (the program's spans among them: ``obs/tracing.py`` drives a
``TraceAnnotation`` per span) and the device operations with the stats of
their metadata, all on the trace's one clock. ``trace_reduce.load_xplane``
drops host planes and metadata stats, and the runners hand the readers no
spans of a serve run, so the readers that need either come here.

The file is the newest ``.xplane.pb`` under ``.perfbench_out/*/trace``:
the one this process has just written (``tracewin.TraceWindow``)."""

from __future__ import annotations

import functools
import pathlib

from perfbench import xplane
from perfbench.trace_reduce import MODULES_LINE, OPS_LINE

OUT = pathlib.Path(__file__).resolve().parent.parent / ".perfbench_out"


def _keep(plane: str, line: str) -> bool:
    if plane.startswith("/device:"):
        return line in (OPS_LINE, MODULES_LINE)
    return plane.startswith("/host:")


@functools.lru_cache(maxsize=2)
def _planes(path: str, _mtime: float) -> list:
    return xplane.load(path, _keep)


def planes(root=None) -> list:
    """The decoded planes of the newest trace under ``root`` (default: any
    cell's ``trace`` directory of this checkout), cached per process; an
    empty list where there is none."""
    if root is None:
        found = [xplane.newest(str(d)) for d in OUT.glob("*/trace")]
        found = [f for f in found if f]
        path = max(found, key=lambda f: pathlib.Path(f).stat().st_mtime,
                   default=None)
    else:
        path = xplane.newest(str(root))
    if path is None:
        return []
    return _planes(path, pathlib.Path(path).stat().st_mtime)


def host_events(root=None) -> list:
    """``{"thread", "name", "args", "start", "dur"}`` of every host-plane
    event, times in ns. ``thread`` is the line's place in the file (thread
    names repeat: every Python thread of a process carries its name)."""
    out = []
    for p in planes(root):
        if not p["name"].startswith("/host:"):
            continue
        for i, line in enumerate(p["lines"]):
            thread = f"{p['name']}#{i}:{line['name']}"
            out.extend({"thread": thread, "name": e["name"],
                        "args": e["stats"], "start": e["start"],
                        "dur": e["dur"]} for e in line["events"])
    return out


def device_lines(root=None) -> dict:
    """``{plane: {"ops": [...], "modules": [...]}}`` for every device plane
    that ran operations in the slice; events are ``{"name", "start", "dur",
    "stats"}`` with the metadata's stats included."""
    out = {}
    for p in planes(root):
        if not p["name"].startswith("/device:"):
            continue
        by = {line["name"]: line["events"] for line in p["lines"]}
        if by.get(OPS_LINE):
            out[p["name"]] = {"ops": by[OPS_LINE],
                              "modules": by.get(MODULES_LINE, [])}
    return out
