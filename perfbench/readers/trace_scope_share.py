"""Device time of the operations that the program traced under one of its
declared scopes (``obs.tracing.scope``: the name is a component of the
operation's ``op_name`` path), as a share of the time of the modules
matching ``of_module``, or of the device's busy time without it, in %.

The path is the ``tf_op`` stat of the operation's metadata. ``scope``: it
has a component in this list (absent: any operation); ``except``: and none
in this list; ``pass``: ``fwd`` or ``bwd`` keeps the operations outside or
inside ``transpose(``. With ``of_module`` only operations that start inside
such a module's execution count.

A file without ``scope`` reads the time under NO declared scope: besides
its ``except`` it leaves out every name of the program's vocabulary
(``obs.tracing.SCOPES``, handed over by the run as ``ctx["scopes"]``) but
those in ``wraps``, the scopes that wrap its whole program (``admit``,
``decode``). A scope declared later is so left out without an edit here.

Time is SELF time: an operation that holds others (a ``while`` and its
body) counts for what its children leave. A fusion carries the path XLA
gave it, its root's: an elementwise operation fused into a neighbour's
matmul counts for the neighbour. Nested scopes overlap (``attn/dropout`` is
in both), so a cell's shares need not sum to 100; nothing is clipped.
Where no operation of the slice carries any name of ``scope``/``except``
(a program without scopes, a trace without the paths) the reader returns
None. Source: device_trace."""

from __future__ import annotations

import bisect
import functools
import re

from perfbench import host_plane

_WRAPPED = re.compile(r"^(?:(?:transpose|jvp|vmap)\()+([^()]+)\)+$")


def op_path(ev) -> str:
    """The ``op_name`` path of a device operation, or '': on the v5e the
    ``tf_op`` stat of the event's metadata (``<path>:``; asynchronous
    copies and a few custom calls have none)."""
    return (ev["stats"].get("tf_op") or "").rstrip(":")


@functools.lru_cache(maxsize=None)
def components(path: str) -> tuple:
    """(names, backward): the path's components with the transformations
    JAX wraps round a scope (``transpose(jvp(attn))``) taken off, and
    whether any component was under ``transpose(``."""
    names, backward = [], False
    for part in path.split("/"):
        m = _WRAPPED.match(part)
        if m:
            backward |= "transpose(" in part
            part = m.group(1)
        names.append(part)
    return tuple(names), backward


def self_times(ops) -> list:
    """Each operation's duration less that of the operations nested in it,
    in the order of ``ops``."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i]["start"], -ops[i]["dur"]))
    own = [o["dur"] for o in ops]
    stack = []                               # (end, index) of open parents
    for i in order:
        s, e = ops[i]["start"], ops[i]["start"] + ops[i]["dur"]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, i))
    return own


def _wanted(names, backward, spec) -> bool:
    if spec.get("pass") and (spec["pass"] == "bwd") != backward:
        return False
    if spec.get("scope") and not any(n in spec["scope"] for n in names):
        return False
    return not any(n in spec.get("except", ()) for n in names)


def share(lines: dict, spec: dict, vocabulary=()):
    """The share over the device planes of ``lines`` (as
    ``host_plane.device_lines`` gives them), or None. ``vocabulary``: the
    scope names the program declares."""
    if not spec.get("scope"):
        spec = {**spec, "except": sorted(
            set(spec.get("except", ()))
            | (set(vocabulary) - set(spec.get("wraps", ()))))}
    declared = set(spec.get("scope", ())) | set(spec.get("except", ()))
    rx = re.compile(spec["of_module"]) if spec.get("of_module") else None
    seen, shares = False, []
    for plane in sorted(lines):
        ops, modules = lines[plane]["ops"], lines[plane]["modules"]
        own = self_times(ops)
        if rx is None:
            inside = [True] * len(ops)
            den = sum(own)
        else:
            runs = sorted((m["start"], m["start"] + m["dur"])
                          for m in modules if rx.search(m["name"]))
            starts = [r[0] for r in runs]
            den = sum(e - s for s, e in runs)

            def within(t):
                k = bisect.bisect_right(starts, t) - 1
                return k >= 0 and t < runs[k][1]
            inside = [within(o["start"]) for o in ops]
        if den <= 0:
            continue
        num = 0.0
        for o, t, ok in zip(ops, own, inside):
            if not ok:
                continue
            names, backward = components(op_path(o))
            seen = seen or not declared.isdisjoint(names)
            if _wanted(names, backward, spec):
                num += t
        shares.append(100.0 * num / den)
    if not shares or not seen:
        return None
    return sum(shares) / len(shares)


def read(spec, ctx):
    if ctx["trace"] is None:
        return None
    return share(host_plane.device_lines(), spec, ctx.get("scopes", ()))
