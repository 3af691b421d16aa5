"""Exact percentile over one field of the window's request results, in
the unit given by ``scale`` (1000 for ms). A request without the field
counts as missing (+inf). Source: host_clock as the program stamps it on
``RequestResult``."""

import math

from perfbench.percentiles import percentile


def read(spec, ctx):
    reqs = ctx.get("requests")
    if not reqs:
        return None
    vals = [r.get(spec["field"]) for r in reqs]
    vals = [math.inf if v is None else v * spec.get("scale", 1.0)
            for v in vals]
    v = percentile(vals, spec["q"])
    return v if math.isfinite(v) else None
