"""Largest over mean of the counters whose names start with ``prefix``
(the loads of the experts a chip holds: 1.0 = an even load). Counters are
the window's differences of what the runner collected from the program;
none found, or all zero, gives nothing. Source: program_counter."""


def read(spec, ctx):
    vals = [float(v) for k, v in ctx["counters"].items()
            if k.startswith(spec["prefix"])]
    if not vals or sum(vals) <= 0:
        return None
    return max(vals) * len(vals) / sum(vals)
