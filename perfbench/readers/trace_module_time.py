"""Device time of the XLA modules matching ``module``, in ms: per
execution; or per ``per`` (a counter of the traced window, e.g. prompt
tokens admitted in it); or per execution x ``per_run_counter`` (e.g. the
ticks in a segment). Source: device_trace."""


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    secs, runs = tr.module_time_s(spec["module"],
                                  trim_edges=bool(spec.get("trim_edges")))
    if runs <= 0:
        return None
    den = runs
    if spec.get("per_run_counter"):
        den = runs * float(ctx["counters"].get(spec["per_run_counter"], 0.0))
        if den <= 0:
            return None
    if spec.get("per"):
        den = float(ctx["counters"].get(spec["per"], 0.0))
        if den <= 0:
            return None
    return 1e3 * secs * float(spec.get("scale", 1.0)) / den
