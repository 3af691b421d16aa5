"""Memory-roofline share of the work traced under a scope: the bytes that
work has to move (``bytes_fn(config, rows)`` of the configuration's family,
``rows`` the traced runs' share of the counter ``rows_counter``) / HBM
bandwidth over the device time under ``scope`` inside the modules matching
``of_module``, in %. Read by scope and not by a kernel's name, so it reads
the same work whatever implements it; whatever else runs under the scope
only adds time, so the share cannot pass 100 while the bytes are counted
right. Never clipped.

``rows``: the counter is of the whole window and the trace of a slice of
it, so the counter's mean per tick (``rows_counter / ticks``) is taken over
the ticks traced (the module's runs x ``segment``), as
``decode_roofline.py`` takes its ticks. A program without the counter or
the scope, or a family without the byte function, gives nothing. Source:
device_trace and program_counter."""

from perfbench import families, host_plane, peaks
from perfbench.readers import trace_scope_share


def read(spec, ctx):
    tr, counters = ctx["trace"], ctx["counters"]
    if tr is None:
        return None
    rows, ticks = counters.get(spec["rows_counter"]), counters.get("ticks")
    secs, runs = tr.module_time_s(spec["of_module"])
    traced_ticks = runs * counters.get("segment", 0)
    if not rows or not ticks or secs <= 0 or traced_ticks <= 0:
        return None
    share = trace_scope_share.share(host_plane.device_lines(), spec,
                                    ctx.get("scopes", ()))
    if not share:
        return None
    try:
        bytes_fn = families.count_fn(ctx["config"], spec["bytes_fn"])
    except LookupError:
        return None
    rows_traced = rows / ticks * traced_ticks
    by = bytes_fn(ctx["config"], rows_traced)
    under = secs * share / 100.0
    floor = by / peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return {"value": 100.0 * floor / under,
            "note": f"memory bound; {by / 1e9:.3f} GB for "
                    f"{rows_traced:.0f} slot-ticks in {traced_ticks:.0f} "
                    f"ticks, {1e3 * under:.3f} ms under "
                    f"{'/'.join(spec['scope'])}"}
