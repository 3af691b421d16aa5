"""Roofline share of the decode tick: the bytes one tick over all slots
has to read (``decode_tick_bytes(cfg, live_context_tokens)`` of the
configuration's family: for a dense model the weights + the K/V of the
live contexts) / HBM bandwidth over the device time per tick of the
segment module, in %. Memory bound by construction (one token per slot per
tick). Never clipped. Source: device_trace and program_counter."""

from perfbench import families, peaks


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    secs, runs = tr.module_time_s(spec["module"])
    ticks = runs * ctx["counters"].get("segment", 0)
    live = ctx["counters"].get("mean_live_context_tokens")
    if ticks <= 0 or secs <= 0 or live is None:
        return None
    by = families.count_fn(ctx["config"], "decode_tick_bytes")(
        ctx["config"], live)
    floor = by / peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return {"value": 100.0 * floor / (secs / ticks),
            "note": f"memory bound; {by / 1e9:.3f} GB per tick, "
                    f"{1e3 * secs / ticks:.3f} ms per tick, "
                    f"{live:.0f} live context tokens"}
