"""Tokens per second that reached the host after the traffic's ramp had
ended: the tokens of each admitted request are laid evenly between its
first token's stamp and its end (they arrive a segment at a time, so a
row's count at the ramp's end is exact to half a segment), those after
``ramp.requests x ramp.gap_s`` are summed and divided by the rest of the
window. A mix without a ramp, or a window that ends inside it, gives
nothing. Source: host_clock as the program stamps it on ``RequestResult``."""


def read(spec, ctx):
    ramp = ctx["traffic"].get("ramp")
    reqs = ctx.get("requests")
    window_s = ctx["counters"].get("window_s")
    if not ramp or not reqs or not window_s:
        return None
    after = float(ramp["requests"]) * float(ramp["gap_s"])
    if window_s <= after:
        return None
    tokens = 0.0
    for r in reqs:
        if r["ttft_s"] is None or not r["tokens"]:
            continue
        first = r["arrival_s"] + r["ttft_s"]
        last = max(r["arrival_s"] + r["latency_s"], first)
        n = r["tokens"]
        if first >= after:
            tokens += n
        elif last > after and n > 1:
            # token k of n lands at first + k (last - first) / (n - 1)
            k = (after - first) * (n - 1) / (last - first)
            tokens += n - 1 - int(k)
    return {"value": tokens / (window_s - after),
            "note": f"{tokens:.0f} tokens in the {window_s - after:.2f} s "
                    f"after the {after:g} s ramp"}
