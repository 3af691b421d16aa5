"""Share of host time in one of the program's spans: summed duration of
the ``numerator`` spans over that of all the ``denominator`` spans, in %.
Source: program_span."""


def _durations(events):
    open_at, total = {}, {}
    for ev in events:
        key = (ev.get("tid"), ev["name"])
        if ev["ph"] == "B":
            open_at.setdefault(key, []).append(ev["ts"])
        elif ev["ph"] == "E" and open_at.get(key):
            total[ev["name"]] = (total.get(ev["name"], 0.0)
                                 + ev["ts"] - open_at[key].pop())
    return total


def read(spec, ctx):
    total = _durations(ctx["spans"] or [])
    den = sum(total.get(n, 0.0) for n in spec["denominator"])
    if den <= 0:
        return None
    return 100.0 * total.get(spec["numerator"], 0.0) / den
