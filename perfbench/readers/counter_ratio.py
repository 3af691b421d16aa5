"""``scale`` x product of the ``numerator`` counters over the product of
the ``denominator`` counters. Counter names are looked up in what the
runner collected from the program (``stats_snapshot`` and its own counts);
a missing or zero denominator gives nothing. Source: program_counter."""


def _product(names, counters):
    out = 1.0
    for n in names:
        if n not in counters:
            return None
        out *= float(counters[n])
    return out


def read(spec, ctx):
    num = _product(spec["numerator"], ctx["counters"])
    den = _product(spec["denominator"], ctx["counters"])
    if num is None or not den:
        return None
    return float(spec.get("scale", 1.0)) * num / den
