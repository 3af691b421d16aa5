"""The upper edge of the bucket that holds the ``q``-th percentile of a
distribution the program keeps as a FAMILY of counters, one count an
observation in the bucket it fell in (not cumulative). Every counter whose
name starts with ``prefix`` is one bucket, and the rest of its name is the
bucket's upper edge in the metric's unit (``inf`` for the bucket with none:
``delivery_gap_upto_154``, ``delivery_gap_upto_inf``), so the edges live in
the program alone. The counts are the window's differences of what the
runner collected from the program, so the percentile is the WINDOW's, of
every run, traced or not. The value is the edge at or under which ``q`` %
of the observations lie (nearest rank), so it overstates by up to a
bucket's width; where that is the bucket without an edge, the highest
finite edge, and the note says so. The note gives the number of
observations, the edge of the median and the highest bucket occupied. No
such counter (a program from before it kept the family), or none that
counted, gives nothing. Source: program_counter."""

import math


def read(spec, ctx):
    prefix = spec["prefix"]
    buckets = sorted((float(name[len(prefix):]), count)
                     for name, count in ctx["counters"].items()
                     if name.startswith(prefix))
    total = sum(count for _, count in buckets)
    if total <= 0:
        return None

    def edge(q):
        rank, below = math.ceil(q / 100.0 * total), 0
        for upper, count in buckets:
            below += count
            if count and below >= rank:
                return upper

    value = edge(float(spec["q"]))
    top = max(upper for upper, count in buckets if count)
    note = (f"{total:g} observations, p50 <= {edge(50.0):g}, highest bucket "
            f"occupied <= {top:g}")
    if math.isinf(value):
        value = max((upper for upper, _ in buckets if math.isfinite(upper)),
                    default=None)
        if value is None:
            return None
        note += f"; the percentile lies ABOVE {value:g}, the last edge"
    return {"value": value, "note": note}
