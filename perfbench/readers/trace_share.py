"""Time of the device operations matching ``ops`` over the traced window
(``of: "window"``) or over the time of the modules matching ``of_module``,
in %. ``union: true`` takes the union of the intervals instead of their
sum (collectives that overlap each other). Source: device_trace."""


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    t = (tr.op_union_s(spec["ops"]) if spec.get("union")
         else tr.op_time_s(spec["ops"]))
    if spec.get("of_module"):
        den, runs = tr.module_time_s(spec["of_module"])
        if runs <= 0:
            return None
    else:
        den = tr.window_s
    if den <= 0 or (t <= 0 and not spec.get("zero_ok")):
        return None
    return 100.0 * t / den
