"""1 - (union of the device-operation intervals / traced window), in %,
averaged over the chips the cell used. Source: device_trace."""


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
