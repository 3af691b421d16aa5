"""Roofline share of a named kernel: the least time the chip could take
for the calls traced (operations and bytes from ``perfbench.flops`` /
``perfbench.bytes`` of the call's shapes, times the number of calls
traced) over the kernel's device time, in %. Says which bound it was taken
against; never clipped. Source: device_trace."""

from perfbench import bytes as nbytes
from perfbench import families, flops, peaks


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    secs = sum(tr.op_time_s(k) for k in spec["kernels"])
    calls = tr.op_count(spec["kernels"][0])
    if secs <= 0 or calls <= 0:
        return None
    # the call's shapes come from the configuration's family
    # (``families.py``): the metric's file says which kind of run
    shape = families.attention_shape(ctx["config"], spec["shape"],
                                     ctx["counters"], ctx["chips"])
    if shape is None:
        return None
    fl = getattr(flops, spec["flops_fn"])(causal=True, **shape) * calls
    by = getattr(nbytes, spec["bytes_fn"])(**shape) * calls
    r = peaks.roofline(fl, by, secs, ctx["device_kind"])
    return {"value": r["share"],
            "note": f"{r['bound']} bound; {calls:.0f} calls, "
                    f"{secs * 1e3:.3f} ms"}
