"""Roofline share of a named kernel: the least time the chip could take
for the calls traced (``flops_fn`` and ``bytes_fn`` of one call's shapes,
times the number of calls traced) over the kernel's device time, in %. The
call's shapes, ``causal`` and the like included, come from the
configuration's family under the name ``shape`` (``families.kernel_shape``)
and go to both functions as they come; the functions are the family's own
or those of ``flops.py`` / ``bytes.py`` (``families.count_fn``). Says which
bound it was taken against; never clipped. Source: device_trace."""

from perfbench import families, peaks


def read(spec, ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    secs = sum(tr.op_time_s(k) for k in spec["kernels"])
    calls = tr.op_count(spec["kernels"][0])
    if secs <= 0 or calls <= 0:
        return None
    cfg = ctx["config"]
    shape = families.kernel_shape(cfg, spec["shape"], ctx["counters"],
                                  ctx["chips"])
    if shape is None:
        return None
    fl = families.count_fn(cfg, spec["flops_fn"])(**shape) * calls
    by = families.count_fn(cfg, spec["bytes_fn"])(**shape) * calls
    r = peaks.roofline(fl, by, secs, ctx["device_kind"])
    return {"value": r["share"],
            "note": f"{r['bound']} bound; {calls:.0f} calls, "
                    f"{secs * 1e3:.3f} ms"}
