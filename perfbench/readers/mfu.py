"""Model FLOP/s utilization of a training cell: tokens/s x FLOPs/token
(``flops_fn`` of the configuration's family, recomputation not counted)
over chips x the published bf16 peak, in %. Never clipped. Source:
host_clock (the run's own tokens/s) and the table of peaks."""

from perfbench import families, peaks


def read(spec, ctx):
    tps = ctx["counters"].get("train_tokens_per_s")
    if not tps:
        return None
    fn = families.count_fn(ctx["config"], spec["flops_fn"])
    per_token = fn(ctx["config"], ctx["counters"]["seq_len"])
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return {"value": 100.0 * tps * per_token / peak,
            "note": f"{per_token / 1e9:.4f} GFLOP/token against "
                    f"{peak / 1e12:.0f} TFLOP/s (compute bound)"}
