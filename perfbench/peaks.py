"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``. One table; an unknown kind is an error,
never a default. Source of the v5e row: Google Cloud documentation,
"TPU v5e" (system architecture page): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}
# the same chip under the name some runtimes report
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"to perfbench/peaks.py with its source") from None


def roofline(flops: float, bytes_moved: float, seconds: float,
             device_kind: str, chips: int = 1) -> dict:
    """Share of the roofline a call reached: the least time the chip(s)
    could take (the larger of flops/peak and bytes/bandwidth) over the
    time taken. Never clipped: over 100% means the counts are wrong."""
    p = peaks_for(device_kind)
    t_compute = flops / (p["bf16_flops"] * chips)
    t_memory = bytes_moved / (p["hbm_bytes_per_s"] * chips)
    bound = "compute" if t_compute >= t_memory else "memory"
    return {"share": 100.0 * max(t_compute, t_memory) / seconds,
            "bound": bound, "t_compute_s": t_compute,
            "t_memory_s": t_memory}
