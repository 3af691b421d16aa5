"""The knee sweep of an open-loop cell, run once by hand on the chip: one
process builds and warms the batcher, then offers the cell's traffic at
each rate for ``--seconds`` and prints, per rate, what the knee is judged
by: requests shed or failed, and the median queue wait of the last third
of arrivals against that of the first third. The knee is the highest rate
with nothing shed and last-third median <= 2 x first-third median; the cell
then runs at four fifths of it, as a number in its traffic file.

    python3 perfbench/sweep.py --workload <cell> --rates 2,3,4,5,6,8 --seconds 30
"""

import argparse
import json
import pathlib
import sys
import time

_T0 = time.monotonic()
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=77)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    import numpy as np

    from perfbench import run as prun
    from perfbench.percentiles import percentile
    from perfbench.runners import serve
    argv = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=0, rehearse=a.rehearse)
    env = prun.Env(argv, prun.load_json(ROOT / "BENCHMARK.json"))
    if a.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from distributed_compute_pytorch_tpu.utils import compilation_cache
    compilation_cache.enable()
    from perfbench.compilewatch import CompileWatch
    env.watch = CompileWatch()
    env.devices = jax.devices()[:1]
    cb, *_ = serve.setup(env)
    print(f"SWEEP set-up {time.monotonic() - _T0:.1f} s", flush=True)
    for rate in [float(r) for r in a.rates.split(",")]:
        traffic = dict(env.traffic, rate_rps=rate)
        before = serve.counters_now(cb)
        reqs, results, t0, t1 = serve.offer(env, cb, traffic, a.seconds)
        built = env.watch.between(t0, t1)
        qw = [r.queue_wait_s if r.queue_wait_s is not None else float("inf")
              for r in results]
        third = max(len(qw) // 3, 1)
        ms = lambda v: float("inf") if v is None else 1e3 * v
        row = {"rate_rps": rate, "requests": len(results),
               "not_ok": sum(r.status != "ok" for r in results),
               "qw_first_third_median_s": float(np.median(qw[:third])),
               "qw_last_third_median_s": float(np.median(qw[-third:])),
               "ttft_p50_ms": percentile([ms(r.ttft_s) for r in results], 50),
               "ttft_p90_ms": percentile([ms(r.ttft_s) for r in results], 90),
               "tpot_p50_ms": percentile([ms(r.tpot_s) for r in results if r.tpot_s is not None], 50),
               "tpot_p90_ms": percentile([ms(r.tpot_s) for r in results if r.tpot_s is not None], 90),
               "tokens_per_s": sum(len(r.tokens) for r in results) / (t1 - t0),
               "window_s": t1 - t0,
               "faults": cb.stats["faults"] - before["faults"],
               "prefill_waves": cb.stats["prefill_calls"] - before["prefill_calls"],
               "prefill_rows": cb.stats["prefill_rows"] - before["prefill_rows"],
               "segments": cb.stats["segments"] - before["segments"],
               "programs_built_in_window": len(built)}
        print("SWEEP", json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
