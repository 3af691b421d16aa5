"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run. Everything that belongs to one configuration, one
traffic mix, one cell or one per-layer metric is a file found by the name in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json``,
``workloads/<cell>.json``, ``layer_metrics/<metric>.json`` (which names its
reader in ``readers/``). The traffic file's ``kind`` picks the runner.

The last line of standard output is the result object. Without a TPU, or
with fewer chips than the cell asks, the command exits non-zero and prints
no result. ``--rehearse`` walks a cell's code path at the tiny sizes its
files give under ``rehearse`` on the CPU (virtual devices for a four-chip
cell) and never prints a result line or a device metric.
"""

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

RUNNERS = {"train_job": "perfbench.runners.train",
           "open_loop": "perfbench.runners.serve",
           "backlog": "perfbench.runners.serve"}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if (isinstance(v, dict)
                                        and isinstance(out.get(k), dict)) else v
    return out


class Env:
    """What a runner is handed."""

    def __init__(self, args, manifest):
        here = ROOT / "perfbench"
        cell = next((w for w in manifest["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            # a cell whose files are in place but which is not in the
            # manifest (kept for a later PR) can still be driven by name
            wl = here / "workloads" / f"{args.workload}.json"
            if not wl.exists():
                raise SystemExit(f"unknown workload {args.workload!r}")
            cell = load_json(wl)
            cell = {"name": args.workload, "config": cell["config"],
                    "traffic": cell["traffic"], "chips": cell["chips"]}
        self.manifest = manifest
        self.name = cell["name"]
        self.chips = int(cell["chips"])
        self.cell = load_json(here / "workloads" / f"{cell['name']}.json")
        cfg_entry = next(
            (c for c in manifest["configs"] if c["name"] == cell["config"]),
            {"file": f"perfbench/configs/{cell['config']}.json"})
        self.config = load_json(ROOT / cfg_entry["file"])
        self.traffic = load_json(here / "traffic" / f"{cell['traffic']}.json")
        self.traffic.setdefault("name", cell["traffic"])
        self.rehearse = bool(args.rehearse)
        if self.rehearse:
            self.config = overlay(self.config, self.config.get("rehearse", {}))
            self.traffic = overlay(self.traffic,
                                   self.traffic.get("rehearse", {}))
            self.cell = overlay(self.cell, self.cell.get("rehearse", {}))
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = int(args.trace)
        self.scratch = ROOT / ".perfbench_out" / (
            self.name + (".rehearse" if self.rehearse else ""))
        self.scratch.mkdir(parents=True, exist_ok=True)
        self.setup_s = None
        self.watch = None
        self.devices = None

    def window_opens(self):
        self.setup_s = time.monotonic() - _T_PROCESS

    def memory_peak(self) -> int:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak


def layer_metrics(env, result, device_kind):
    """Every per-layer metric of the manifest that lists this cell (or
    lists none), read by the reader its file names."""
    from distributed_compute_pytorch_tpu.obs import tracing
    here = ROOT / "perfbench"
    ctx = {"spans": result.get("spans"), "counters": result["counters"],
           # the scope names the program declares (none in a program from
           # before it had any): what "under no declared scope" leaves out
           "scopes": tuple(getattr(tracing, "SCOPES", ())),
           "trace": result.get("trace"), "requests": result.get("requests"),
           "e2e": result["e2e"], "config": env.config,
           "traffic": env.traffic, "cell": env.cell, "chips": env.chips,
           "device_kind": device_kind}
    out = {}
    for m in env.manifest["per_layer"]:
        if "workloads" in m and env.name not in m["workloads"]:
            continue
        spec = load_json(here / "layer_metrics" / f"{m['name']}.json")
        reader = importlib.import_module(f"perfbench.readers.{spec['reader']}")
        got = reader.read(spec, ctx)
        if got is None:
            continue
        note = None
        if isinstance(got, dict):
            got, note = got["value"], got.get("note")
        out[m["name"]] = {"value": got, "unit": m["unit"]}
        print(f"LAYER {m['name']} = {got!r} {m['unit']}"
              + (f"  ({note})" if note else ""))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    manifest = load_json(ROOT / "BENCHMARK.json")
    env = Env(args, manifest)

    if env.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={env.chips}")
        # a rehearsal keeps its programs out of the checkout's cache
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / ".perfbench_out" / "rehearse_cache")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from distributed_compute_pytorch_tpu.utils import compilation_cache
    if env.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if not env.rehearse and (devices[0].platform != "tpu"
                             or len(devices) < env.chips):
        print(f"perfbench: {env.name} needs {env.chips} TPU chip(s); found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    env.devices = devices[:env.chips]
    cache_dir = compilation_cache.enable()
    from perfbench.compilewatch import CompileWatch
    env.watch = CompileWatch()
    print(f"perfbench | {env.name} | seed {env.seed} | {env.seconds:g} s | "
          f"trace {env.trace} | {devices[0].platform} "
          f"{devices[0].device_kind} x{len(env.devices)} | compile cache "
          f"{cache_dir}" + (" | REHEARSAL on the CPU: no number below is a "
                            "device number" if env.rehearse else ""))

    runner = importlib.import_module(RUNNERS[env.traffic["kind"]])
    result = runner.run(env)

    checks = result["checks"]
    checks.print()
    print(f"INFO end to end (this run): {result['e2e']}")
    watch = env.watch.summary()
    print(f"INFO set-up {env.setup_s:.3f} s; programs built in this process: "
          f"{watch['compiles']} ({watch['compile_s']:.1f} s), of which "
          f"fetched from the cache {watch['cache_fetches']}; "
          f"peak device memory {result['memory_peak_bytes'] / 1e9:.3f} GB")
    if env.rehearse:
        print(f"REHEARSAL done: checks "
              f"{'pass' if checks.correct else 'FAIL'}; e2e "
              f"{ {k: round(v, 3) for k, v in result['e2e'].items()} } "
              f"(CPU numbers, not device numbers)")
        if env.trace:
            print("LAYER lines below: CPU rehearsal against the v5e table, "
                  "NOT device numbers")
            layer_metrics(env, result, "TPU v5 lite")
        return 0 if checks.correct else 1

    kind = devices[0].device_kind
    correct = checks.correct
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(env.devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if env.trace:
        tr = result["trace"]
        metrics = layer_metrics(env, result, kind)
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            line["breakdown"] = {"device_ops": tr.top_ops(10),
                                 "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = {}
        values = dict(result["e2e"], setup_s=env.setup_s)
        for m in manifest["end_to_end"]:
            if "workloads" in m and env.name not in m["workloads"]:
                continue
            v = values.get(m["name"])
            if v is None:
                continue
            if not math.isfinite(v):
                v, correct = 1e12, False
                line["correct"] = False
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
