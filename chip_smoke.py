#!/usr/bin/env python3
"""The standing chip check: dcp-train -> checkpoint -> dcp-serve on the TPU.

Drives the main path once, through the entry points a user would call, at
GPT-2-small's published widths (12 layers, d_model 768, 12 heads of 64,
d_ff 3072) at sequence length 1024 in bf16 — `train.py` takes a few AdamW
steps on a byte-tokenized corpus generated here from a seed and writes a
checkpoint, then `python -m distributed_compute_pytorch_tpu.cli_serve`
loads it and answers a request file. The vocabulary follows the byte
tokenizer (259 rows: that is the CLI's contract for `--dataset text`, not
GPT-2's 50,257-row read-out). With four or more chips visible it also
trains on `--mesh data=4` and serves with `--replicas 4`.

It fails (non-zero exit, no REPORT and no result line) unless: JAX finds a TPU; every leg
exits 0 inside its time limit; the loss is finite, starts near ln(vocab)
and falls; every request comes back `ok` with its full budget, no fault
recovered, identical prompts giving identical streams; the serve process
saw bf16 weights and a bf16 pool; and the compiled programs carry the
Pallas kernels that belong on the path as Mosaic custom calls (flash
forward and backward in the train step, flash forward in admission
prefill, the pool window write in the decode segment). A last leg serves
a tiny Llama with heads of 128 lanes (random weights, float32 at the
highest matmul precision, so that greedy streams can be compared token for
token) through `ContinuousBatcher`: on the chip the decode tick must read
the pool through the block-table kernel (`paged_read == "kernel"`,
`dcp_paged_decode_attn` in the compiled segment) and every stream must
equal `infer.generate`'s contiguous decode of the same prompt.

The chip belongs to one process at a time, so this parent never imports
JAX or the package: it starts one child after another (a probe, the train
leg, the serve leg), each with JAX_PLATFORMS=tpu so a missing chip is
JAX's own error, and waits for each to exit.

    python3 chip_smoke.py                 # the check; last stdout line is
                                          # {"ok": true, "device": {"platform":
                                          # ..., "kind": ..., "count": N}} and
                                          # nothing else; the line before it,
                                          # "REPORT {...}", is the full record
    python3 chip_smoke.py --rehearse-cpu  # control-flow rehearsal at a tiny
                                          # size on faked CPU devices — says
                                          # so, and is NOT a chip result

Small artifacts (logs, metrics, the result) land in chiprun_out/chip_smoke/;
corpus and checkpoints live in a work directory under it that is removed at
exit. The compile cache is the program's own (JAX_COMPILATION_CACHE_DIR if
set, else <checkout>/.jax_cache); its entry count is reported before and
after.
"""

import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
WORK = os.path.join(OUT, "work")
DEADLINE_S = 1150            # one-chip legs, compilation included
VOCAB = 259                  # byte tokenizer: 256 bytes + pad/bos/eos
BATCH = 8
STEPS = 12

# the real size, and the tiny one the CPU rehearsal cuts it to
CHIP = {"seq_len": 1024, "model_args": [],
        "widths": {"num_layers": 12, "d_model": 768, "num_heads": 12,
                   "d_ff": 3072, "max_seq_len": 1024},
        "prompts": [(16, 16), (48, 32), (130, 80), (257, 24), (384, 64),
                    (512, 48), (700, 96), (130, 80)]}
TINY = {"seq_len": 64, "model_args": ["--model_preset", "tiny"],
        "widths": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                   "d_ff": 128, "max_seq_len": 64},
        "prompts": [(4, 4), (8, 8), (12, 12), (16, 6), (20, 12), (24, 8),
                    (40, 16), (12, 12)]}

_PROBE = r"""
import importlib.metadata as md, json
import jax
d = jax.devices()
def ver(p):
    try:
        return md.version(p)
    except md.PackageNotFoundError:
        return None
rec = {"platform": d[0].platform, "device_kind": d[0].device_kind,
       "count": len(d), "jax": jax.__version__, "jaxlib": ver("jaxlib"),
       "libtpu": ver("libtpu")}
print("PROBE " + json.dumps(rec), flush=True)
from distributed_compute_pytorch_tpu import native
print("NATIVE " + json.dumps(native.available()), flush=True)
"""

# the paged decode read: a Llama whose heads are whole 128-lane tiles, so
# the block-table kernel is eligible; prompts and budgets that cross block
# (8) and chunk (512) edges, more requests than slots so rows park and are
# reused. argv[1] = [t_max, [[prompt tokens, max_new], ...]]
_PAGED = r"""
import dataclasses, json, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
from distributed_compute_pytorch_tpu.infer import generate
from distributed_compute_pytorch_tpu.models.llama import LlamaConfig, LlamaLM
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
t_max, shapes = json.loads(sys.argv[1])
model = LlamaLM(dataclasses.replace(
    LlamaConfig.tiny(), d_model=512, num_heads=4, num_kv_heads=2, d_ff=1024,
    max_seq_len=t_max))
params, _ = model.init(jax.random.key(0))
rng = np.random.default_rng(0)
reqs = [Request(tokens=[int(t) for t in rng.integers(0, 256, n)], max_new=m)
        for n, m in shapes]
cb = ContinuousBatcher(model, params, slots=3, t_max=t_max,
                       prompt_buf=max(n for n, _ in shapes), segment=8)
served = cb.serve(reqs)
snap = cb.stats_snapshot()
solo = [[int(t) for t in np.asarray(generate(
            model, params, jnp.asarray([r.tokens], jnp.int32),
            r.max_new))[0, len(r.tokens):]] for r in reqs]
print("PAGED " + json.dumps({
    "paged_read": snap["paged_read"], "engine": snap["engine"],
    "head_dim": model.config.head_dim, "stats": snap["stats"],
    "kernels": cb.kernel_census().get("segment", {}).get("kernels", {}),
    "served": served, "solo": solo}), flush=True)
"""
PAGED_CHIP = [640, [[5, 12], [250, 20], [500, 40], [17, 30], [64, 9],
                    [5, 12]]]
PAGED_TINY = [48, [[3, 6], [14, 10], [20, 12], [5, 9], [3, 6]]]

_COMPILE_RE = re.compile(
    r"Finished (?:tracing \+ transforming|jaxpr to MLIR module conversion|"
    r"XLA compilation of) .* in ([0-9.eE+-]+) sec")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


_live: list = []             # children still running (killed on any exit)


def _kill(proc) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def run_child(name: str, cmd: list, env: dict, timeout: float) -> dict:
    """Run one child to its end (the chip has one owner at a time): its
    output goes to OUT/<name>.log; returns wall time split by JAX's own
    compile timers (JAX_LOG_COMPILES) into compile and run."""
    log_path = os.path.join(OUT, f"{name}.log")
    check(timeout > 5, f"{name}: no time left inside the {DEADLINE_S}s limit")
    shown = " ".join(a for a in cmd[1:6] if "\n" not in a)
    say(f"{name}: python {shown} ... (limit {timeout:.0f}s)")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        _live.append(proc)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            _kill(proc)
            _live.remove(proc)
    wall = time.monotonic() - t0
    with open(log_path, errors="replace") as f:
        text = f.read()
    if rc != 0:
        tail = "\n".join(text.splitlines()[-40:])
        raise SmokeFailure(
            f"{name}: " + (f"timed out after {timeout:.0f}s" if rc is None
                           else f"exit code {rc}") + f"\n{tail}")
    compile_s = sum(float(m) for m in _COMPILE_RE.findall(text))
    say(f"{name}: ok in {wall:.1f}s (compile {compile_s:.1f}s)")
    # replicas compile on concurrent threads: their timers can sum past
    # the wall, in which case nothing is left to call run time
    return {"wall_s": round(wall, 1), "compile_s": round(compile_s, 1),
            "run_s": round(max(wall - compile_s, 0.0), 1), "log": text}


def read_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def cache_entries(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


# ---------------------------------------------------------------------------
# inputs, generated from a seed
# ---------------------------------------------------------------------------

def make_text(n_chars: int, seed: int = 0) -> str:
    """ASCII pseudo-language: a first-order Markov chain over a made-up
    vocabulary, so a few steps are enough for the loss to fall."""
    rng = random.Random(seed)
    letters = "abcdefghijklmnopqrstuvwxyz"
    words = ["".join(rng.choice(letters) for _ in range(rng.randint(2, 8)))
             for _ in range(200)]
    follow = [rng.sample(range(len(words)), 6) for _ in words]
    out, w, size = [], 0, 0
    while size < n_chars:
        w = rng.choice(follow[w])
        piece = words[w] + (". " if rng.random() < 0.1 else " ")
        out.append(piece)
        size += len(piece)
    return "".join(out)[:n_chars]


def write_inputs(size: dict) -> tuple:
    """Corpus sized so the trainer takes exactly STEPS full batches (it has
    no step cap: the corpus length sets it; text_lm keeps the last 5% of
    windows for eval and appends one eos), and the request file."""
    T = size["seq_len"]
    n_seq = next(n for n in range(STEPS * BATCH, 2 * STEPS * BATCH + 2)
                 if n - max(1, round(n * 0.05)) == STEPS * BATCH)
    text = make_text(n_seq * T - 1)
    corpus = os.path.join(WORK, "corpus.txt")
    with open(corpus, "w") as f:
        f.write(text)
    requests = os.path.join(OUT, "requests.jsonl")
    with open(requests, "w") as f:
        for i, (n_prompt, max_new) in enumerate(size["prompts"]):
            # byte tokenizer: ids are the UTF-8 bytes. The last request
            # repeats the third: identical prompts must give identical
            # streams whatever rows and neighbours they decode beside
            start = 37 * (2 if i == len(size["prompts"]) - 1 else i)
            ids = list(text[start:start + n_prompt].encode())
            f.write(json.dumps({"tokens": ids, "max_new": max_new}) + "\n")
    return corpus, requests


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def train_leg(name, size, env, corpus, mesh, timeout, on_chip) -> dict:
    metrics = os.path.join(OUT, f"{name}_metrics.jsonl")
    ckpt = os.path.join(WORK, f"{name}.npz")
    for p in (metrics, ckpt):
        if os.path.exists(p):
            os.unlink(p)
    cmd = [sys.executable, "train.py", "--model", "gpt2",
           *size["model_args"], "--dataset", "text", "--data_dir", corpus,
           "--tokenizer", "byte", "--seq_len", str(size["seq_len"]),
           "--batch_size", str(BATCH), "--epochs", "1",
           "--optimizer", "adamw", "--lr", "3e-4",
           "--compute_dtype", "bfloat16", "--param_dtype", "bfloat16",
           "--mesh", mesh, "--log_every", "1", "--ckpt_path", ckpt,
           "--metrics_jsonl", metrics, "--collective_stats"]
    leg = run_child(name, cmd, env, timeout)
    recs = read_jsonl(metrics)
    run = next(r for r in recs if r["kind"] == "run")
    losses = [r["loss"] for r in recs if r["kind"] == "train"]
    evals = [r["loss"] for r in recs if r["kind"] == "eval"]
    census = next(r for r in recs if r["kind"] == "collectives")
    mem = [r for r in recs if r["kind"] == "memory"]
    cfg = run["model_config"]
    check(all(cfg[k] == v for k, v in size["widths"].items())
          and cfg["vocab_size"] == VOCAB,
          f"{name}: model is not the configured size: {cfg}")
    check(run["param_dtype"] == "bfloat16",
          f"{name}: parameters are {run['param_dtype']}, not bfloat16")
    check(len(losses) >= 8, f"{name}: only {len(losses)} steps")
    check(all(math.isfinite(x) for x in losses + evals) and evals,
          f"{name}: non-finite loss {losses} {evals}")
    # random init reads out near-uniform logits: the first loss is the
    # analytic reference ln(vocab), and training must move it down
    check(abs(losses[0] - math.log(VOCAB)) < 0.1 * math.log(VOCAB),
          f"{name}: first loss {losses[0]} is not near ln({VOCAB})")
    check(losses[-1] < losses[0] - 0.1 and evals[-1] < losses[0],
          f"{name}: loss is not falling: {losses} eval {evals}")
    check(os.path.exists(ckpt), f"{name}: no checkpoint at {ckpt}")
    kernels = (census.get("kernels") or {}).get("kernels", {})
    if on_chip:
        check(run["platform"] == "tpu", f"{name}: ran on {run['platform']}")
        for k in ("dcp_flash_fwd", "dcp_flash_bwd_dq", "dcp_flash_bwd_dkv"):
            check(kernels.get(k, 0) >= 1,
                  f"{name}: {k} is not in the compiled train step as a "
                  f"Mosaic call (census: {census.get('kernels')})")
    peaks = {}
    for r in mem[-1:]:
        peaks = {k.split(".")[1]: v for k, v in r.items()
                 if k.endswith(".peak_bytes_in_use")}
    return {**{k: leg[k] for k in ("wall_s", "compile_s", "run_s")},
            "mesh": run["mesh"], "devices": run["devices"],
            "param_count": run["param_count"],
            "param_dtype": run["param_dtype"], "config": cfg,
            "steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1], "eval_loss": evals[-1],
            "losses": losses, "kernels": census.get("kernels"),
            "collectives": census.get("hlo"),
            "peak_bytes_per_device": peaks, "ckpt": ckpt}


def serve_leg(name, size, env, ckpt, requests, extra, timeout,
              on_chip) -> dict:
    metrics = os.path.join(OUT, f"{name}_metrics.jsonl")
    if os.path.exists(metrics):
        os.unlink(metrics)
    cmd = [sys.executable, "-m", "distributed_compute_pytorch_tpu.cli_serve",
           "--ckpt_path", ckpt, "--model", "gpt2", *size["model_args"],
           "--vocab_size", str(VOCAB),
           "--max_seq_len", str(size["widths"]["max_seq_len"]),
           "--requests", requests, "--metrics_jsonl", metrics, *extra]
    leg = run_child(name, cmd, env, timeout)
    lines = [json.loads(l) for l in leg["log"].splitlines()
             if l.startswith('{"id"')]
    want = size["prompts"]
    check(len(lines) == len(want),
          f"{name}: {len(lines)} result lines for {len(want)} requests")
    for rec, (n_prompt, max_new) in zip(lines, want):
        check(rec["status"] == "ok" and not rec.get("migrated"),
              f"{name}: {rec['id']} is {rec['status']} "
              f"(migrated={rec.get('migrated')}): {rec.get('error')}")
        check(len(rec["prompt"]) == n_prompt and len(rec["new"]) == max_new
              and all(0 <= t < VOCAB for t in rec["new"]),
              f"{name}: {rec['id']} returned {len(rec['new'])} tokens "
              f"for a budget of {max_new}")
    check(lines[2]["new"] == lines[-1]["new"],
          f"{name}: identical prompts gave different streams")
    engines = [r for r in read_jsonl(metrics)
               if r["kind"] == "serve_kernels"]
    check(engines and all("programs" in e for e in engines),
          f"{name}: no kernel census: {engines}")
    for e in engines:
        eng = e["engine"]
        # a fault "recovered" on a healthy chip is a failure here: session
        # reconstruction (or a migration off the replica) would otherwise
        # hide a kernel that does not compile
        check(e["stats"]["faults"] == 0
              and e["stats"]["reconstructions"] == 0,
              f"{name}: engine {e['replica']} recovered from "
              f"{e['stats']['faults']} fault(s)")
        check(eng["param_dtype"] == "bfloat16"
              and eng["pool_dtype"] == "bfloat16",
              f"{name}: serve process saw {eng}")
        if on_chip:
            check(eng["platform"] == "tpu" and eng["pool_write"] == "pallas",
                  f"{name}: engine {eng}")
            admit = e["programs"]["admit"]["kernels"]
            segment = e["programs"]["segment"]["kernels"]
            check(admit.get("dcp_flash_fwd", 0) >= 1,
                  f"{name}: flash forward is not in the compiled admission "
                  f"prefill as a Mosaic call ({admit})")
            check(segment.get("dcp_kv_pool_write", 0) >= 1,
                  f"{name}: the pool window write is not in the compiled "
                  f"decode segment as a Mosaic call ({segment})")
    return {**{k: leg[k] for k in ("wall_s", "compile_s", "run_s")},
            "requests_ok": len(lines),
            "recoveries": sum(e["stats"]["faults"] for e in engines),
            "new_tokens": sum(len(r["new"]) for r in lines),
            "engines": [{"replica": e["replica"], **e["engine"],
                         "kernels": {p: c["kernels"] for p, c
                                     in e["programs"].items()}}
                        for e in engines],
            "streams": [r["new"] for r in lines]}


def paged_leg(env, shapes, timeout, on_chip) -> dict:
    """The decode tick's pool read through the block table: engaged where
    the operands allow (on the chip, heads of 128), and token for token
    what the contiguous decode gives."""
    leg = run_child("paged", [sys.executable, "-c", _PAGED,
                              json.dumps(shapes)], env, timeout)
    rec = json.loads(re.search(r"^PAGED (.*)$", leg["log"], re.M)[1])
    check(rec["head_dim"] == 128, f"paged: heads of {rec['head_dim']}")
    check(rec["stats"]["faults"] == 0
          and rec["stats"]["reconstructions"] == 0,
          f"paged: recovered from {rec['stats']['faults']} fault(s)")
    for i, (got, want) in enumerate(zip(rec["served"], rec["solo"])):
        check(len(got) == shapes[1][i][1] and got == want,
              f"paged: request {i} {shapes[1][i]} served {got}, the "
              f"contiguous decode gives {want}")
    want_path = "kernel" if on_chip else "gather"
    check(rec["paged_read"] == want_path,
          f"paged: the tick reads the pool through {rec['paged_read']!r}, "
          f"not {want_path!r} ({rec['engine']})")
    if on_chip:
        check(rec["kernels"].get("dcp_paged_decode_attn", 0) >= 1,
              f"paged: the block-table read is not in the compiled decode "
              f"segment as a Mosaic call ({rec['kernels']})")
    return {**{k: leg[k] for k in ("wall_s", "compile_s", "run_s")},
            "paged_read": rec["paged_read"], "requests_same": len(shapes[1]),
            "new_tokens": sum(len(t) for t in rec["served"]),
            "kernels": rec["kernels"]}


def four_chip_legs(size, env, corpus, requests, one, on_chip) -> dict:
    """--mesh data=4 at the same global batch, then --replicas 4, each
    checked against its one-chip leg."""
    train = train_leg("train4", size, env, corpus, "data=4", 900, on_chip)
    deltas = [abs(a - b) for a, b in zip(train["losses"],
                                         one["train"]["losses"])]
    # the logged loss is a bf16 scalar: 0.031 apart at this magnitude
    check(len(deltas) == STEPS and max(deltas) < 0.15,
          f"train4: loss differs from the one-chip leg by {max(deltas)}: "
          f"{train['losses']} vs {one['train']['losses']}")
    train["max_loss_delta_vs_one_chip"] = max(deltas)
    if on_chip:
        peaks = list(train["peak_bytes_per_device"].values())
        check(len(peaks) == 4 and max(peaks) < 1.25 * min(peaks),
              f"train4: per-device peak memory is uneven: "
              f"{train['peak_bytes_per_device']}")
        # each chip's kernel grid covers its LOCAL batch: (B/4)*heads rows
        # — a gather of q/k/v into the call would show the global batch
        rows = BATCH // 4 * size["widths"]["num_heads"]
        shapes = train["kernels"]["shapes"]["dcp_flash_fwd"]
        check(shapes and shapes[0].startswith(f"bf16[{rows},"),
              f"train4: flash output {shapes}: not the local batch "
              f"({rows} rows)")
    serve = serve_leg("serve4", size, env, one["train"]["ckpt"], requests,
                      ["--replicas", "4"], 900, on_chip)
    check(serve["streams"] == one["serve"]["streams"],
          "serve4: output lines differ from the one-replica run")
    devs = [tuple(e["devices"]) for e in serve["engines"]]
    check(len(devs) == 4 and len(set(devs)) == 4
          and all(len(d) == 1 for d in devs),
          f"serve4: replicas are not on four devices: {devs}")
    return {"train4": train, "serve4": serve}


def main(argv) -> int:
    rehearse = argv == ["--rehearse-cpu"]
    if argv and not rehearse:
        print(__doc__)
        return 2
    size = TINY if rehearse else CHIP
    t_start = time.monotonic()
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(WORK)
    env = dict(os.environ, JAX_PLATFORMS="cpu" if rehearse else "tpu",
               JAX_LOG_COMPILES="1", PYTHONUNBUFFERED="1")
    if rehearse:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(ROOT, ".jax_cache"))
    result = {"cache": {"dir": cache_dir,
                        "entries_before": cache_entries(cache_dir)}}

    def left() -> float:
        return DEADLINE_S - (time.monotonic() - t_start)

    try:
        try:
            probe_log = run_child("probe", [sys.executable, "-c", _PROBE],
                                  env, min(120, left()))["log"]
        except SmokeFailure as e:
            raise SmokeFailure(f"no chip: JAX found no TPU, or the "
                               f"repository is not here\n{e}") from None
        probe = json.loads(re.search(r"^PROBE (.*)$", probe_log, re.M)[1])
        native = json.loads(re.search(r"^NATIVE (.*)$", probe_log, re.M)[1])
        say(f"probe: {probe} native.available()={native}")
        on_chip = probe["platform"] == "tpu"
        check(on_chip or rehearse,
              f"no chip: JAX reports platform {probe['platform']!r}")
        result["device"] = {"platform": probe["platform"],
                            "kind": probe["device_kind"],
                            "count": probe["count"]}
        result["versions"] = {k: probe[k] for k in ("jax", "jaxlib",
                                                    "libtpu")}
        result["native_available"] = native

        corpus, requests = write_inputs(size)
        one = {"train": train_leg("train", size, env, corpus, "data=1",
                                  min(700, left()), on_chip)}
        say(f"train: {one['train']['steps']} steps, loss "
            f"{one['train']['first_loss']:.3f} -> "
            f"{one['train']['last_loss']:.3f}, "
            f"{one['train']['param_count']} params, config "
            f"{one['train']['config']}")
        one["serve"] = serve_leg("serve", size, env, one["train"]["ckpt"],
                                 requests, [], min(700, left()), on_chip)
        one["paged"] = paged_leg(env, PAGED_TINY if rehearse else PAGED_CHIP,
                                 min(400, left()), on_chip)
        legs = dict(one)
        if probe["count"] >= 4:
            legs.update(four_chip_legs(size, env, corpus, requests, one,
                                       on_chip))
    except SmokeFailure as e:
        say(f"FAILED after {time.monotonic() - t_start:.0f}s: {e}")
        return 1
    finally:
        for proc in list(_live):
            _kill(proc)
        shutil.rmtree(WORK, ignore_errors=True)

    for leg in legs.values():
        for k in ("losses", "streams", "ckpt"):
            leg.pop(k, None)
    result["cache"]["entries_after"] = cache_entries(cache_dir)
    result["legs"] = legs
    result["wall_s"] = round(time.monotonic() - t_start, 1)
    if rehearse:
        # NOT a chip result: control flow only, tiny size, CPU devices
        report = {"rehearsal": "passed", "chip_result": False,
                  "note": "CPU rehearsal at the tiny preset: no number "
                          "here is a device measurement", **result}
    else:
        report = {"ok": True, **result}
    with open(os.path.join(OUT, "result.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("REPORT " + json.dumps(report), flush=True)
    if not rehearse:
        # the contract's last line: these two keys and nothing else
        print(json.dumps({"ok": True, "device": result["device"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main(sys.argv[1:]))
