"""``dcp-serve`` — continuous-batching batch inference over a request file.

The serving-side companion of ``dcp-generate`` (which compiles one
fixed-shape batch): this drives ``serve.ContinuousBatcher`` — a fixed
pool of KV-cache rows decoding in compiled segments while finished rows
take the next queued request — so a FILE of mixed-length requests runs
through one statically-shaped program with no per-shape recompiles and
no padding to the longest request. Every request's output is
token-identical to what ``dcp-generate`` would produce for it alone
(``tests/test_serve.py``).

Requests come from ``--requests FILE`` (or ``-`` for stdin), one per
line, either

    12,7,90                     # token ids; --max_new_tokens applies
    {"tokens": [12,7,90], "max_new": 16}   # per-request budget

or, with ``--tokenizer``, ``{"text": "..."}`` lines / raw text lines.
``--prefix_cache`` turns on radix prefix caching over the paged KV
block pool (``--kv_block_tokens``): requests sharing a prompt prefix
attach to already-prefilled blocks copy-on-write instead of re-running
prefill — token-identical outputs, and every output line reports how
many prompt tokens were served from cache (``"cached_prefix"``).
JSON requests may also carry per-request sampling settings
(``"temperature"``, ``"top_k"``, ``"top_p"``, ``"seed"``), overriding
the CLI defaults — requests with different settings decode side by
side in the same compiled segment — a per-request wall-clock
``"deadline"`` (seconds), and a stable ``"id"`` (default:
``req-{line}``) that names the session in the journal and on its
output line. Prints one JSON line per request, in input order:
{"id": ..., "prompt": [...], "new": [...], "status": "ok"} (+ "text"
when a tokenizer is given; + "error" for non-ok outcomes).

CRASH DURABILITY (``serve_journal.py``): ``--journal_dir DIR`` keeps
an append-only CRC-framed write-ahead log of every admission, every
harvested token batch, and every terminal status (``--journal_fsync``
prices durability: every_frame | every_harvest | os). A killed
process restarted with the same ``--journal_dir`` and request file
dedups journal-completed requests (recorded stream, zero device
work) and resumes incomplete sessions token-identically from their
prompt + emitted-so-far. ``--supervise N`` runs serving under an
in-process supervisor: the serve loop runs as a subprocess and is
respawned (with ``elastic.backoff_delays`` backoff, at most N times)
whenever it dies abnormally — SIGKILL/OOM/crash — while clean exits
(0, 1, and 75/preempted) pass through.

Serving is FAULT-TOLERANT per request (``serve.serve_detailed``): a
request fails, times out (``--request_deadline`` default /
per-request ``"deadline"``), is shed under overload
(``--max_pending``), or is cut by a drain — the rest keep their
tokens. SIGTERM/SIGINT drains gracefully: admission stops, in-flight
rows finish within ``--drain_deadline``, every completed output is
still printed, and the process exits 75 (``EXIT_PREEMPTED``, same as
the trainer's preemption contract). A device fault mid-stream
triggers session reconstruction (token-identical resume from
host-tracked state); ``--fault_at_segment``/``--fault_mode`` inject
faults to drill exactly that path, the serving analogue of
``dcp-train --fault_at_step``.

``--mesh`` serves SHARDED (same spec language as ``dcp-generate``):
the checkpoint restores straight into the mesh layout, cache rows
shard over the batch axes and KV heads over ``tensor`` — ``--slots``
must then be a multiple of the batch-axis product.

Example:

    dcp-serve --ckpt_path ck.npz --model llama --model_preset tiny \\
        --requests prompts.txt --slots 8 --max_new_tokens 32 \\
        --mesh data=2,tensor=2 --temperature 0.8 --top_p 0.95
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _read_requests(path: str, tok, default_new: int, defaults: dict):
    """Parse the request file into dicts; JSON lines may override the
    CLI's sampling ``defaults`` (temperature/top_k/top_p/seed) per
    request."""
    lines = (sys.stdin if path == "-" else open(path)).read().splitlines()
    out = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        text = None
        sampling = dict(defaults)
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                raise SystemExit(f"requests line {i + 1}: bad JSON ({e})")
            if "text" in obj:
                text = obj["text"]
                ids = None
            else:
                ids = obj.get("tokens")
                if not isinstance(ids, list):
                    raise SystemExit(f"requests line {i + 1}: need "
                                     f"'tokens' (list) or 'text'")
            new = obj.get("max_new", default_new)
            if not isinstance(new, int) or new < 1:
                raise SystemExit(f"requests line {i + 1}: max_new must "
                                 f"be a positive integer, got {new!r}")
            for k in ("temperature", "top_k", "top_p", "seed",
                      "deadline", "id"):
                if k in obj:
                    sampling[k] = obj[k]
            if sampling.get("id") is not None \
                    and not isinstance(sampling["id"], str):
                raise SystemExit(f"requests line {i + 1}: 'id' must be "
                                 f"a string, got {sampling['id']!r}")
            if sampling["temperature"] == 0.0 and (
                    sampling["top_k"] is not None
                    or sampling["top_p"] is not None):
                raise SystemExit(
                    f"requests line {i + 1}: top_k/top_p require "
                    f"temperature > 0")
        elif tok is not None:
            text, ids, new = line, None, default_new
        else:
            try:
                ids = [int(t) for t in line.replace(",", " ").split()]
            except ValueError:
                raise SystemExit(
                    f"requests line {i + 1}: token ids expected (pass "
                    f"--tokenizer to serve raw text), got {line!r}")
            new = default_new
        if text is not None:
            if tok is None:
                raise SystemExit(f"requests line {i + 1} is text but no "
                                 f"--tokenizer was given")
            ids = tok.encode(text)
        if not ids:
            raise SystemExit(f"requests line {i + 1}: empty prompt")
        out.append({"tokens": ids, "max_new": new, **sampling})
    if not out:
        raise SystemExit("no requests")
    return out


def _strip_supervise(argv: list[str]) -> list[str]:
    """The child command line: everything the supervisor got, minus
    the --supervise flag itself (a supervised child must not recurse
    into another supervisor)."""
    out = []
    it = iter(argv)
    for a in it:
        if a == "--supervise":
            next(it, None)
            continue
        if a.startswith("--supervise="):
            continue
        out.append(a)
    return out


def _supervise(budget: int, argv) -> int:
    """The restart loop: run the serve CLI as a subprocess; respawn on
    abnormal death (a signal, or an exit code outside the CLI's
    contract) with exponential backoff, at most ``budget`` times.
    Clean exits pass through: 0 (all ok), 1 (some requests non-ok — a
    deterministic outcome a restart would only repeat), and 75
    (EXIT_PREEMPTED: the drain protocol already ran)."""
    import subprocess
    from distributed_compute_pytorch_tpu.train.elastic import (
        EXIT_PREEMPTED, backoff_delays)
    child = _strip_supervise(list(sys.argv[1:] if argv is None else argv))
    cmd = [sys.executable, "-m",
           "distributed_compute_pytorch_tpu.cli_serve", *child]
    delays = backoff_delays(max(1, budget), 1.0)
    restarts = 0
    while True:
        rc = subprocess.call(cmd)
        if rc in (0, 1, EXIT_PREEMPTED):
            return rc
        if restarts >= budget:
            print(f"dcp-serve supervisor: restart budget ({budget}) "
                  f"exhausted; giving up (last rc {rc})",
                  file=sys.stderr, flush=True)
            return rc if rc > 0 else 1
        delay = delays[min(restarts, len(delays) - 1)]
        restarts += 1
        print(f"dcp-serve supervisor: serve process died (rc {rc}); "
              f"restart {restarts}/{budget} in {delay:.2f}s",
              file=sys.stderr, flush=True)
        time.sleep(delay)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--ckpt_path", required=True)
    p.add_argument("--model", default="gpt2",
                   choices=("gpt2", "llama", "moe"))
    p.add_argument("--model_preset", default=None)
    p.add_argument("--vocab_size", type=int, default=None)
    p.add_argument("--max_seq_len", type=int, default=None)
    p.add_argument("--requests", required=True,
                   help="request file ('-' = stdin), one request per "
                        "line (see module docstring for formats)")
    p.add_argument("--slots", type=int, default=8,
                   help="cache rows decoding concurrently (per replica)")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a replica set: N independent "
                        "batcher replicas behind the health-checked "
                        "router (serve_router.ServeRouter) — radix-"
                        "affinity + least-loaded dispatch, circuit "
                        "breakers, and failover-by-migration when a "
                        "replica dies. 1 (default) = the single-batcher "
                        "path, unchanged")
    p.add_argument("--fault_replica", type=int, default=0,
                   help="with --replicas > 1, which replica the "
                        "injected --fault_at_segment chaos targets "
                        "(drills failover-by-migration)")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="elastic fleet (ISSUE 20): wrap the replica "
                        "set in serve_fleet.ElasticFleetController and "
                        "let load scale it between MIN and MAX "
                        "replicas. Requests are served in windows with "
                        "a control step between: queue depth + SLO "
                        "burn feed a hysteresis/cooldown decider, "
                        "scale-ups come up warm off the shared "
                        "compiled-program cache, scale-downs drain by "
                        "migration (token-identical on survivors), "
                        "and breaker-DEAD replicas are replaced. "
                        "--replicas sets the starting size")
    p.add_argument("--elastic_window", type=int, default=8,
                   help="with --autoscale/--upgrade_to: requests per "
                        "serving window (the control-loop period)")
    p.add_argument("--upgrade_to", default=None, metavar="CKPT",
                   help="rolling weight upgrade (ISSUE 20): after the "
                        "first serving window, walk the fleet one "
                        "replica at a time — drain by migration, "
                        "reload weights from CKPT in place (compiled "
                        "programs survive), re-admit — with zero "
                        "dropped requests. Bumps the fleet's weights "
                        "version; the version stamp keeps old-version "
                        "KV prefixes off the new weights")
    p.add_argument("--weights_version", type=int, default=0,
                   help="version stamp for the served weights (ISSUE "
                        "20): threads through radix entries, tier "
                        "sidecars, handoff payloads and the journal "
                        "config frame so cross-version KV reuse "
                        "declines to token replay. A journaled run "
                        "recovered under a different version warns "
                        "and replays incomplete sessions from tokens "
                        "(completed ids still dedup)")
    p.add_argument("--prefill_chunk_tokens", type=int, default=None,
                   help="chunked prefill: cap each admission wave's "
                        "prefill at N prompt tokens (rounded up to a "
                        "KV-block multiple); longer prompts admit "
                        "their first chunk and extend chunk-by-chunk "
                        "between decode segments, so one long prompt "
                        "never stalls live decode rows for a whole "
                        "prefill. Outputs stay token-identical (greedy "
                        "AND sampled). Default: unchunked; not "
                        "supported for --model moe")
    p.add_argument("--prefill_replicas", type=int, default=0,
                   help="with --replicas > 1: dedicate the first K "
                        "replicas to prompt prefill (disaggregated "
                        "serving). Sessions prefill there, then hop to "
                        "a decode replica — the finished KV blocks are "
                        "handed over through the host tier instead of "
                        "being re-prefilled (falls back to token-"
                        "identical replay on any miss). Requires "
                        "--prefix_cache and at least one decode "
                        "replica. 0 (default) = unified replicas")
    p.add_argument("--t_max", type=int, default=None,
                   help="cache length == total tick horizon (default: "
                        "sized from the workload)")
    p.add_argument("--prompt_buf", type=int, default=None,
                   help="static prompt window (default: longest prompt)")
    p.add_argument("--segment", type=int, default=16,
                   help="decode ticks per compiled segment")
    p.add_argument("--max_new_tokens", type=int, default=32,
                   help="budget for requests that don't carry max_new")
    p.add_argument("--eos_id", type=int, default=None)
    p.add_argument("--tokenizer", default=None,
                   help="'byte' or a tokenizer .json: serve TEXT lines "
                        "and decode outputs back to text")
    p.add_argument("--quantize", default=None, choices=("int8",),
                   help="weight-only int8 serving")
    p.add_argument("--kv_dtype", default="bf16", choices=("bf16", "int8"),
                   help="KV-cache pool storage dtype (ISSUE 16). 'bf16' "
                        "means UNQUANTIZED: blocks are stored in the "
                        "dtype the checkpoint's parameters were saved "
                        "in (dcp-train --param_dtype) — bfloat16 for a "
                        "bfloat16 checkpoint, float32 for a float32 "
                        "one. int8 "
                        "stores each block as int8 with per-row f32 "
                        "scales — roughly half the HBM/host/disk/"
                        "handoff bytes per cached token, so ~1.9x the "
                        "resident prefix tokens per byte. Outputs are "
                        "NOT bit-identical to bf16 (bounded logit "
                        "error; >=99% greedy match on the bench "
                        "streams — see DESIGN.md 'Quantized KV'). "
                        "Replicas inherit; a journaled run refuses to "
                        "recover under a different kv_dtype")
    p.add_argument("--decode_width_buckets", type=int, default=None,
                   help="width-bucket ladder depth (ISSUE 19): decode/"
                        "verify dispatches slice the block tables to "
                        "the smallest power-of-two rung covering the "
                        "live working set, so per-tick KV gather "
                        "traffic tracks live tokens instead of t_max. "
                        "Default: the full ladder; N keeps only the "
                        "widest N rungs (1 = a single full-horizon "
                        "bucket, i.e. bucketing off). Outputs are "
                        "token-identical at any setting")
    p.add_argument("--prewarm_widths", action="store_true",
                   help="compile every width-bucket rung's decode "
                        "program at startup (and again after each "
                        "--supervise respawn, which re-runs this "
                        "entrypoint), so the first long session never "
                        "eats a mid-traffic XLA compile when its "
                        "bucket grows; counted in "
                        "serve.width.prewarmed_programs")
    p.add_argument("--mesh", default=None,
                   help="mesh spec for SHARDED serving (e.g. "
                        "data=2,tensor=2): cache rows shard over the "
                        "batch axes, kv heads over tensor; --slots must "
                        "be a multiple of the batch-axis product")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="default sampling temperature (0 = greedy); "
                        "JSON requests may override per request")
    p.add_argument("--top_k", type=int, default=None,
                   help="default top-k truncation (needs temperature>0)")
    p.add_argument("--top_p", type=float, default=None,
                   help="default nucleus truncation (needs temperature>0)")
    p.add_argument("--seed", type=int, default=None,
                   help="base sampling seed; request i uses seed+i "
                        "(default: i) so the whole file is deterministic")
    p.add_argument("--prefix_cache", action="store_true",
                   help="radix prefix caching over the paged KV pool: "
                        "requests sharing a prompt prefix (system "
                        "prompts) attach to already-prefilled blocks "
                        "copy-on-write instead of re-running prefill; "
                        "outputs stay token-identical. Each output "
                        "line reports its 'cached_prefix' length. Not "
                        "supported for --model moe (routing is "
                        "group-dependent)")
    p.add_argument("--speculate", type=int, default=0,
                   help="speculative decoding: draft K tokens per "
                        "verify step with the self-drafting n-gram "
                        "proposer and score all K+1 positions in one "
                        "forward pass — outputs stay token-identical "
                        "(the accept/reject rule is exact; greedy AND "
                        "sampled), only throughput moves. Replicas "
                        "inherit the setting. Sustained low acceptance "
                        "auto-disables back to plain decode. 0 (default) "
                        "= off; not supported for --model moe")
    p.add_argument("--kv_block_tokens", type=int, default=None,
                   help="logical tokens per KV-pool block (default: "
                        "the Pallas cache window; rounded up to a "
                        "window multiple). Smaller blocks share "
                        "prefixes at a finer grain")
    p.add_argument("--host_cache_mb", type=float, default=None,
                   help="hierarchical KV (kv_tier.py): host-RAM spill "
                        "tier of this many MB under the radix prefix "
                        "cache. Evicted refcount-0 prefixes demote D2H "
                        "instead of being discarded and promote back "
                        "with one async H2D copy on the next hit — the "
                        "prefix cache outlives HBM. Outputs stay "
                        "token-identical. Requires --prefix_cache; "
                        "with --replicas the budget is PER REPLICA "
                        "(each owns its own host pool — one process, "
                        "one failure domain)")
    p.add_argument("--disk_cache_dir", type=str, default=None,
                   help="optional third tier below --host_cache_mb: "
                        "host-LRU prefixes spill to CRC-verified "
                        "part-NNNNN.npz entries (the v2 shard entry "
                        "format) in this directory; a corrupt part "
                        "degrades to a cache miss, never a failure. "
                        "Replicas spill into replica-N/ subdirectories")
    p.add_argument("--admit_policy", default="fifo",
                   choices=("fifo", "skip_fit"),
                   help="admission order: strict FIFO (fairness: no "
                        "request is leapfrogged) or skip-fit (a free row "
                        "takes the first queued request that fits)")
    # --- crash durability (serve_journal.py; module docstring) ---
    p.add_argument("--journal_dir", type=str, default=None,
                   help="crash-durable serving: append-only CRC-framed "
                        "write-ahead session journal in this directory. "
                        "Admissions are logged before any device work, "
                        "harvested tokens per segment, terminal status "
                        "at completion; restarting with the same dir "
                        "and request file dedups completed requests "
                        "and resumes incomplete sessions token-"
                        "identically (greedy AND sampled)")
    p.add_argument("--journal_fsync", default="every_harvest",
                   choices=("every_frame", "every_harvest", "os"),
                   help="journal durability price: fsync per frame "
                        "(power-loss safe, slowest), per harvest "
                        "boundary (default), or never — flush to the "
                        "OS page cache only, which still survives any "
                        "process death (SIGKILL/OOM), just not power "
                        "loss")
    p.add_argument("--supervise", type=int, default=0,
                   help="run the serve loop as a supervised subprocess: "
                        "respawn it (exponential backoff via "
                        "elastic.backoff_delays) when it dies "
                        "abnormally, at most N restarts; clean exits "
                        "(0, 1, 75/preempted) pass through. Requires "
                        "--journal_dir so restarts recover sessions "
                        "instead of redoing them. 0 (default) = off")
    # --- fault tolerance (serve_detailed; module docstring) ---
    p.add_argument("--max_pending", type=int, default=None,
                   help="bounded admission: accept at most slots + N "
                        "requests, shed the rest at submission with "
                        "zero device work (default: unbounded)")
    p.add_argument("--request_deadline", type=float, default=None,
                   help="default per-request wall-clock deadline in "
                        "seconds (JSON requests may override with "
                        "'deadline'); expired requests return their "
                        "partial stream with status 'timeout'")
    p.add_argument("--drain_deadline", type=float, default=30.0,
                   help="graceful-drain budget after SIGTERM/SIGINT: "
                        "in-flight rows get this many seconds to "
                        "finish before returning partial streams")
    p.add_argument("--tick_timeout", type=float, default=None,
                   help="tick watchdog: seconds a segment's token "
                        "harvest may block before the device is "
                        "declared hung and the session reconstructed "
                        "(default: no watchdog)")
    p.add_argument("--max_recoveries", type=int, default=2,
                   help="session reconstructions to attempt per run "
                        "before failing the remaining requests")
    p.add_argument("--fault_at_segment", type=int, default=None,
                   help="fault injection (testing): trip --fault_mode "
                        "at the Nth dispatched segment")
    p.add_argument("--fault_mode", default="raise",
                   choices=("raise", "hang", "slow", "poison"),
                   help="injected fault flavour (serve_lifecycle."
                        "ChaosInjector); 'poison' needs "
                        "--poison_request")
    p.add_argument("--poison_request", type=int, default=None,
                   help="request index that deterministically poisons "
                        "its row (with --fault_mode poison)")
    # --- observability (ISSUE 8, obs/; "Observability" in DESIGN.md) ---
    p.add_argument("--heartbeat", type=float, default=10.0,
                   help="seconds between heartbeat lines on stderr: one "
                        "JSON stats_snapshot() per interval (queue "
                        "depth, SLO percentiles, waste counters) while "
                        "the serve loop runs; 0 disables")
    p.add_argument("--metrics_jsonl", type=str, default=None,
                   help="append heartbeat snapshots and the final "
                        "stats_snapshot() to this JSONL file")
    p.add_argument("--trace_path", type=str, default=None,
                   help="write a Chrome-trace JSON of host-side spans "
                        "(admit/dispatch/harvest/reconstruct) here at "
                        "exit; load in Perfetto")
    p.add_argument("--flight_recorder", type=str, default=None,
                   help="record scheduler events in a bounded ring and "
                        "dump them as JSON to this path on any failure "
                        "(watchdog timeout, reconstruction, poison "
                        "eviction, SIGTERM drain, crash; obs/flight.py)")
    p.add_argument("--profile_dir", type=str, default=None,
                   help="XLA profiler traces: alone, profiles the whole "
                        "serve run (utils.timing.maybe_profile); with "
                        "--profile_segments, arms on-demand profiling "
                        "instead")
    p.add_argument("--profile_segments", type=int, default=None,
                   help="profile the next N dispatched segments into "
                        "--profile_dir, starting now; SIGUSR1 re-arms "
                        "the same window on demand mid-run")
    p.add_argument("--force-cpu", action="store_true", dest="force_cpu")
    args = p.parse_args(argv)

    if args.max_new_tokens < 1:
        raise SystemExit("--max_new_tokens must be >= 1")
    if args.profile_segments is not None and args.profile_dir is None:
        raise SystemExit("--profile_segments needs --profile_dir")
    if args.temperature == 0.0 and (args.top_k is not None
                                    or args.top_p is not None):
        raise SystemExit("--top_k/--top_p require --temperature > 0")
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.weights_version < 0:
        raise SystemExit("--weights_version must be >= 0")
    if args.elastic_window < 1:
        raise SystemExit("--elastic_window must be >= 1")
    autoscale = None
    if args.autoscale is not None:
        try:
            lo, _, hi = args.autoscale.partition(":")
            autoscale = (int(lo), int(hi))
        except ValueError:
            raise SystemExit(f"--autoscale wants MIN:MAX, got "
                             f"{args.autoscale!r}")
        if not 1 <= autoscale[0] <= autoscale[1]:
            raise SystemExit(f"--autoscale needs 1 <= MIN <= MAX, got "
                             f"{args.autoscale!r}")
    elastic = args.autoscale is not None or args.upgrade_to is not None
    if elastic and args.profile_segments is not None:
        raise SystemExit("--profile_segments profiles one fixed "
                         "batcher; not supported with --autoscale/"
                         "--upgrade_to")
    if elastic and args.mesh is not None:
        raise SystemExit("--autoscale/--upgrade_to build replicas "
                         "dynamically: not supported with --mesh (one "
                         "process drives one device set)")
    if args.replicas > 1 and args.mesh is not None:
        raise SystemExit("--replicas > 1 with --mesh is not supported "
                         "from this CLI: each replica would need its own "
                         "mesh (one process drives one device set); run "
                         "replicated-sharded serving programmatically "
                         "via serve_router.ServeRouter")
    if args.replicas > 1 and args.profile_segments is not None:
        raise SystemExit("--profile_segments profiles one batcher; "
                         "not supported with --replicas > 1")
    if args.host_cache_mb is not None and not args.prefix_cache:
        raise SystemExit("--host_cache_mb spills the radix prefix "
                         "cache: it requires --prefix_cache")
    if args.host_cache_mb is not None and args.host_cache_mb <= 0:
        raise SystemExit("--host_cache_mb must be > 0")
    if args.disk_cache_dir is not None and args.host_cache_mb is None:
        raise SystemExit("--disk_cache_dir is the tier below host RAM: "
                         "it requires --host_cache_mb")
    if not 0 <= args.fault_replica < args.replicas:
        raise SystemExit(f"--fault_replica {args.fault_replica} outside "
                         f"[0, {args.replicas})")
    if args.prefill_chunk_tokens is not None \
            and args.prefill_chunk_tokens < 1:
        raise SystemExit("--prefill_chunk_tokens must be >= 1")
    if args.decode_width_buckets is not None \
            and args.decode_width_buckets < 1:
        raise SystemExit("--decode_width_buckets must be >= 1 "
                         "(1 = a single full-horizon bucket)")
    if args.prefill_chunk_tokens is not None and args.model == "moe":
        raise SystemExit("--prefill_chunk_tokens is not supported for "
                         "--model moe (expert routing is group-"
                         "dependent, so a chunked prefill would not be "
                         "token-identical)")
    if args.prefill_replicas:
        if not 0 <= args.prefill_replicas < args.replicas:
            raise SystemExit(f"--prefill_replicas {args.prefill_replicas} "
                             f"outside [0, {args.replicas}): at least "
                             f"one decode replica must remain")
        if not args.prefix_cache:
            raise SystemExit("--prefill_replicas hands finished KV "
                             "blocks over through the radix cache: it "
                             "requires --prefix_cache")
    if args.supervise < 0:
        raise SystemExit("--supervise must be >= 0")
    if args.supervise and args.journal_dir is None:
        raise SystemExit("--supervise without --journal_dir would redo "
                         "completed work on every restart; give the "
                         "supervisor a journal to recover from")
    if args.supervise:
        # supervisor mode: the actual serving (heavy imports, compile,
        # checkpoint load) happens in a child process this parent
        # respawns on abnormal death — before any signal handlers or
        # device state exist in the parent
        return _supervise(args.supervise, argv)
    # crash durability, step 1: recover BEFORE the heavy imports and
    # checkpoint load — a config mismatch against the journaled run
    # (kv_dtype: the recorded streams are promises another pool dtype
    # cannot keep) must refuse in one line, not after a full compile
    recovery = None
    if args.journal_dir:
        from distributed_compute_pytorch_tpu import serve_journal
        recovery = serve_journal.recover(args.journal_dir)
        jc = recovery.config or {}
        # a fresh/empty journal has nothing to mismatch; a non-empty
        # one without a config frame is a pre-config-frame journal,
        # which only a bf16 engine could have written
        if recovery.frames and jc.get("kv_dtype", "bf16") != args.kv_dtype:
            raise SystemExit(
                f"--journal_dir was written with kv_dtype="
                f"{jc.get('kv_dtype', 'bf16')}, refusing to recover "
                f"with --kv_dtype {args.kv_dtype}")
        # a weights-version mismatch is SAFE to recover across (unlike
        # kv_dtype): completed ids still dedup, and incomplete sessions
        # replay from their journaled tokens — token replay never
        # touches old-version KV. One line so the operator knows the
        # push happened between crash and restart.
        jwv = recovery.weights_version
        if (recovery.frames and jwv is not None
                and jwv != args.weights_version):
            print(f"warning: journal was written at weights_version="
                  f"{jwv}, recovering under {args.weights_version}: "
                  f"incomplete sessions replay from tokens (no "
                  f"cross-version KV reuse)", file=sys.stderr,
                  flush=True)
    # SIGTERM/SIGINT -> graceful drain, armed BEFORE the heavy imports /
    # checkpoint load / compiles so a preemption at ANY point of startup
    # drains instead of dying mid-load (the trainer's PreemptionGuard,
    # reused: first signal latches the flag, a second one kills)
    from distributed_compute_pytorch_tpu.train.elastic import (
        EXIT_PREEMPTED, PreemptionGuard)
    guard = PreemptionGuard()
    guard.__enter__()
    import jax
    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    from distributed_compute_pytorch_tpu.cli_generate import (
        check_eos, check_tokenizer_vocab, load_model_and_params)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.utils.compilation_cache import (
        enable as enable_compile_cache)
    from distributed_compute_pytorch_tpu.utils.logging import device_banner
    enable_compile_cache()
    # stderr (stdout carries the result lines): a server that landed on
    # the CPU because the accelerator runtime failed to load says so first
    print(f"dcp-serve | {device_banner()}", file=sys.stderr, flush=True)

    model, params, mesh = load_model_and_params(
        args.model, args.model_preset, args.vocab_size, args.max_seq_len,
        args.ckpt_path, mesh_spec=args.mesh, quantize=args.quantize)

    tok = None
    if args.tokenizer is not None:
        from distributed_compute_pytorch_tpu.data.tokenizer import (
            build_tokenizer)
        tok = build_tokenizer(args.tokenizer)
        check_tokenizer_vocab(tok, model)
        if args.eos_id is None:
            args.eos_id = tok.eos_id
    defaults = {"temperature": args.temperature, "top_k": args.top_k,
                "top_p": args.top_p, "seed": None,
                "deadline": args.request_deadline, "id": None}
    reqs = _read_requests(args.requests, tok, args.max_new_tokens,
                          defaults)
    # stable session identities: explicit JSON "id" wins, otherwise the
    # line position — DETERMINISTIC across restarts, which is what lets
    # a rerun of the same request file dedup against the journal
    seen_ids: set[str] = set()
    for i, r in enumerate(reqs):
        rid = r["id"] if r["id"] is not None else f"req-{i:05d}"
        if rid in seen_ids:
            raise SystemExit(f"duplicate request id {rid!r}: journal "
                             f"recovery dedups by id, so ids must be "
                             f"unique per run")
        seen_ids.add(rid)
        r["id"] = rid

    vocab = model.config.vocab_size
    bad = [t for r in reqs for t in r["tokens"] if not 0 <= t < vocab]
    if bad:
        raise SystemExit(f"prompt ids {bad[:8]} outside vocab [0, {vocab})")
    check_eos(args.eos_id, vocab)

    cap = getattr(model.config, "max_seq_len", None)
    if cap is not None:
        over = [r for r in reqs if len(r["tokens"]) + r["max_new"] > cap]
        if over:
            raise SystemExit(
                f"{len(over)} request(s) exceed the model's "
                f"max_seq_len={cap} (prompt+max_new); shrink them")
    prompt_buf = args.prompt_buf or max(len(r["tokens"]) for r in reqs)
    if args.t_max is None:
        # horizon: positions are PER ROW (rows recycle in place), so
        # t_max only needs to bound the single largest request — the
        # prompt window plus its segment-rounded budget — not the whole
        # stream's tick total. The batcher rounds up to the Pallas
        # cache-window multiple itself. The slot horizon may
        # legitimately exceed the model's max_seq_len — only each row's
        # LOGICAL positions are capacity-bound (checked above).
        S = args.segment
        t_max = prompt_buf + max(-(-r["max_new"] // S) * S for r in reqs)
    else:
        t_max = args.t_max
    from distributed_compute_pytorch_tpu.obs.tracing import (
        Tracer, configure_tracer)
    tracer = Tracer() if args.trace_path else None
    if tracer is not None:
        configure_tracer(tracer)
    from distributed_compute_pytorch_tpu.obs import flight
    if args.flight_recorder:
        flight.configure_flight(
            flight.FlightRecorder(path=args.flight_recorder))
        flight.install_crash_hook()
    metrics_f = open(args.metrics_jsonl, "a") if args.metrics_jsonl else None

    def on_heartbeat(snap, replica=None):
        rec = {"kind": "serve_heartbeat", "ts": time.time()}
        if replica is not None:
            rec["replica"] = replica
        line = json.dumps({**rec, **snap})
        print(line, file=sys.stderr, flush=True)
        if metrics_f is not None:
            metrics_f.write(line + "\n")
            metrics_f.flush()

    # crash durability, step 2: the manifest was recovered (and its
    # config validated) up top, before the checkpoint load; open the
    # writer now — both ends repair a torn tail, so either order finds
    # a clean log. One shared writer for every replica: frames
    # interleave, recovery keys by id.
    journal = None
    if args.journal_dir:
        from distributed_compute_pytorch_tpu import serve_journal
        if recovery.sessions:
            print(json.dumps({
                "kind": "serve_recovery", "ts": time.time(),
                "sessions": len(recovery.sessions),
                "completed": len(recovery.completed),
                "incomplete": len(recovery.incomplete),
                "torn_bytes": recovery.torn_bytes}),
                file=sys.stderr, flush=True)
        journal = serve_journal.ServeJournal(args.journal_dir,
                                             fsync=args.journal_fsync)
        # stamp this process's config so the NEXT restart can refuse a
        # mismatched --kv_dtype before touching any session
        journal.config({"kv_dtype": args.kv_dtype,
                        "weights_version": args.weights_version})

    def build_batcher(rep_params, replica=None, weights_version=None):
        hb_cb = None
        if args.heartbeat:
            hb_cb = (on_heartbeat if replica is None else
                     (lambda snap, _r=replica: on_heartbeat(snap, _r)))
        disk_dir = args.disk_cache_dir
        if disk_dir is not None and replica is not None:
            # one failure domain per replica: separate spill directories
            disk_dir = os.path.join(disk_dir, f"replica-{replica}")
        if replica is not None:
            # replica i lives on local device i (round-robin when
            # replicas outnumber chips): the engine keeps its pool, row
            # state and programs wherever its parameters are. The copy
            # made here (from the host) is the engine's alone
            devs = jax.local_devices()
            rep_params = jax.device_put(rep_params,
                                        devs[replica % len(devs)])
        return ContinuousBatcher(
            model,
            rep_params,
            slots=args.slots, t_max=t_max,
            prompt_buf=prompt_buf, segment=args.segment,
            eos_id=args.eos_id, mesh=mesh,
            admit_policy=args.admit_policy,
            max_pending=args.max_pending,
            tick_timeout_s=args.tick_timeout,
            max_recoveries=args.max_recoveries,
            kv_block_tokens=args.kv_block_tokens,
            prefix_cache=args.prefix_cache,
            host_cache_mb=args.host_cache_mb,
            disk_cache_dir=disk_dir,
            heartbeat_s=args.heartbeat or None,
            on_heartbeat=hb_cb,
            speculate=args.speculate or None,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            journal=journal,
            kv_dtype=args.kv_dtype,
            decode_width_buckets=args.decode_width_buckets,
            weights_version=(args.weights_version
                             if weights_version is None
                             else weights_version))

    # ONE copy of the weights a device. An engine cuts the stacked tree
    # it is handed into its own per-layer form at its first dispatch and
    # lets go of each stacked leaf as it goes (ContinuousBatcher.
    # _cut_weights), so no stacked tree may outlive the hand-over on an
    # engine's device. A fleet's copy (later replicas and the upgrade
    # walk build from it) waits on the HOST, and every replica takes a
    # copy of its own to its device; a single engine takes the restored
    # tree itself and this function drops its reference.
    router = None
    if args.replicas > 1 or elastic:
        from distributed_compute_pytorch_tpu.serve_router import ServeRouter
        params = jax.device_get(params)
        router = ServeRouter([build_batcher(params, i)
                              for i in range(args.replicas)],
                             prefill_replicas=args.prefill_replicas)
        cb = router.replicas[0]        # profile/SIGUSR1 target
    else:
        cb = build_batcher(params)
        params = None

    controller = None
    upgrade_to = None
    if elastic:
        from distributed_compute_pytorch_tpu.serve_fleet import (
            ElasticFleetController, ScalePolicy)
        lo, hi = autoscale if autoscale else (args.replicas,
                                              args.replicas)
        controller = ElasticFleetController(
            router,
            lambda p, wv, slot: build_batcher(p, slot,
                                              weights_version=wv),
            params=params, weights_version=args.weights_version,
            policy=ScalePolicy(min_replicas=lo, max_replicas=hi))
        if args.upgrade_to:
            # the new weights load through the same checkpoint-restore
            # path as the serving set and wait on the host beside it; the
            # rolling walk pushes them after the first window
            upgrade_to = (jax.device_get(load_model_and_params(
                args.model, args.model_preset, args.vocab_size,
                args.max_seq_len, args.upgrade_to, mesh_spec=args.mesh,
                quantize=args.quantize)[1]), args.weights_version + 1)

    if args.prewarm_widths:
        # one batcher warms the fleet: replicas share compiled programs
        # through the _PROGRAM_CACHE donor, so each ladder rung compiles
        # exactly once. A --supervise respawn re-enters this entrypoint
        # and prewarms again — the restarted process's jit cache is cold
        cb.prewarm_widths(sampling=args.temperature > 0)

    if args.profile_segments is not None:
        # on-demand window (first N segments now; SIGUSR1 re-arms). The
        # whole-run maybe_profile below stays off in this mode — the two
        # would fight over one jax.profiler trace session.
        import signal
        cb.profile_next(args.profile_segments, args.profile_dir)
        signal.signal(
            signal.SIGUSR1,
            lambda *_: cb.profile_next(args.profile_segments,
                                       args.profile_dir))

    def req_seed(i, r):
        if r["seed"] is not None:
            return r["seed"]
        return None if args.seed is None else args.seed + i

    chaos = None
    if args.fault_at_segment is not None or args.poison_request is not None:
        from distributed_compute_pytorch_tpu.serve_lifecycle import (
            ChaosInjector)
        chaos = ChaosInjector(fault_at_segment=args.fault_at_segment,
                              fault_mode=args.fault_mode,
                              poison_request=args.poison_request)

    from distributed_compute_pytorch_tpu.utils.timing import maybe_profile
    whole_run_profile = (args.profile_dir
                         if args.profile_segments is None else None)
    try:
        with maybe_profile(whole_run_profile):
            try:
                requests = [Request(list(r["tokens"]), r["max_new"],
                                    temperature=r["temperature"],
                                    top_k=r["top_k"],
                                    top_p=r["top_p"], seed=req_seed(i, r),
                                    deadline_s=r["deadline"],
                                    request_id=r["id"])
                            for i, r in enumerate(reqs)]
                if controller is not None:
                    results = controller.serve_stream(
                        requests, window=args.elastic_window,
                        drain=guard,
                        drain_deadline_s=args.drain_deadline,
                        chaos=({args.fault_replica: chaos}
                               if chaos is not None else None),
                        recovery=recovery, upgrade_to=upgrade_to)
                elif router is not None:
                    results = router.route(
                        requests, drain=guard,
                        drain_deadline_s=args.drain_deadline,
                        chaos=({args.fault_replica: chaos}
                               if chaos is not None else None),
                        recovery=recovery)
                else:
                    results = cb.serve_detailed(
                        requests, drain=guard,
                        drain_deadline_s=args.drain_deadline, chaos=chaos,
                        recovery=recovery)
            finally:
                guard.__exit__()
    finally:
        # telemetry flushes on EVERY exit path (drain, fault, Ctrl-C x2)
        if metrics_f is not None:
            snap = (controller.stats_snapshot()
                    if controller is not None
                    else router.stats_snapshot() if router is not None
                    else cb.stats_snapshot())
            metrics_f.write(json.dumps({"kind": "serve_final",
                                        "ts": time.time(),
                                        **snap}) + "\n")
            # which Pallas kernels the compiled programs carry, and where
            # each engine ran: one record per batcher, after serving
            for i, b in enumerate(router.replicas if router is not None
                                  else [cb]):
                rec = {"kind": "serve_kernels", "ts": time.time(),
                       "replica": i if router is not None else None,
                       "engine": b.engine_info(),
                       "stats": dict(b.stats)}
                try:
                    rec["programs"] = b.kernel_census()
                except Exception as e:  # noqa: BLE001 — telemetry on an
                    # exit path: record the failure, keep the real error
                    rec["error"] = f"{type(e).__name__}: {e}"
                metrics_f.write(json.dumps(rec) + "\n")
            metrics_f.close()
        if journal is not None:
            journal.close()
        if tracer is not None:
            configure_tracer(None)
            tracer.dump(args.trace_path)
    for r, res in zip(reqs, results):
        rec = {"id": r["id"], "prompt": r["tokens"], "new": res.tokens,
               "status": res.status,
               "cached_prefix": res.cached_prefix_tokens}
        if router is not None:
            rec["replica"] = res.replica
            rec["migrated"] = res.migrated
        if res.error is not None:
            rec["error"] = res.error
        if tok is not None:
            rec["text"] = tok.decode(res.tokens)
        print(json.dumps(rec))
    if guard.preempted:
        return EXIT_PREEMPTED
    return 0 if all(r.ok for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
