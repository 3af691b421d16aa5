"""Runner for traffic of kind ``open_loop`` and ``backlog``: drives the
program's ``ContinuousBatcher`` through ``serve_detailed`` with requests
made by ``perfbench.trafficgen``, and afterwards runs the plain reference
teacher-forced over a seeded sample of the requests the window finished.

Order of a run: weights from the seed on the device -> the batcher (pool)
-> warm-up of exactly the programs the window can use (every decode width
rung, every admission-wave size up to ``warm_waves``) -> ``reset()`` ->
the measured window: ONE ``serve_detailed`` call on the same batcher ->
the batcher and its weights are freed -> the reference over the sample."""

from __future__ import annotations

import collections
import gc
import math
import time

import numpy as np

from perfbench import check as chk
from perfbench import families, tracewin, trafficgen, weights
from perfbench.percentiles import percentile


class _CloseAt:
    """The ``drain`` object of a backlog window: ``preempted`` turns true
    when the window's seconds are up."""

    def __init__(self, seconds: float):
        self.deadline = None
        self.seconds = seconds

    def start(self):
        self.deadline = time.monotonic() + self.seconds

    @property
    def preempted(self) -> bool:
        return self.deadline is not None and time.monotonic() >= self.deadline


def jax_block(tree):
    import jax
    jax.block_until_ready(tree)


def build(env, device=None):
    """Weights from the seed in the served type, and the program's model."""
    import jax
    cfg, run_kw = env.config, env.cell["run"]
    ref = families.reference_module(cfg)
    model = families.build_program_model(
        cfg, dict(run_kw, max_seq_len=run_kw["t_max"]))
    spec = ref.param_spec(cfg)
    dtypes = ref.param_dtypes(cfg, run_kw["param_dtype"])
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    want = jax.tree.map(lambda s, d: (s[0], d), spec, dtypes,
                        is_leaf=weights._is_leaf)
    if have != want:
        raise SystemExit(f"the program's parameter tree differs from the "
                         f"reference's spec:\n{have}\n{want}")
    params = weights.make_params(spec, env.seed, dtypes,
                                 device=device or jax.devices()[0])
    return model, params, ref, spec, dtypes


def sample_for_check(reqs, results, seed, min_served=400, min_requests=3,
                     max_requests=6):
    """Finished requests drawn from the seed, the longest first: at least
    ``min_requests`` of them and ``min_served`` served tokens."""
    done = [i for i, r in enumerate(results)
            if r.status == "ok" and len(r.tokens) >= 1]
    if not done:
        return []
    longest = max(done, key=lambda i: len(reqs[i]["tokens"]) + len(results[i].tokens))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 3])
    rest = [i for i in rng.permutation(done) if i != longest]
    pick, served = [longest], len(results[longest].tokens)
    for i in rest:
        if ((served >= min_served and len(pick) >= min_requests)
                or len(pick) >= max_requests):
            break
        pick.append(int(i))
        served += len(results[i].tokens)
    return pick


def reference_gaps(env, ref, spec, dtypes, reqs, results, pick, control=()):
    """Worst and mean gap of the served tokens of the picked requests."""
    import jax
    params = weights.make_params(spec, env.seed, dtypes,
                                 device=jax.devices()[0])
    out = {k: [] for k in ("served",) + tuple(control)}
    for i in pick:
        g = ref.served_token_gaps(params, reqs[i]["tokens"],
                                  results[i].tokens, env.config,
                                  pad_to=env.traffic.get("reference_pad_to", 512),
                                  control=control)
        for k in g:
            out[k].extend(float(x) for x in g[k])
    del params
    return out


def setup(env):
    """Weights, batcher and warm-up: every decode width rung, every
    admission-wave size up to ``warm_waves``; then a fresh session."""
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    run_kw = env.cell["run"]
    t_a = time.monotonic()
    model, params, ref, spec, dtypes = build(env)
    jax_block(params)
    t_b = time.monotonic()
    cb = ContinuousBatcher(
        model, params, slots=run_kw["slots"], t_max=run_kw["t_max"],
        prompt_buf=run_kw["prompt_buf"], kv_dtype=run_kw.get("kv_dtype", "bf16"))
    del params
    # one call per admission-wave size; the k-th call's longest prompt is
    # sized to land its decode segment on the k-th width rung (a segment
    # needs row_pos + S + 1 = len(prompt) + S - 1 slots), so every rung the
    # traffic's lengths can reach is compiled here too. (The program's own
    # prewarm_widths() cannot be used at this size: it re-zeroes the pool
    # by making a second one beside the first.)
    rungs, w = [], 1
    while w < cb.nb:
        rungs.append(w)
        w *= 2
    rungs.append(cb.nb)
    prompts = [min(max(cb.bt * w - (cb.S - 1), 2), run_kw["prompt_buf"])
               for w in rungs if cb.bt * w >= cb.S + 1]
    waves = max(run_kw["warm_waves"], len(prompts))
    t_c, per_wave = time.monotonic(), []
    for k in range(1, waves + 1):
        t_w = time.monotonic()
        longest = prompts[k - 1] if k <= len(prompts) else 3
        warm = [Request(tokens=[1 + (j + t) % 7 for t in range(
                            longest if j == 0 else 3)], max_new=4)
                for j in range(min(k, run_kw["warm_waves"]))]
        res = cb.serve_detailed(warm)
        if any(r.status != "ok" for r in res):
            raise SystemExit(f"warm-up wave of {k} failed: "
                             f"{[r.error for r in res if r.status != 'ok']}")
        per_wave.append(round(time.monotonic() - t_w, 2))
    print(f"INFO set-up phases: weights {t_b - t_a:.1f} s, batcher "
          f"{t_c - t_b:.1f} s, warm-up waves {per_wave}", flush=True)
    if cb.stats["faults"]:
        raise SystemExit("a device fault during warm-up")
    # no cb.reset() here: it too re-zeroes the pool by making a second one
    # beside the first, which does not fit at this size. A served call
    # leaves every block free, so the next call starts clean; the window's
    # counters are read as differences (``counters_now``).
    return cb, ref, spec, dtypes


def counters_now(cb) -> dict:
    """The program's running counters, to be differenced over a window."""
    snap = cb.stats_snapshot()
    out = {k: v for k, v in snap["stats"].items()
           if isinstance(v, (int, float))}
    out["ticks"] = snap["ticks"]
    out["planned_ticks"] = snap["waste"]["planned_ticks"]
    return out


def make_requests(env, traffic, seconds):
    from distributed_compute_pytorch_tpu.serve import Request
    reqs = trafficgen.requests(traffic, seconds, env.seed,
                               env.config["vocab_size"])
    return reqs, [Request(tokens=r["tokens"], max_new=r["max_new"],
                          arrival_s=r["arrival_s"]) for r in reqs]


def offer(env, cb, traffic, seconds, made=None):
    """One window: ONE ``serve_detailed`` call over the requests made from
    ``traffic``. Returns (request dicts, results, t0, t1)."""
    reqs, requests = made or make_requests(env, traffic, seconds)
    close = _CloseAt(seconds) if traffic["kind"] == "backlog" else None
    t0 = time.monotonic()
    if close is not None:
        close.start()
        results = cb.serve_detailed(requests, drain=close,
                                    drain_deadline_s=0.0)
    else:
        results = cb.serve_detailed(requests)
    return reqs, results, t0, time.monotonic()


def run(env) -> dict:
    import jax

    cfg, traffic, cell = env.config, env.traffic, env.cell
    run_kw = cell["run"]
    kind = traffic["kind"]
    checks = chk.Checks()

    # ---- set-up -----------------------------------------------------
    cb, ref, spec, dtypes = setup(env)
    win = tracewin.TraceWindow(env, start_frac=0.4)

    # ---- the measured window ---------------------------------------
    made = make_requests(env, traffic, env.seconds)
    before = counters_now(cb)
    gc.collect()
    env.window_opens()
    win.arm(time.monotonic())
    reqs, results, t0, t1 = offer(env, cb, traffic, env.seconds, made)
    win.close()
    built = env.watch.between(t0, t1)

    # ---- after the window ------------------------------------------
    peak = env.memory_peak()
    snap = cb.stats_snapshot()
    after = counters_now(cb)
    stats = {k: after[k] - before[k] for k in after}
    admitted = [i for i, r in enumerate(results) if r.queue_wait_s is not None]
    tokens_out = sum(len(r.tokens) for r in results)
    if kind == "backlog":
        attempted = len(admitted)
        bad = [i for i in admitted
               if results[i].status not in ("ok", "cancelled")
               or (results[i].status == "ok"
                   and len(results[i].tokens) != reqs[i]["max_new"])]
        e2e = {"serve_tokens_per_s": tokens_out / (t1 - t0)}
    else:
        attempted = len(results)
        bad = [i for i, r in enumerate(results)
               if r.status != "ok" or len(r.tokens) != reqs[i]["max_new"]]
        missing = set(bad)

        def ms(i, v):
            return math.inf if v is None or i in missing else 1e3 * v

        ttft = [ms(i, r.ttft_s) for i, r in enumerate(results)]
        tpot = [ms(i, r.tpot_s) for i, r in enumerate(results)
                if reqs[i]["max_new"] > 1]
        e2e = {"ttft_p90_ms": percentile(ttft, 90),
               "tpot_p90_ms": percentile(tpot, 90)}
    checks.add("requests_not_ok_or_short", len(bad), 0, "==")
    checks.add("device_faults", stats["faults"], 0, "==")
    checks.add("session_reconstructions", stats["reconstructions"], 0, "==")
    checks.add("slot_and_block_leaks",
               snap["slot_leaks"] + snap["block_leaks"], 0, "==")
    checks.add("programs_built_in_window", len(built), 0, "==")
    if built:
        print(f"INFO built in window: {built[:5]}")

    rows = [{"arrival_s": reqs[i]["arrival_s"],
             "prompt_tokens": len(reqs[i]["tokens"]),
             "max_new": reqs[i]["max_new"], "status": r.status,
             "tokens": len(r.tokens), "queue_wait_s": r.queue_wait_s,
             "ttft_s": r.ttft_s, "tpot_s": r.tpot_s,
             "latency_s": r.latency_s} for i, r in enumerate(results)]
    counters = dict(stats)
    counters.update(
        slots=run_kw["slots"], segment=cb.S, tokens_emitted=tokens_out,
        window_s=t1 - t0, requests_admitted=len(admitted))
    if win.on and win.t_start is not None:
        counters.update(_trace_facts(rows, t0, win.t_start, win.t_stop))
    # rows of one admission wave carry the same admission stamp
    waves = collections.Counter(
        round(r["arrival_s"] + r["queue_wait_s"], 3) for r in rows
        if r["queue_wait_s"] is not None)
    sizes = collections.Counter(waves.values())
    print(f"INFO admission waves by rows (rows: waves) "
          f"{dict(sorted(sizes.items()))}; warmed up to "
          f"{run_kw['warm_waves']} rows")
    print(f"INFO window {t1 - t0:.2f} s for {env.seconds:g} s of traffic; "
          f"{len(results)} requests offered, {len(admitted)} admitted, "
          f"{tokens_out} tokens out, {stats['segments']} segments, "
          f"{stats['prefill_calls']} prefill waves of "
          f"{stats['prefill_rows']} rows; statuses "
          f"{ {s: sum(1 for r in results if r.status == s) for s in set(r.status for r in results)} }")
    if kind == "open_loop" and rows:
        qw = sorted(r["queue_wait_s"] for r in rows
                    if r["queue_wait_s"] is not None)
        third = max(len(rows) // 3, 1)
        by_arrival = [r["queue_wait_s"] or 0.0 for r in rows]
        print(f"INFO queue wait median first third "
              f"{np.median(by_arrival[:third]):.4f} s, last third "
              f"{np.median(by_arrival[-third:]):.4f} s; overall p50 "
              f"{qw[len(qw) // 2] if qw else float('nan'):.4f} s")

    pick = sample_for_check(reqs, results, env.seed)
    reqs_k = {i: reqs[i] for i in pick}
    res_k = {i: results[i] for i in pick}
    del cb, results
    gc.collect()
    jax.clear_caches()
    t_ref = time.monotonic()
    gaps = reference_gaps(env, ref, spec, dtypes, reqs_k, res_k, pick)
    ref_s = time.monotonic() - t_ref
    served = gaps["served"]
    checks.add("sampled_served_tokens", len(served),
               cell["limits"]["min_sampled_tokens"], ">=")
    checks.add("served_token_worst_gap_below_reference_best",
               max(served) if served else math.inf,
               cell["limits"]["served_token_gap"])
    print(f"INFO reference: {ref_s:.1f} s over {len(pick)} requests "
          f"({[len(reqs_k[i]['tokens']) for i in pick]} prompt, "
          f"{[len(res_k[i].tokens) for i in pick]} served tokens); mean gap "
          f"{np.mean(served) if served else float('nan'):.5f}, tokens with "
          f"a gap {sum(1 for g in served if g > 0)}")
    counters["reference_s"] = ref_s
    return {"checks": checks, "attempted": attempted, "failed": len(bad),
            "e2e": e2e, "memory_peak_bytes": peak, "spans": [],
            "counters": counters, "trace": win.result(), "requests": rows}


def _trace_facts(rows, t0, a, b) -> dict:
    """What the traced interval [a, b] held, from the requests' own
    stamps: prompt tokens admitted in it, and the mean number of context
    tokens live in the pool over it."""
    admitted_tokens = 0
    grid = np.linspace(a, b, 64)
    live = np.zeros_like(grid)
    for r in rows:
        if r["queue_wait_s"] is None:
            continue
        adm = t0 + r["arrival_s"] + r["queue_wait_s"]
        end = t0 + r["arrival_s"] + r["latency_s"]
        if a <= adm <= b:
            admitted_tokens += r["prompt_tokens"]
        frac = np.clip((grid - adm) / max(end - adm, 1e-9), 0.0, 1.0)
        inside = (grid >= adm) & (grid <= end)
        live += inside * (r["prompt_tokens"] + frac * r["tokens"])
    return {"prompt_tokens_admitted_in_trace": admitted_tokens,
            "mean_live_context_tokens": float(live.mean())}


def control(env, seeds, seconds):
    """The control at the cell's own size, in ONE process (set-up is
    long): weights from the first seed; for every seed a short window at
    the cell's own load; then the batcher is freed and the reference runs
    over each window's sample in float32 AND in the control precisions.
    Yields, per seed, the worst gap of the served tokens (the program's
    number) and of the tokens each control precision puts first."""
    import jax
    cb, ref, spec, dtypes = setup(env)
    weights_seed = env.seed
    kept = []
    for seed in seeds:
        env.seed = seed
        reqs, results, t0, t1 = offer(env, cb, env.traffic, seconds)
        pick = sample_for_check(reqs, results, seed)
        kept.append((seed, {i: reqs[i] for i in pick},
                     {i: results[i] for i in pick}, pick,
                     sum(r.status == "ok" for r in results), len(results)))
    del cb
    gc.collect()
    jax.clear_caches()
    env.seed = weights_seed
    for seed, reqs_k, res_k, pick, ok, n in kept:
        g = reference_gaps(env, ref, spec, dtypes, reqs_k, res_k, pick,
                           control=("int8", "fp8"))
        yield {"seed": seed, "requests_ok": ok, "requests": n,
               "sampled_tokens": len(g["served"]),
               **{f"{k}_worst_gap": max(v) for k, v in g.items()},
               **{f"{k}_mean_gap": float(np.mean(v)) for k, v in g.items()},
               **{f"{k}_tokens_with_gap": sum(1 for x in v if x > 0)
                  for k, v in g.items()}}
