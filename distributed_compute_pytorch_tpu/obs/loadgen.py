"""Open-loop Poisson load generation for the serve engine (ROADMAP 3).

A CLOSED-loop driver (send, wait, send) self-throttles when the server
slows down and so can never observe queueing collapse; an OPEN-loop
driver commits to an arrival process up front and lets queue-wait
absorb whatever the server cannot sustain — the methodology every
serving paper's goodput/p99 curves assume. The serve engine is
synchronous (one ``serve_detailed`` call takes the whole request
list), so open-loop arrivals ride IN-BAND: each ``serve.Request``
carries an ``arrival_s`` offset and the scheduler refuses to admit a
request before its arrival time (and idles to the next arrival when
the pool drains early). That keeps the drill single-threaded and
deterministic given a seed — the same property the chaos harness
(``serve_lifecycle.ChaosInjector``) relies on.

``offered_load(...)`` builds the request stream: exponential
inter-arrival gaps at ``rate_rps`` (a Poisson process), prompt lengths
and budgets uniform over the given ranges, all from one seeded
``numpy`` generator. ``run_load(...)`` serves it and reduces the
results + the batcher's SLO histograms into one report: goodput (ok tokens per wall second), completion mix, and
p50/p90/p95/p99 for queue-wait, TTFT, TPOT and e2e latency.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np


@dataclass
class LoadSpec:
    """One open-loop drill's shape. ``rate_rps`` is the OFFERED arrival
    rate — wall-clock, independent of service capacity (that gap is
    the point). Prompt token ids are uniform over ``[1, vocab)`` (0 is
    reserved as a conventional pad id in the tokenizer stack)."""

    n_requests: int = 16
    rate_rps: float = 8.0
    seed: int = 0
    vocab: int = 256
    prompt_len: tuple[int, int] = (2, 10)    # inclusive range
    max_new: tuple[int, int] = (4, 12)       # inclusive range


def poisson_arrivals(rate_rps: float, n: int, rng) -> list[float]:
    """Cumulative arrival offsets (seconds) of a Poisson process:
    i.i.d. exponential gaps with mean ``1/rate_rps``."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    gaps = rng.exponential(1.0 / rate_rps, size=n)
    return [float(t) for t in np.cumsum(gaps)]


def offered_load(spec: LoadSpec) -> list:
    """Build the arrival-stamped request list for ``serve_detailed``.
    Deterministic in ``spec.seed``; requests are in arrival order (the
    FIFO admission contract assumes it)."""
    from distributed_compute_pytorch_tpu.serve import Request
    rng = np.random.default_rng(spec.seed)
    arrivals = poisson_arrivals(spec.rate_rps, spec.n_requests, rng)
    reqs = []
    for t in arrivals:
        ln = int(rng.integers(spec.prompt_len[0], spec.prompt_len[1] + 1))
        new = int(rng.integers(spec.max_new[0], spec.max_new[1] + 1))
        reqs.append(Request(
            tokens=[int(x) for x in rng.integers(1, spec.vocab, size=ln)],
            max_new=new, arrival_s=t))
    return reqs


def run_load(cb, requests: list, *, drain=None,
             drain_deadline_s: float | None = None, chaos=None) -> dict:
    """Serve an arrival-stamped stream and reduce to the load report.

    Returns ``{"wall_s", "goodput_tok_s", "ok", "completed_tokens",
    "statuses", "slo": {queue_wait_s|ttft_s|tpot_s|e2e_s|delivery_gap_s:
    {count, mean, p50, p90, p95, p99, ...}}, "results", "snapshot"}`` — ``results``
    are the raw ``RequestResult``s (token-parity checks), ``snapshot``
    the batcher's full ``stats_snapshot()``.
    """
    t0 = time.monotonic()
    results = cb.serve_detailed(requests, drain=drain,
                                drain_deadline_s=drain_deadline_s,
                                chaos=chaos)
    wall_s = time.monotonic() - t0
    ok_tokens = sum(len(r.tokens) for r in results if r.ok)
    statuses: dict[str, int] = {}
    for r in results:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    snapshot = cb.stats_snapshot()
    return {"wall_s": wall_s,
            "goodput_tok_s": ok_tokens / wall_s if wall_s > 0 else 0.0,
            "ok": statuses.get("ok", 0),
            "completed_tokens": ok_tokens,
            "statuses": statuses,
            "slo": snapshot["slo"],
            "results": results,
            "snapshot": snapshot}
