"""Unified telemetry (obs/): histogram accuracy vs numpy, span-trace
structural validity under nesting and thread interleaving, snapshot
equivalence with the legacy stats/waste dicts on a real serve drill,
the open-loop load generator's determinism and arrival semantics, and
the disabled path's no-op contract (including token parity with
telemetry off — observation must never change behaviour)."""

import dataclasses
import json
import math
import threading

import jax
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.obs import loadgen
from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics
from distributed_compute_pytorch_tpu.obs import tracing
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request


@pytest.fixture
def tiny_cb():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return ContinuousBatcher(model, params, slots=2, t_max=64,
                             prompt_buf=10, segment=4)


def _requests(rng, n):
    return [Request(
        tokens=[int(t) for t in
                rng.integers(1, 256, size=int(rng.integers(2, 9)))],
        max_new=int(rng.integers(3, 8))) for _ in range(n)]


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_vs_numpy(dist):
    """The log-bucket estimate must land within one bucket's relative
    width of numpy's exact quantile — the documented accuracy bound."""
    rng = np.random.default_rng(0)
    n = 5000
    if dist == "lognormal":
        xs = rng.lognormal(mean=-4.0, sigma=1.5, size=n)   # latency-ish
    elif dist == "uniform":
        xs = rng.uniform(1e-4, 1e-1, size=n)
    else:
        xs = np.concatenate([rng.normal(2e-3, 2e-4, n // 2),
                             rng.normal(5e-1, 5e-2, n // 2)])
        xs = np.abs(xs) + 1e-9
    h = obs_metrics.Histogram("t", per_decade=16)
    for x in xs:
        h.record(float(x))
    for q in (0.5, 0.9, 0.95, 0.99):
        est = h.percentile(q)
        # inverted_cdf picks an actual sample: at a bimodal density gap
        # the default linear interpolation invents a value BETWEEN the
        # modes that no estimator bounded by observed samples can match
        true = float(np.quantile(xs, q, method="inverted_cdf"))
        # one bucket's width in log10 space, plus interpolation slack
        assert abs(math.log10(est) - math.log10(true)) <= 1.5 / 16, (
            dist, q, est, true)
    assert h.count == n
    assert h.min == float(np.min(xs)) and h.max == float(np.max(xs))


def test_histogram_edges_and_summary():
    h = obs_metrics.Histogram("t", lo=1e-3, hi=1e3, per_decade=4)
    assert math.isnan(h.percentile(0.5))
    assert h.summary() == {"count": 0}
    for v in (1e-6, 1.0, 1e6):      # underflow, in-range, overflow
        h.record(v)
    assert h.count == 3
    # percentiles clamp to observed extremes even from the end buckets
    assert h.percentile(0.0) == 1e-6
    assert h.percentile(1.0) == 1e6
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 1e-6 and s["max"] == 1e6
    json.dumps(s)                   # serialisable as-is


def test_registry_get_or_create_and_type_conflict():
    reg = obs_metrics.Registry()
    c = reg.counter("a")
    assert reg.counter("a") is c
    with pytest.raises(TypeError):
        reg.gauge("a")
    reg.histogram("h").record(2.0)
    reg.gauge("g").set(7)
    snap = reg.snapshot()
    assert snap["g"] == 7 and snap["h"]["count"] == 1
    json.dumps(snap)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_thread_interleaving_valid():
    """Nested spans in the main thread plus concurrent spans from worker
    threads must produce a structurally valid Chrome trace: matched
    LIFO B/E per (pid, tid), monotonic timestamps."""
    tr = tracing.Tracer()
    prev = tracing.configure_tracer(tr)
    try:
        with tracing.span("outer", wave=1):
            with tracing.span("inner"):
                tracing.instant("marker", n=3)

        def worker(i):
            for _ in range(20):
                with tracing.span(f"w{i}"):
                    with tracing.span(f"w{i}.child"):
                        pass

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        tracing.configure_tracer(prev)
    events = tr.events()
    assert tracing.validate_chrome_trace(events) == []
    assert sum(e["ph"] == "B" for e in events) == 2 + 4 * 40
    assert any(e["ph"] == "i" and e["name"] == "marker" for e in events)
    args = next(e for e in events if e["name"] == "outer")["args"]
    assert args == {"wave": 1}


def test_validate_chrome_trace_catches_violations():
    base = {"pid": 1, "tid": 1}
    assert tracing.validate_chrome_trace(
        [{**base, "ph": "E", "name": "x", "ts": 1.0}])
    assert tracing.validate_chrome_trace(
        [{**base, "ph": "B", "name": "x", "ts": 1.0}])       # unclosed
    assert tracing.validate_chrome_trace(
        [{**base, "ph": "B", "name": "x", "ts": 2.0},
         {**base, "ph": "E", "name": "x", "ts": 1.0}])       # ts regress
    assert tracing.validate_chrome_trace(
        [{**base, "ph": "B", "name": "x", "ts": 1.0},
         {**base, "ph": "B", "name": "y", "ts": 2.0},
         {**base, "ph": "E", "name": "x", "ts": 3.0},
         {**base, "ph": "E", "name": "y", "ts": 4.0}])       # not LIFO
    ok = [{**base, "ph": "B", "name": "x", "ts": 1.0},
          {**base, "ph": "E", "name": "x", "ts": 2.0},
          {"pid": 1, "tid": 2, "ph": "B", "name": "x", "ts": 0.5},
          {"pid": 1, "tid": 2, "ph": "E", "name": "x", "ts": 0.9}]
    assert tracing.validate_chrome_trace(ok) == []


def test_span_disabled_paths():
    """No tracer and no profile running -> null span; telemetry off ->
    null span even with a tracer; counters/histograms no-op when
    disabled, gauges do not."""
    assert tracing.current_tracer() is None
    s = tracing.span("x")
    assert s is tracing.span("y")           # the shared null context
    with s:
        pass
    tr = tracing.Tracer()
    prev = tracing.configure_tracer(tr)
    try:
        obs_metrics.set_enabled(False)
        assert tracing.span("x") is s
        tracing.instant("x")
        c = obs_metrics.Counter("c")
        c.inc()
        h = obs_metrics.Histogram("h")
        h.record(1.0)
        g = obs_metrics.Gauge("g")
        g.set(3)
        assert c.value == 0 and h.count == 0 and g.value == 3
    finally:
        obs_metrics.set_enabled(True)
        tracing.configure_tracer(prev)
    assert tr.events() == []


# ---------------------------------------------------------------------------
# serve integration: snapshot equivalence, SLO fields, disabled parity
# ---------------------------------------------------------------------------

def test_stats_snapshot_matches_legacy_views(tiny_cb):
    """stats_snapshot() must agree with the legacy dicts (which the
    tests still index) AND with the registry gauges the
    MetricDict mirrors into — the three can never diverge."""
    rng = np.random.default_rng(7)
    results = tiny_cb.serve_detailed(_requests(rng, 6))
    assert all(r.ok for r in results)
    snap = tiny_cb.stats_snapshot()
    assert snap["stats"] == dict(tiny_cb.stats)
    assert snap["waste"] == dict(tiny_cb.waste)
    reg = tiny_cb.obs.snapshot()
    for k, v in tiny_cb.stats.items():
        assert reg[f"serve.{k}"] == v
    for k, v in tiny_cb.waste.items():
        assert reg[f"serve.waste.{k}"] == v
    assert snap["slot_leaks"] == 0 and snap["block_leaks"] == 0
    # SLO histograms saw every admitted request
    assert snap["slo"]["e2e_s"]["count"] == len(results)
    assert snap["slo"]["queue_wait_s"]["count"] == len(results)
    assert snap["slo"]["ttft_s"]["count"] == len(results)
    json.dumps(snap)
    # reset clears the histograms with the counters
    tiny_cb.reset()
    assert tiny_cb.stats_snapshot()["slo"]["e2e_s"] == {"count": 0}


def test_request_results_carry_slo_fields(tiny_cb):
    rng = np.random.default_rng(11)
    results = tiny_cb.serve_detailed(_requests(rng, 5))
    for r in results:
        assert r.ok
        assert r.queue_wait_s is not None and r.queue_wait_s >= 0
        assert r.ttft_s is not None and r.ttft_s >= r.queue_wait_s
        assert r.latency_s >= r.ttft_s
        if len(r.tokens) > 1:
            assert r.tpot_s is not None and r.tpot_s >= 0


def test_serve_token_parity_with_telemetry_disabled(tiny_cb):
    """Observation must not change behaviour: the same workload with
    telemetry off produces identical tokens, and the functional
    stats/waste views keep counting."""
    rng = np.random.default_rng(13)
    reqs = _requests(rng, 6)

    def clone():
        return [dataclasses.replace(r) for r in reqs]

    base = tiny_cb.serve(clone())
    tiny_cb.reset()
    obs_metrics.set_enabled(False)
    try:
        off = tiny_cb.serve(clone())
    finally:
        obs_metrics.set_enabled(True)
    assert off == base
    assert tiny_cb.stats["segments"] > 0          # gauges kept working
    assert tiny_cb.stats_snapshot()["slo"]["e2e_s"] == {"count": 0}


# ---------------------------------------------------------------------------
# open-loop load generation
# ---------------------------------------------------------------------------

def test_poisson_arrivals_shape_and_determinism():
    with pytest.raises(ValueError):
        loadgen.poisson_arrivals(0.0, 4, np.random.default_rng(0))
    a = loadgen.poisson_arrivals(10.0, 200, np.random.default_rng(1))
    b = loadgen.poisson_arrivals(10.0, 200, np.random.default_rng(1))
    assert a == b
    assert all(t1 > t0 for t0, t1 in zip(a, a[1:]))   # strictly increasing
    # mean inter-arrival within 3 sigma of 1/rate
    gaps = np.diff([0.0] + a)
    assert abs(gaps.mean() - 0.1) < 3 * 0.1 / math.sqrt(len(gaps))


def test_offered_load_deterministic_and_well_formed():
    spec = loadgen.LoadSpec(n_requests=12, rate_rps=5.0, seed=3)
    r1, r2 = loadgen.offered_load(spec), loadgen.offered_load(spec)
    assert [(r.tokens, r.max_new, r.arrival_s) for r in r1] == \
           [(r.tokens, r.max_new, r.arrival_s) for r in r2]
    for r in r1:
        assert spec.prompt_len[0] <= len(r.tokens) <= spec.prompt_len[1]
        assert spec.max_new[0] <= r.max_new <= spec.max_new[1]
        assert all(1 <= t < spec.vocab for t in r.tokens)
    assert [r.arrival_s for r in r1] == sorted(r.arrival_s for r in r1)


def test_arrival_gating_delays_admission(tiny_cb):
    """With free slots, a future-dated request is NOT admitted early:
    the scheduler idles to its arrival (the serve wall absorbs the
    gap), while queue_wait — measured from ARRIVAL, not submission —
    stays near zero. Negative arrivals are rejected at validation."""
    import time as _time
    rng = np.random.default_rng(17)
    tiny_cb.serve_detailed(_requests(rng, 2))      # pay compiles here
    tiny_cb.reset()
    late = _requests(rng, 1)[0]
    late.arrival_s = 0.3
    t0 = _time.monotonic()
    (res,) = tiny_cb.serve_detailed([late])
    wall = _time.monotonic() - t0
    assert res.ok
    assert wall >= 0.3                  # idled to the arrival, free slots
    assert res.queue_wait_s < 0.25      # from arrival, not submission
    bad = Request(tokens=[1, 2], max_new=2)
    bad.arrival_s = -1.0
    (res,) = tiny_cb.serve_detailed([bad])
    assert res.status == "failed" and "arrival_s" in res.error


def test_open_loop_load_keeps_tokens_and_leaves_a_valid_trace(tiny_cb):
    """Arrival gating must never change outputs: the stream offered at
    its Poisson arrival times, with the spans traced, completes every
    request with the tokens the same stream gives when all of it is due
    at once; every request's first token is stamped, nothing leaks, and
    the spans written DURING the drill validate as a Chrome trace."""
    spec = loadgen.LoadSpec(n_requests=8, rate_rps=40.0, seed=0,
                            prompt_len=(2, 9), max_new=(3, 7))
    load = loadgen.offered_load(spec)
    base = tiny_cb.serve_detailed(
        [dataclasses.replace(r, arrival_s=0.0) for r in load])
    tiny_cb.reset()
    tr = tracing.Tracer()
    prev = tracing.configure_tracer(tr)
    try:
        report = loadgen.run_load(
            tiny_cb, [dataclasses.replace(r) for r in load])
    finally:
        tracing.configure_tracer(prev)
    assert report["statuses"] == {"ok": len(load)}
    assert [r.tokens for r in report["results"]] == \
        [r.tokens for r in base]
    assert report["completed_tokens"] == sum(len(r.tokens) for r in base)
    assert report["slo"]["ttft_s"]["count"] == len(load)
    assert report["snapshot"]["slot_leaks"] == 0
    assert report["snapshot"]["block_leaks"] == 0
    events = tr.events()
    assert tracing.validate_chrome_trace(events) == []
    assert {"admit_wave", "dispatch_segment", "harvest"} <= \
        {e["name"] for e in events}


@pytest.mark.slow
def test_run_load_end_to_end(tiny_cb):
    spec = loadgen.LoadSpec(n_requests=10, rate_rps=20.0, seed=5)
    report = loadgen.run_load(tiny_cb, loadgen.offered_load(spec))
    assert report["ok"] == 10
    assert report["goodput_tok_s"] > 0
    assert report["slo"]["ttft_s"]["count"] == 10
    assert math.isfinite(report["slo"]["ttft_s"]["p99"])
    assert report["snapshot"]["slot_leaks"] == 0


# ---------------------------------------------------------------------------
# MetricLogger lifecycle + profile arming
# ---------------------------------------------------------------------------

def test_metric_logger_context_manager_and_registry(tmp_path):
    from distributed_compute_pytorch_tpu.utils.logging import MetricLogger
    reg = obs_metrics.Registry()
    path = tmp_path / "m.jsonl"
    with MetricLogger(str(path), registry=reg) as ml:
        ml.train_line(0, 2, 10, 0.5)
        ml.eval_line(0, 0.4, 9, 10)
        ml.telemetry("memory", {"mem.0.bytes_in_use": 123})
        ml.close()
        ml.close()                  # idempotent
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["kind"] for rec in lines] == ["train", "eval", "memory"]
    snap = reg.snapshot()
    assert snap["train.loss"] == 0.5 and snap["train.step"] == 2
    assert snap["eval.accuracy"] == 0.9


def test_profile_next_arms_and_disarms(tiny_cb, tmp_path, monkeypatch):
    """profile_next(N) starts one XLA trace at the next dispatch and
    stops it N segments later — monkeypatched profiler, real drill."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    with pytest.raises(ValueError):
        tiny_cb.profile_next(0, str(tmp_path))
    tiny_cb.profile_next(2, str(tmp_path))
    rng = np.random.default_rng(19)
    assert all(r.ok for r in tiny_cb.serve_detailed(_requests(rng, 4)))
    assert calls[0] == ("start", str(tmp_path))
    assert calls.count(("stop", None)) == 1
    assert tiny_cb._profile_req is None       # disarmed after the window
