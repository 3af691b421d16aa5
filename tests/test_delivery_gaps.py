"""Every delivery is stamped (ISSUE 38).

A DELIVERY is one harvest handing a request new tokens. The scheduler
stamps each with the harvest's one clock read, counts the gap to the
request's previous delivery into ``stats`` (``deliveries_*``,
``delivery_gap_s_*`` and the bucket family ``delivery_gap_upto_<ms>``,
which ``stats_snapshot()["slo"]["delivery_gap_s"]`` is read off), and says by
the DEVICE's order whether admission stood between the two segments that
delivered. ``RequestResult`` carries the request's own view
(``deliveries``, ``max_gap_s``), the spans carry it to the timeline, and
six benchmark metrics read the counters."""

import bisect
import json
import math
import pathlib

import jax
import pytest

from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.obs import tracing
from distributed_compute_pytorch_tpu import serve
from distributed_compute_pytorch_tpu.obs.metrics import Histogram
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from distributed_compute_pytorch_tpu.serve_lifecycle import (
    ChaosInjector, RequestResult)
from distributed_compute_pytorch_tpu.serve_router import (
    ServeRouter, _Session)
from distributed_compute_pytorch_tpu.spec_decode import SpecConfig
from perfbench.readers import counter_bucket_percentile, counter_ratio

ROOT = pathlib.Path(__file__).resolve().parent.parent
METRICS = ROOT / "perfbench" / "layer_metrics"
FAMILY = "delivery_gap_upto_"
SEGMENT = 4


@pytest.fixture(scope="module")
def llama():
    model = build_model("llama", preset="tiny")
    params, _ = model.init(jax.random.key(0))
    return model, params


def batcher(llama, **kw):
    model, params = llama
    return ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=8,
                             segment=SEGMENT, **kw)


def family(stats) -> dict:
    return {k: v for k, v in stats.items() if k.startswith(FAMILY)}


def gaps_closed(cb) -> int:
    return (cb.stats["deliveries_clear"]
            + cb.stats["deliveries_behind_admission"])


def hold_the_identities(cb, results):
    """What has to agree whatever was served: the two counts, the
    bucket family, the operator's digest and the requests' own fields."""
    st = cb.stats
    slo = cb.stats_snapshot()["slo"]["delivery_gap_s"]
    assert gaps_closed(cb) == sum(family(st).values()) == slo["count"]
    # every delivery of a request but its first closed one gap
    assert gaps_closed(cb) == sum(max(r.deliveries - 1, 0) for r in results)
    for r in results:
        assert (r.max_gap_s is None) == (r.deliveries < 2), r
        assert (r.deliveries > 0) == bool(r.tokens), r
    assert "deliveries" not in st       # the sum of two counters that stay
    if gaps_closed(cb):
        assert slo["max"] == max(r.max_gap_s for r in results
                                 if r.max_gap_s is not None)
        assert (st["delivery_gap_s_clear"]
                + st["delivery_gap_s_behind_admission"]
                == pytest.approx(slo["mean"] * slo["count"]))


@pytest.fixture(scope="module")
def later_admission(llama):
    """Two slots, three requests at once: the long one decodes all the
    way through, the short one is done with its first segment, and only
    then does its slot admit the third: ONE admission dispatch between
    two segments that both deliver to the long request."""
    cb = batcher(llama)
    reqs = [Request(tokens=[5, 9, 12], max_new=6 * SEGMENT),
            Request(tokens=[7, 3], max_new=SEGMENT),
            Request(tokens=[8, 2, 6, 4], max_new=2 * SEGMENT)]
    tr = tracing.Tracer()
    prev = tracing.configure_tracer(tr)
    try:
        results = cb.serve_detailed(reqs)
    finally:
        tracing.configure_tracer(prev)
    assert [len(r.tokens) for r in results] == [r.max_new for r in reqs]
    return cb, results, tr.events()


def span_args(events, name, ph):
    return [e.get("args", {}) for e in events
            if e["name"] == name and e["ph"] == ph]


def test_the_delivery_after_a_later_admission_is_behind_it(later_admission):
    cb, results, events = later_admission
    st = cb.stats
    assert [r.deliveries for r in results] == [6, 1, 2]
    # the long request's 5 gaps and the third's 1; of them only the long
    # request's delivery from the first segment dispatched after the
    # third's admission had that admission before it on the device
    assert st["deliveries_behind_admission"] == 1
    assert st["deliveries_clear"] == 5
    assert st["delivery_gap_s_behind_admission"] > 0
    hold_the_identities(cb, results)


def test_the_spans_carry_the_window_that_stood_between(later_admission):
    cb, _results, events = later_admission
    admitted = [a["admitted_window_tokens"]
                for a in span_args(events, "dispatch_segment", "B")]
    # the first segment follows the first wave, one later segment the
    # second; every other segment follows a segment
    assert admitted[0] > 0 and sorted(admitted)[-2] > 0
    assert sorted(admitted)[:-2] == [0] * (len(admitted) - 2)
    assert sum(admitted) == cb.stats["prefill_window_tokens"]
    ends = span_args(events, "harvest", "E")
    behind = [a for a in ends if a["behind_admission"]]
    assert len(behind) == 1 and behind[0]["behind_admission"] == 1
    assert set(ends[0]) >= {"first", "done", "gap_max_ms",
                            "behind_admission"}
    # by the device's order: that harvest is the one that FOLLOWS the
    # segment dispatched behind the wave, not the one the host ran next
    names = [(e["name"], e.get("args", {})) for e in events
             if e["ph"] == "B" and e["name"] in ("dispatch_segment",
                                                 "harvest")]
    after_wave = next(i for i, (n, a) in enumerate(names[1:], 1)
                      if n == "dispatch_segment"
                      and a["admitted_window_tokens"])
    assert names[after_wave + 1][0] == "harvest"       # of the segment before


def test_max_gap_is_the_longest_gap_and_none_for_one_delivery(
        later_admission):
    cb, results, events = later_admission
    long_one, short_one, third = results
    assert short_one.deliveries == 1 and short_one.max_gap_s is None
    # the long request is in every harvest, so the longest gap any
    # harvest closed is its longest
    longest = max(a["gap_max_ms"] for a in span_args(events, "harvest", "E"))
    assert long_one.max_gap_s * 1e3 == pytest.approx(longest, abs=1e-3)
    assert third.max_gap_s <= long_one.max_gap_s
    assert (cb.stats_snapshot()["slo"]["delivery_gap_s"]["max"]
            == long_one.max_gap_s)


def test_a_first_delivery_counts_no_gap(llama):
    cb = batcher(llama)
    results = cb.serve_detailed([Request(tokens=[5, 9], max_new=SEGMENT),
                                 Request(tokens=[7], max_new=2)])
    assert [r.deliveries for r in results] == [1, 1]
    assert all(r.ttft_s is not None and r.max_gap_s is None
               for r in results)
    assert gaps_closed(cb) == 0 and not any(family(cb.stats).values())
    assert cb.stats_snapshot()["slo"]["delivery_gap_s"] == {"count": 0}
    hold_the_identities(cb, results)


def test_the_identities_hold_under_speculation(llama):
    cb = batcher(llama, speculate=SpecConfig(k=3))
    reqs = [Request(tokens=[3, 4, 3, 4, 3], max_new=14),
            Request(tokens=[9, 1], max_new=5),
            Request(tokens=[6, 6, 6], max_new=9)]
    results = cb.serve_detailed(reqs)
    assert cb.spec["verify_segments"] > 0
    assert [len(r.tokens) for r in results] == [r.max_new for r in reqs]
    # a verify step hands over 1..k+1 tokens: more deliveries than a plain
    # segment's share, fewer than one a token unless nothing is accepted
    assert all(math.ceil(r.max_new / 4) <= res.deliveries <= r.max_new
               for r, res in zip(reqs, results))
    assert cb.stats["deliveries_behind_admission"] >= 1   # the third's wave
    hold_the_identities(cb, results)


def test_the_identities_hold_after_a_reconstruction(llama):
    cb = batcher(llama)
    results = cb.serve_detailed(
        [Request(tokens=[5, 9, 12], max_new=6 * SEGMENT)],
        chaos=ChaosInjector(fault_at_segment=4, fault_mode="raise"))
    assert cb.stats["reconstructions"] == 1
    (r,) = results
    assert r.status == "ok" and r.recoveries == 1 and r.deliveries == 6
    # the re-prefill is admission the device ran before the next delivery
    assert cb.stats["prefill_calls"] == 2
    assert cb.stats["deliveries_behind_admission"] == 1
    assert cb.stats["deliveries_clear"] == 4
    hold_the_identities(cb, results)


def test_a_fresh_session_starts_the_counts_again(llama):
    cb = batcher(llama)
    cb.serve([Request(tokens=[5, 9], max_new=3 * SEGMENT)])
    assert gaps_closed(cb) == 2
    cb.reset()
    assert gaps_closed(cb) == 0 and not any(family(cb.stats).values())
    assert cb.stats_snapshot()["slo"]["delivery_gap_s"] == {"count": 0}


def test_the_router_carries_both_fields(llama):
    results = ServeRouter([batcher(llama)]).route(
        [Request(tokens=[5, 9], max_new=3 * SEGMENT)])
    assert results[0].deliveries == 3 and results[0].max_gap_s > 0
    # a session that moved keeps what its placements reported
    sess = _Session(req=None, arrive_abs=0.0, deadline_at=None)
    sess.bank(RequestResult(tokens=[1, 2], deliveries=2, max_gap_s=0.25))
    sess.bank(RequestResult(tokens=[3], deliveries=1))
    sess.bank(RequestResult(tokens=[4, 5], deliveries=2, max_gap_s=0.125))
    assert (sess.tokens, sess.deliveries, sess.max_gap_s) == (
        [1, 2, 3, 4, 5], 5, 0.25)


def test_the_operators_digest_is_read_off_the_family(llama):
    """One store: ``stats_snapshot()["slo"]["delivery_gap_s"]`` has the
    SLO histograms' keys, from the counters and the longest gap alone."""
    cb = batcher(llama)
    st = cb.stats
    st["delivery_gap_upto_100"] += 90
    st["delivery_gap_upto_133"] += 8
    st["delivery_gap_upto_inf"] += 2
    st["deliveries_clear"] += 90
    st["deliveries_behind_admission"] += 10
    st["delivery_gap_s_clear"] += 9.0
    st["delivery_gap_s_behind_admission"] += 26.0
    cb._longest_gap_s = 12.5
    slo = cb.stats_snapshot()["slo"]["delivery_gap_s"]
    like = Histogram("like")
    like.record(1.0)
    assert set(slo) == set(like.summary()) - {"min"}
    assert (slo["count"], slo["mean"], slo["max"]) == (100, 0.35, 12.5)
    # a percentile is its bucket's upper edge, held to the longest gap
    assert slo["p50"] == slo["p90"] == pytest.approx(0.1, rel=1e-9)
    assert slo["p95"] == pytest.approx(0.1334, rel=1e-3)
    assert slo["p99"] == 12.5
    json.dumps(slo, allow_nan=False)


# ---- the benchmark's side: the reader and the six metric files -------

HAND_MADE = {"delivery_gap_upto_100": 90, "delivery_gap_upto_115": 0,
             "delivery_gap_upto_133": 8, "delivery_gap_upto_1000": 2,
             "delivery_gap_upto_inf": 0, "deliveries": 100,
             "delivery_gap_s_clear": 9.0, "deliveries_clear": 90}


@pytest.mark.parametrize("q,edge", [(50, 100.0), (90, 100.0), (91, 133.0),
                                    (98, 133.0), (99, 1000.0),
                                    (100, 1000.0)])
def test_bucket_percentile_returns_the_edge_of_the_bucket(q, edge):
    got = counter_bucket_percentile.read(
        {"prefix": FAMILY, "q": q}, {"counters": HAND_MADE})
    assert got["value"] == edge
    assert got["note"].startswith("100 observations, p50 <= 100, highest "
                                  "bucket occupied <= 1000")


@pytest.mark.parametrize("counters", [
    {"deliveries": 4, "segments": 9},                  # the parent: no family
    {k: 0 for k in HAND_MADE},                          # a window with none
], ids=["no_family", "empty_family"])
def test_bucket_percentile_reads_nothing_where_nothing_counted(counters):
    assert counter_bucket_percentile.read(
        {"prefix": FAMILY, "q": 99}, {"counters": counters}) is None


def test_bucket_percentile_beyond_the_last_edge_says_so():
    got = counter_bucket_percentile.read(
        {"prefix": FAMILY, "q": 99},
        {"counters": {"delivery_gap_upto_100": 5,
                      "delivery_gap_upto_10000": 0,
                      "delivery_gap_upto_inf": 5}})
    assert got["value"] == 10000.0 and "ABOVE 10000" in got["note"]
    assert math.isfinite(got["value"])          # the result line is JSON


NEW_METRICS = sorted(p.name for p in METRICS.glob("delivery_gap_*.json"))


def test_there_are_six_delivery_metrics_each_in_the_manifest():
    assert len(NEW_METRICS) == 6
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in manifest["per_layer"]]
    # appended after what was there, wherever later entries follow them
    mine = [manifest["per_layer"][names.index(n[:-len(".json")])]
            for n in NEW_METRICS]
    assert min(names.index(m["name"]) for m in mine) > names.index(
        "decode_rows_parked_share.serve_backlog")
    for m in mine:
        assert (m["layer"], m["source"], m["unit"], m["better"]) == (
            "Scheduler", "program_counter", "ms", "lower")
        if m["name"].endswith(".serve_backlog"):
            assert m["moves"] == "serve_tokens_per_s"
            assert all(cell.endswith("_backlog") for cell in m["workloads"])
        else:
            assert m["moves"] == "tpot_p90_ms"
            assert m["workloads"] == ["mistral7b_chat_steady"]
    # the rule the benchmark's own manifest tests end on, held here for
    # every entry: a per-layer metric lists a cell only if the metric it
    # moves does too
    e2e = {e["name"]: e for e in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in e2e[m["moves"]].get("workloads", [cell]), (
                m["name"], cell)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_counter_a_delivery_metric_reads_is_in_fresh_stats(
        name, llama):
    """The way ``test_tracing_scopes`` holds scope metrics to ``SCOPES``:
    a metric file reads only names a fresh batcher already has, so a
    window that opens before the first delivery differences them."""
    spec = json.loads((METRICS / name).read_text())
    stats = batcher(llama).stats_snapshot()["stats"]
    if spec["reader"] == "counter_ratio":
        for counter in spec["numerator"] + spec["denominator"]:
            assert stats[counter] == 0, counter
        assert counter_ratio.read(spec, {"counters": stats}) is None
    else:
        assert spec["reader"] == "counter_bucket_percentile"
        edges = sorted(float(k[len(spec["prefix"]):]) for k in stats
                       if k.startswith(spec["prefix"]))
        # 16 a decade from 10 ms to 10 s, and the bucket with no edge
        assert len(edges) == 49 and edges[-1] == math.inf
        assert edges[0] == 11.5 and edges[-2] == 10000.0
        assert all(b / a == pytest.approx(10 ** (1 / 16), rel=0.01)
                   for a, b in zip(edges[:-2], edges[1:-1]))
        assert counter_bucket_percentile.read(
            spec, {"counters": stats}) is None


def test_a_gap_counts_in_the_bucket_named_by_its_upper_edge(llama):
    """The family is on ``obs.metrics.Histogram``'s spacing; each gap of a
    run lands once, under the first edge above it."""
    cb = batcher(llama)
    assert list(family(cb.stats)) == list(serve._GAP_COUNTERS)
    for name, edge in zip(serve._GAP_COUNTERS, serve._GAP_EDGES_S):
        assert float(name[len(FAMILY):]) == pytest.approx(1e3 * edge,
                                                          rel=5e-3)
    spaced = Histogram("like", lo=1e-2, hi=10.0)
    for gap in (0.001, 0.0114, 0.0116, 0.2, 9.99, 10.5, 99.0):
        at = bisect.bisect_right(serve._GAP_EDGES_S, gap)
        spaced.record(gap)
        # the histogram keeps an underflow bucket, the family none
        assert spaced.counts[at + 1 if gap >= 1e-2 else 0] == 1, gap
        spaced.counts = [0] * len(spaced.counts)
    (r,) = cb.serve_detailed([Request(tokens=[5, 9], max_new=5 * SEGMENT)])
    occupied = {k: v for k, v in family(cb.stats).items() if v}
    assert sum(occupied.values()) == 4
    top = max(occupied, key=lambda k: float(k[len(FAMILY):]))
    assert top == serve._GAP_COUNTERS[
        bisect.bisect_right(serve._GAP_EDGES_S, r.max_gap_s)]
