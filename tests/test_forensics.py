"""Forensics (obs/flight, obs/sentinel): the flight ring's
bounded/ordered/thread-safe semantics and its dump-on-every-failure-path
contract (chaos drills must produce a dump NAMING the injected fault),
the divergence sentinel catching a single-replica bit flip within one
check on the faked dp mesh (and staying silent on clean runs), the
hash chain's bitwise run-diffing determinism, and the post-compile HLO
collective census closing the SPMD-jit blind spot."""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import make_mesh
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.obs import flight, sentinel
from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics
from distributed_compute_pytorch_tpu.obs import tracing
from distributed_compute_pytorch_tpu.parallel import collectives as coll
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Capture(flight.FlightRecorder):
    """Recorder that keeps EVERY dump (last_dump only keeps the final
    one; the drills need to see the mid-session poison/fault dumps)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.all_dumps: list = []

    def dump(self, *a, **k):
        doc = super().dump(*a, **k)
        self.all_dumps.append(doc)
        return doc


@pytest.fixture(scope="module")
def gpt2_cb():
    """One batcher for every drill in this module (reset() between
    tests) — the compiled programs are per-instance, so sharing keeps
    the compile bill at one program set (test_serve_faults pattern)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return ContinuousBatcher(model, params, slots=2, t_max=64,
                             prompt_buf=10, segment=3)


def _reqs(rng, n, min_new=5, max_new=8):
    return [Request(
        tokens=[int(t) for t in
                rng.integers(1, 256, size=int(rng.integers(2, 9)))],
        max_new=int(rng.integers(min_new, max_new + 1))) for _ in range(n)]


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_ring_wraparound_keeps_newest_and_counts_dropped():
    r = flight.FlightRecorder(capacity=8)
    for i in range(20):
        r.record("ev", i=i)
    assert r.recorded == 20
    evs = r.events()
    assert [e["seq"] for e in evs] == list(range(12, 20))
    assert [e["i"] for e in evs] == list(range(12, 20))   # newest kept
    doc = r.dump("test")
    assert flight.validate_dump(doc) == []
    assert doc["dropped"] == 12 and doc["recorded"] == 20
    with pytest.raises(ValueError):
        flight.FlightRecorder(capacity=0)


def test_ring_multithreaded_orderly_under_capacity():
    r = flight.FlightRecorder(capacity=512)
    def worker(w):
        for i in range(100):
            r.record("ev", w=w, i=i)
    ts = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = r.events()
    assert len(evs) == 400
    # seqs are unique and contiguous from 0 — no lost or duplicated slot
    assert [e["seq"] for e in evs] == list(range(400))
    # each writer's own events arrive in its program order
    for w in range(4):
        mine = [e["i"] for e in evs if e["w"] == w]
        assert mine == list(range(100))
    assert flight.validate_dump(r.dump("test")) == []


def test_dump_writes_atomic_artifact_and_validates(tmp_path):
    path = tmp_path / "flight.json"
    r = flight.FlightRecorder(capacity=16, path=str(path))
    r.record("step", i=0)
    doc = r.dump("unit_test", fault="synthetic", extra_field=7)
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(doc))    # same artifact
    assert doc["reason"] == "unit_test" and doc["fault"] == "synthetic"
    assert doc["extra_field"] == 7 and doc["pid"] == os.getpid()
    assert flight.validate_dump(doc) == []
    # dump failure must not mask the fault: bad path still returns doc
    r2 = flight.FlightRecorder(capacity=4, path="/nonexistent/dir/x.json")
    assert r2.dump("t")["reason"] == "t"


def test_validate_dump_catches_violations():
    r = flight.FlightRecorder(capacity=8)
    r.record("a")
    r.record("b")
    doc = r.dump("t")
    assert flight.validate_dump(doc) == []
    bad = dict(doc, schema_version=99)
    assert any("schema_version" in p for p in flight.validate_dump(bad))
    bad = dict(doc)
    bad.pop("reason")
    assert any("reason" in p for p in flight.validate_dump(bad))
    gap = json.loads(json.dumps(doc))
    gap["events"][1]["seq"] = 5                       # seq gap
    assert any("contiguous" in p for p in flight.validate_dump(gap))


def test_global_feed_from_span_and_instant_sites():
    """The existing span/instant call sites feed the ring with no
    tracer installed — and record nothing when telemetry is off."""
    r = flight.FlightRecorder(capacity=32)
    prev = flight.configure_flight(r)
    try:
        assert tracing.current_tracer() is None
        with tracing.span("dispatch_segment", segment=1):
            pass
        tracing.instant("fault", error="x")
        kinds = [e["kind"] for e in r.events()]
        assert kinds == ["dispatch_segment", "fault"]
        assert r.events()[0]["segment"] == 1
        obs_metrics.set_enabled(False)
        try:
            tracing.instant("invisible")
        finally:
            obs_metrics.set_enabled(True)
        assert r.recorded == 2                        # disabled: nothing
    finally:
        flight.configure_flight(prev)
    tracing.instant("dropped")                        # no recorder: no-op
    assert r.recorded == 2


def test_crash_hook_dumps_and_chains(monkeypatch):
    """install_crash_hook: idempotent, dumps the ring on an unhandled
    exception, then chains to the pre-existing excepthook."""
    calls = []
    monkeypatch.setattr(sys, "excepthook", lambda tp, v, tb: calls.append(tp))
    monkeypatch.setattr(flight, "_hook_installed", False)
    flight.install_crash_hook()
    hook = sys.excepthook
    flight.install_crash_hook()
    assert sys.excepthook is hook                     # wraps only once
    r = flight.FlightRecorder(capacity=16)
    prev = flight.configure_flight(r)
    try:
        flight.record("work", i=1)
        sys.excepthook(ValueError, ValueError("boom"), None)
    finally:
        flight.configure_flight(prev)
    assert calls == [ValueError]                      # chained through
    assert r.dumps == 1
    assert r.last_dump["reason"] == "unhandled_exception"
    assert "boom" in r.last_dump["fault"]
    assert any(e["kind"] == "unhandled_exception" for e in
               r.last_dump["events"])
    assert flight.validate_dump(r.last_dump) == []


# ---------------------------------------------------------------------------
# dump-on-failure-path: every chaos fault class names its fault
# ---------------------------------------------------------------------------

def _serve_with_flight(cb, reqs, chaos, **kw):
    r = _Capture(capacity=256)
    prev = flight.configure_flight(r)
    try:
        res = cb.serve_detailed([dataclasses.replace(q) for q in reqs],
                                chaos=chaos, **kw)
    finally:
        flight.configure_flight(prev)
    for d in r.all_dumps:
        assert flight.validate_dump(d) == [], d["reason"]
    return res, r


def test_dump_on_injected_raise_names_fault(gpt2_cb):
    gpt2_cb.reset()
    rng = np.random.default_rng(31)
    res, r = _serve_with_flight(
        gpt2_cb, _reqs(rng, 4),
        ChaosInjector(fault_at_segment=2, fault_mode="raise"))
    assert all(q.status == "ok" for q in res)         # recovered
    reasons = [d["reason"] for d in r.all_dumps]
    assert "serve_fault" in reasons and "serve_session_end" in reasons
    d = next(d for d in r.all_dumps if d["reason"] == "serve_fault")
    assert "InjectedFault" in d["fault"]              # names the fault
    assert any(e["kind"] == "chaos_injection" and e["mode"] == "raise"
               for e in d["events"])                  # and the injection


def test_dump_on_watchdog_timeout_names_fault(gpt2_cb):
    gpt2_cb.reset()
    rng = np.random.default_rng(37)
    gpt2_cb.tick_timeout_s = 0.4
    try:
        res, r = _serve_with_flight(
            gpt2_cb, _reqs(rng, 4),
            ChaosInjector(fault_at_segment=2, fault_mode="hang",
                          hang_s=1.5))
    finally:
        gpt2_cb.tick_timeout_s = None
    assert all(q.status == "ok" for q in res)
    d = next(d for d in r.all_dumps if d["reason"] == "serve_fault")
    assert "Timeout" in d["fault"] and "exceeded" in d["fault"]
    assert any(e["kind"] == "chaos_injection" and e["mode"] == "hang"
               for e in d["events"])


def test_dump_on_poison_eviction_names_fault(gpt2_cb):
    gpt2_cb.reset()
    reqs = ([Request([1, 2, 3], 14)]
            + [Request([4 + i, 5, 6], 5) for i in range(3)])
    res, r = _serve_with_flight(
        gpt2_cb, reqs,
        ChaosInjector(fault_mode="poison", poison_request=1,
                      fault_count=10))
    assert res[1].status == "failed"
    d = next(d for d in r.all_dumps if d["reason"] == "poison_eviction")
    assert "poison" in d["fault"]
    assert any(e["kind"] == "poison_eviction" for e in d["events"])


def test_dump_on_slow_chaos_via_session_end(gpt2_cb):
    """'slow' never raises and never reaches handle_fault — the
    injection is only visible because the injector records itself and
    the session-end dump fires whenever chaos tripped."""
    gpt2_cb.reset()
    rng = np.random.default_rng(41)
    res, r = _serve_with_flight(
        gpt2_cb, _reqs(rng, 3),
        ChaosInjector(fault_at_segment=2, fault_mode="slow", slow_s=0.05))
    assert all(q.status == "ok" for q in res)
    assert gpt2_cb.stats["faults"] == 0               # under the budget
    assert [d["reason"] for d in r.all_dumps] == ["serve_session_end"]
    d = r.all_dumps[0]
    assert d["chaos_trips"] == 1
    assert any(e["kind"] == "chaos_injection" and e["mode"] == "slow"
               for e in d["events"])


def test_dump_on_sigterm_drain(gpt2_cb):
    gpt2_cb.reset()

    class Guard:
        preempted = False

    g = Guard()
    chaos = ChaosInjector(
        on_segment=lambda s: setattr(g, "preempted", g.preempted or s >= 2))
    rng = np.random.default_rng(43)
    res, r = _serve_with_flight(gpt2_cb, _reqs(rng, 6), chaos,
                                drain=g, drain_deadline_s=30.0)
    assert "shed" in {q.status for q in res}
    assert any(d["reason"] == "sigterm_drain" for d in r.all_dumps)


def test_trainer_nonfinite_raise_dumps():
    from distributed_compute_pytorch_tpu.train.trainer import Trainer
    r = flight.FlightRecorder(capacity=16)
    prev = flight.configure_flight(r)
    fake = SimpleNamespace(
        config=SimpleNamespace(nonfinite_policy="raise"))
    try:
        with pytest.raises(RuntimeError, match="non-finite"):
            Trainer._poll_nonfinite(fake, float("nan"), 0, 7)
    finally:
        flight.configure_flight(prev)
    assert r.last_dump["reason"] == "trainer_nonfinite"
    assert "non-finite" in r.last_dump["fault"]
    assert any(e["kind"] == "nonfinite_abort" for e in
               r.last_dump["events"])
    assert flight.validate_dump(r.last_dump) == []


def test_disabled_record_path_under_one_percent(gpt2_cb):
    """The PR 8 deterministic overhead bound, extended to the flight
    feed: with NO recorder installed, the per-call cost of the gated
    record site times a generous per-segment call census must be under
    1% of this box's measured segment wall."""
    gpt2_cb.reset()
    t0 = time.perf_counter()
    res = gpt2_cb.serve_detailed(_reqs(np.random.default_rng(47), 3))
    wall = time.perf_counter() - t0
    assert all(q.status == "ok" for q in res)
    seg_wall = wall / max(1, gpt2_cb.stats["segments"])
    assert flight.current_flight() is None
    N = 20000
    t0 = time.perf_counter()
    for _ in range(N):
        flight.record("noop", a=1)
    per_call = (time.perf_counter() - t0) / N
    calls_per_segment = 16                            # generous census
    assert per_call * calls_per_segment / seg_wall < 0.01


def test_serve_snapshot_carries_mem_gauges(gpt2_cb):
    """Satellite: device memory gauges ride the serve snapshot — a
    dict keyed mem.<device>.<stat>; CPU backends contribute nothing
    but the key must exist for dashboard consumers."""
    gpt2_cb.reset()
    res = gpt2_cb.serve_detailed([Request([1, 2, 3], 3)])
    assert res[0].status == "ok"
    snap = gpt2_cb.stats_snapshot()
    assert isinstance(snap["mem"], dict)
    for k in snap["mem"]:
        assert k.startswith("serve.mem.")
    json.dumps(snap)


@pytest.mark.slow
def test_crash_dump_subprocess_end_to_end(tmp_path):
    """A real process dying of an unhandled exception leaves a
    validating dump artifact naming the crash."""
    dump = tmp_path / "crash.json"
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from distributed_compute_pytorch_tpu.obs import flight\n"
        f"r = flight.FlightRecorder(capacity=64, path={str(dump)!r})\n"
        "flight.configure_flight(r)\n"
        "flight.install_crash_hook()\n"
        "for i in range(5):\n"
        "    flight.record('step', i=i)\n"
        "raise RuntimeError('injected-crash')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=120)
    assert proc.returncode != 0
    doc = json.loads(dump.read_text())
    assert flight.validate_dump(doc) == []
    assert doc["reason"] == "unhandled_exception"
    assert "injected-crash" in doc["fault"]
    assert sum(e["kind"] == "step" for e in doc["events"]) == 5


# ---------------------------------------------------------------------------
# divergence sentinel
# ---------------------------------------------------------------------------

def _replicated(mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P()))


def _one_replica_flipped(mesh, arr, victim=3):
    """A nominally-replicated array whose ``victim``-th device buffer
    has ONE bit flipped — the silent-corruption scenario."""
    bad = arr.copy()
    bad.view(np.uint32)[0] ^= 1
    bufs = [jax.device_put(bad if i == victim else arr, d)
            for i, d in enumerate(mesh.devices.flat)]
    return jax.make_array_from_single_device_arrays(
        arr.shape, NamedSharding(mesh, P()), bufs)


def test_sentinel_silent_on_clean_replicas(devices8):
    mesh = make_mesh("data=8", devices=devices8)
    check = sentinel.make_divergence_check(mesh)
    assert check is not None
    tree = {"w": _replicated(mesh, np.arange(32, dtype=np.float32)),
            "b": _replicated(mesh, np.ones((4, 4), np.float32))}
    assert check(tree) == 0
    assert check(tree) == 0                           # stable across calls


def test_sentinel_catches_one_replica_bit_flip(devices8):
    mesh = make_mesh("data=8", devices=devices8)
    check = sentinel.make_divergence_check(mesh)
    clean = np.arange(32, dtype=np.float32)
    tree = {"w": _one_replica_flipped(mesh, clean),
            "b": _replicated(mesh, np.ones((4, 4), np.float32))}
    assert check(tree) != 0                           # caught in ONE check


def test_sentinel_none_without_dp_axis(devices8):
    assert sentinel.make_divergence_check(
        make_mesh("data=1", devices=devices8[:1])) is None


def test_fingerprint_sensitive_to_leaf_identity():
    """The FNV fold makes leaf ORDER matter: two trees with swapped
    equal-norm leaves must not collide."""
    a = jnp.ones((4,)) * 2.0
    b = jnp.ones((4,)) * 3.0
    fp1 = int(sentinel.tree_fingerprint({"x": a, "y": b}))
    fp2 = int(sentinel.tree_fingerprint({"x": b, "y": a}))
    assert fp1 != fp2
    assert fp1 == int(sentinel.tree_fingerprint({"x": a, "y": b}))


def test_hash_chain_bitwise_diffing():
    c1, c2 = sentinel.HashChain(), sentinel.HashChain()
    for i in range(10):
        c1.update(float(i), float(i) * 2)
        c2.update(float(i), float(i) * 2)
    assert c1.digest() == c2.digest() and c1.steps == 10
    d_before = c1.digest()
    c1.update(1.0)
    c2.update(1.0 + 1e-15)                            # one ulp-ish differs
    assert c1.digest() != c2.digest()                 # first divergence
    assert c1.digest() != d_before                    # chain, not a hash


def test_trainer_divergence_check_end_to_end(devices8, tmp_path):
    """--divergence_check on a real 2-epoch dp run: clean replicas stay
    silent, hash_chain lines land in the metrics JSONL, and the chain
    digest is reproducible across identical runs."""
    from distributed_compute_pytorch_tpu.core.config import Config
    from distributed_compute_pytorch_tpu.data.datasets import synthetic_lm
    from distributed_compute_pytorch_tpu.train.trainer import Trainer

    data = synthetic_lm(64, seq_len=128, vocab=256, seed=3)

    def run(tag):
        cfg = Config(batch_size=32, lr=1e-3, epochs=1, mesh="data=8",
                     model="gpt2", model_preset="tiny",
                     dataset="synthetic-lm",
                     optimizer="adamw", divergence_check=True,
                     log_every=1, force_cpu=True,
                     ckpt_path=str(tmp_path / f"ck{tag}.npz"),
                     metrics_jsonl=str(tmp_path / f"m{tag}.jsonl"))
        Trainer(cfg, train_data=data, eval_data=data).fit()
        lines = [json.loads(ln) for ln in
                 (tmp_path / f"m{tag}.jsonl").read_text().splitlines()]
        return [ln for ln in lines if ln["kind"] == "hash_chain"]

    chains_a, chains_b = run("a"), run("b")
    assert chains_a and chains_a[-1]["steps"] > 0
    assert [c["digest"] for c in chains_a] == \
           [c["digest"] for c in chains_b]            # bitwise-identical


# ---------------------------------------------------------------------------
# HLO collective census (the SPMD-jit blind spot)
# ---------------------------------------------------------------------------

def test_hlo_census_sees_partitioner_inserted_collectives(devices8):
    """Pure SPMD-jit: the jaxpr census truthfully reports zero (no
    collective primitives before compilation) while the partitioner
    inserts an all-reduce — the compiled-HLO census must see it."""
    mesh = make_mesh("data=8", devices=devices8)
    x = jax.device_put(np.ones((8, 32), np.float32),
                       NamedSharding(mesh, P("data")))

    @jax.jit
    def f(x):
        return jnp.sum(x)

    assert coll.jaxpr_collectives(f, x) == []         # the PR 8 gap
    census = coll.hlo_collectives(f, x)
    assert census["count"] >= 1 and census["bytes"] > 0
    assert "all-reduce" in census["ops"]
    # no collectives -> an honest zero
    g = jax.jit(lambda x: x * 2)
    none = coll.hlo_collectives(g, np.ones((4,), np.float32))
    assert none == {"ops": {}, "count": 0, "bytes": 0}
