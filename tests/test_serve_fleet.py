"""Elastic fleet control (serve_fleet.py): the ISSUE 20 drills.

The failure domain is fleet MEMBERSHIP: replicas join, retire, die and
reload weights while a stream is in flight. The drills pin, on a shared
tiny-GPT2 setup (shapes match test_serve_router's fleet, so the shared
program cache keeps replica construction cheap): the pure hysteresis/
cooldown decider (a fleet that never flaps), scale-up and scale-down
mid-stream with token parity against a FIXED reference fleet and zero
leaks on every member including retired ones, breaker-DEAD replacement
plus the probe-revival-vs-replacement race (RETIRED has one winner),
the rolling weight upgrade under live traffic with zero dropped
requests and exact parity for a same-value push, the weights_version
stamp declining cross-version attach/adoption without raising, and
journal recovery across a version boundary (completed ids dedup,
incomplete sessions token-replay, ``RecoveryManifest.weights_version``
surfaces the stamp). The open-loop Poisson autoscale drill rides
behind ``slow``.
"""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.obs import flight
from distributed_compute_pytorch_tpu.obs.loadgen import LoadSpec, offered_load
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from distributed_compute_pytorch_tpu.serve_fleet import (
    ElasticFleetController, ScaleDecider, ScalePolicy)
from distributed_compute_pytorch_tpu.serve_lifecycle import FAILED, OK
from distributed_compute_pytorch_tpu.serve_router import (
    CLOSED, DEAD, RETIRED, ServeRouter)
from distributed_compute_pytorch_tpu import serve_journal


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return model, params


_KW = dict(slots=2, t_max=64, prompt_buf=12, segment=3,
           prefix_cache=True, max_recoveries=0)


def _build(gpt2, weights_version=0, params=None, **over):
    model, p0 = gpt2
    return ContinuousBatcher(model, p0 if params is None else params,
                             weights_version=weights_version,
                             **{**_KW, **over})


def _controller(gpt2, n=2, weights_version=0, **policy_kw):
    model, params = gpt2
    router = ServeRouter([_build(gpt2, weights_version)
                          for _ in range(n)])
    ctl = ElasticFleetController(
        router,
        lambda p, wv, slot: _build(gpt2, wv, params=p),
        params=params, weights_version=weights_version,
        policy=ScalePolicy(**policy_kw))
    return router, ctl


def _requests(seed, n, max_new=6):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        ln = int(rng.integers(2, 9))
        reqs.append(Request(
            tokens=[int(t) for t in rng.integers(0, 256, size=ln)],
            max_new=max_new))
    if n > 3:
        # one index-default-seed sampled request: windowing/migration
        # must leave the (seed, tokens) stream untouched
        reqs[3] = dataclasses.replace(reqs[3], temperature=0.9)
    return reqs


def _copies(reqs):
    return [dataclasses.replace(r) for r in reqs]


def _reference(gpt2, reqs, n=2):
    """Fixed n-replica fleet, one monolithic route call — the parity
    oracle every elastic run must be token-identical to."""
    ref = ServeRouter([_build(gpt2) for _ in range(n)])
    return ref.route(_copies(reqs))


def _assert_no_leaks(router):
    for i, rep in enumerate(router.replicas):
        assert rep.last_slot_leaks == 0, i
        assert rep.last_block_leaks == 0, i
        assert getattr(rep, "last_host_block_leaks", 0) == 0, i


# ---- decider units (pure host logic, no fleet) --------------------------


def test_scale_policy_validates():
    with pytest.raises(ValueError):
        ScalePolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        ScalePolicy(min_replicas=0)
    with pytest.raises(ValueError):
        ScalePolicy(low_watermark=0.8, high_watermark=0.7)
    with pytest.raises(ValueError):
        ScalePolicy(up_after=0)
    with pytest.raises(ValueError):
        ScalePolicy(cooldown_s=-1.0)


def test_decider_hysteresis_streaks():
    d = ScaleDecider(ScalePolicy(up_after=2, down_after=3))
    assert d.observe(0.9, 0.0) is None          # one spike never decides
    assert d.observe(0.9, 1.0) == "up"          # a streak does
    # a mid-band observation resets BOTH streaks
    d = ScaleDecider(ScalePolicy(up_after=2, down_after=2))
    assert d.observe(0.9, 0.0) is None
    assert d.observe(0.5, 1.0) is None
    assert d.observe(0.9, 2.0) is None          # streak restarted
    assert d.observe(0.9, 3.0) == "up"
    # down needs its own streak
    d = ScaleDecider(ScalePolicy(up_after=2, down_after=3))
    assert d.observe(0.1, 0.0) is None
    assert d.observe(0.1, 1.0) is None
    assert d.observe(0.1, 2.0) == "down"


def test_decider_cooldown_never_flaps():
    d = ScaleDecider(ScalePolicy(up_after=1, down_after=1,
                                 cooldown_s=10.0))
    assert d.observe(0.9, 0.0) == "up"
    # inside the cooldown nothing decides OR accumulates — the signal
    # is still measuring the pre-event capacity
    assert d.observe(0.1, 1.0) is None
    assert d.observe(0.1, 9.9) is None
    assert d.observe(0.1, 10.0) == "down"       # cooldown expired
    # oscillating load around the watermarks never flaps with streaks
    d = ScaleDecider(ScalePolicy(up_after=2, down_after=2))
    for t, u in enumerate([0.9, 0.1, 0.9, 0.1, 0.9, 0.1]):
        assert d.observe(u, float(t)) is None


# ---- scale events mid-stream --------------------------------------------


def test_scale_up_token_parity_and_leak_free(gpt2):
    reqs = _requests(7, 12)
    ref = _reference(gpt2, reqs)
    router, ctl = _controller(gpt2, n=2, min_replicas=1, max_replicas=4,
                              up_after=1, down_after=99)
    res = ctl.serve_stream(_copies(reqs), window=4)
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert all(r.status == OK for r in res)
    assert ctl.fleet["scale_ups"] >= 1
    assert len(router.replicas) > 2
    assert ctl.fleet["current_replicas"] == len(router.active_replicas())
    _assert_no_leaks(router)
    snap = ctl.stats_snapshot()
    assert snap["fleet"]["scale_ups"] == ctl.fleet["scale_ups"]
    assert snap["router"]["router"]["routed"] == len(reqs)


def test_scale_down_token_parity_and_leak_free(gpt2):
    reqs = _requests(11, 12)
    ref = _reference(gpt2, reqs, n=3)
    router, ctl = _controller(gpt2, n=3, min_replicas=1, max_replicas=3,
                              up_after=99, down_after=1,
                              low_watermark=0.5, high_watermark=5.0)
    res = ctl.serve_stream(_copies(reqs), window=3)
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert all(r.status == OK for r in res)
    assert ctl.fleet["scale_downs"] >= 1
    retired = [i for i, s in enumerate(router.breaker_states())
               if s == RETIRED]
    assert retired, "down decision must retire a member"
    # the retired member is terminally out of dispatch but leak-free
    assert set(router.active_replicas()).isdisjoint(retired)
    _assert_no_leaks(router)


def test_scale_bounds_respected(gpt2):
    router, ctl = _controller(gpt2, n=2, min_replicas=2, max_replicas=2,
                              up_after=1, down_after=1)
    assert ctl.scale_up() is None               # at max
    assert ctl.scale_down() is None             # at min
    assert ctl.fleet["scale_ups"] == 0 and ctl.fleet["scale_downs"] == 0
    assert len(router.replicas) == 2


# ---- DEAD replacement and the revival race ------------------------------


def test_dead_replica_replaced_and_stream_survives(gpt2):
    reqs = _requests(13, 10)
    ref = _reference(gpt2, reqs)
    router, ctl = _controller(gpt2, n=3, min_replicas=1, max_replicas=4,
                              up_after=99, down_after=99)
    # replica 1's breaker exhausted its probe schedule mid-stream
    router._breakers[1].state = DEAD
    router._breakers[1].retry_at = None
    res = ctl.serve_stream(_copies(reqs), window=4)
    assert all(r.status == OK for r in res)
    assert ctl.fleet["replacements"] == 1
    assert router.breaker_states()[1] == RETIRED
    assert len(router.replicas) == 4            # replacement joined
    assert 1 not in router.active_replicas()
    assert router.breaker_states()[3] == CLOSED
    # parity: a 2-healthy elastic fleet serves windows exactly like a
    # fixed 2-replica fleet serves the monolithic call
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    _assert_no_leaks(router)


def test_probe_revival_vs_replacement_race(gpt2):
    """Before retirement, an operator probe may revive a DEAD member;
    after the controller replaces it, RETIRED is terminal — the race
    has exactly one winner and capacity can never double."""
    router, ctl = _controller(gpt2, n=2, min_replicas=1, max_replicas=4,
                              up_after=99, down_after=99)
    b = router._breakers[1]
    b.state = DEAD
    b.retry_at = None
    # the replica process is actually fine -> the canary probe wins
    assert router.probe_replica(1)
    assert router.breaker_states()[1] == CLOSED
    # DEAD again, but this time the controller replaces it first
    b.state = DEAD
    b.retry_at = None
    assert ctl.replace_dead() == 1
    assert router.breaker_states()[1] == RETIRED
    assert not router.probe_replica(1)          # probe refuses RETIRED
    assert router.breaker_states()[1] == RETIRED
    assert len(router.active_replicas()) == 2   # no double capacity


# ---- rolling weight upgrade ---------------------------------------------


def test_rolling_upgrade_between_windows_zero_drops(gpt2):
    """serve_stream's upgrade_to: the push lands after the first
    window; a same-value push must be invisible — zero failures and
    exact token parity with an un-upgraded fixed fleet."""
    model, params = gpt2
    reqs = _requests(17, 12)
    ref = _reference(gpt2, reqs)
    router, ctl = _controller(gpt2, n=2, min_replicas=2, max_replicas=2,
                              up_after=99, down_after=99)
    res = ctl.serve_stream(_copies(reqs), window=4,
                           upgrade_to=(params, 1))
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert all(r.status == OK for r in res)
    assert ctl.fleet["upgrades"] == 1
    assert ctl.weights_version == 1
    assert [r.weights_version for r in router.replicas] == [1, 1]
    assert all(r.fleet["weights_version"] == 1
               for r in router.replicas)
    _assert_no_leaks(router)


def test_rolling_upgrade_mid_route_zero_drops(gpt2):
    """The live-traffic push: upgrade() from a second thread while a
    route() is in flight. Displaced sessions are planned migrations —
    zero failures, exact parity (migration replays are
    token-identical), every replica lands on the new version."""
    model, params = gpt2
    reqs = _requests(19, 14, max_new=8)
    ref = _reference(gpt2, reqs)
    router, ctl = _controller(gpt2, n=2, min_replicas=2, max_replicas=2,
                              up_after=99, down_after=99)
    out = {}

    def _serve():
        out["res"] = router.route(_copies(reqs))

    t = threading.Thread(target=_serve)
    t.start()
    time.sleep(0.05)                    # let the round get airborne
    ctl.upgrade(params, 1)
    t.join(timeout=120)
    assert not t.is_alive()
    res = out["res"]
    assert all(r.status == OK for r in res)
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert [r.weights_version for r in router.replicas] == [1, 1]
    # every session cut from a retiring replica was a PLANNED migration
    assert router.stats["retire_migrations"] == \
        ctl.fleet["upgrade_migrations"]
    _assert_no_leaks(router)


def test_ramp_scales_up_at_once_and_takes_a_push_in_the_same_stream(gpt2):
    """One stream, both membership changes: a backlog hits a 1-replica
    fleet (max 3) whose controller must decide ``up`` at its FIRST
    control step, and a same-value weight push lands after the first
    window through the rolling walk. Zero failed requests, tokens
    identical to a fixed fleet's, every active member (added ones
    too) on the new version, no member leaks, and the scale and
    upgrade events are in the flight recorder for the post-mortem."""
    model, params = gpt2
    reqs = _requests(29, 16)
    ref = _reference(gpt2, reqs)
    router, ctl = _controller(gpt2, n=1, min_replicas=1, max_replicas=3,
                              up_after=1, down_after=99)
    decisions = []
    control_step = ctl.control_step

    def logged(queued=0):
        decisions.append(control_step(queued))
        return decisions[-1]

    ctl.control_step = logged
    rec = flight.FlightRecorder(capacity=512)
    prev = flight.configure_flight(rec)
    try:
        res = ctl.serve_stream(_copies(reqs), window=4,
                               upgrade_to=(params, 1))
        kinds = {ev["kind"] for ev in rec.events()}
    finally:
        flight.configure_flight(prev)
    assert decisions and decisions[0] == "up"
    assert all(r.status == OK for r in res)
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert ctl.fleet["scale_ups"] >= 1 and ctl.fleet["upgrades"] == 1
    active = router.active_replicas()
    assert len(active) > 1
    assert all(router.replicas[i].weights_version == 1 for i in active)
    _assert_no_leaks(router)
    assert {"fleet_scale_up", "fleet_upgrade_step"} <= kinds


def test_reload_weights_drops_cached_kv(gpt2):
    model, params = gpt2
    b = _build(gpt2, prompt_buf=24)
    rng = np.random.default_rng(23)
    prompt = [int(t) for t in rng.integers(0, 256, size=17)]
    b.serve([Request(tokens=list(prompt), max_new=4)])
    assert b.prefix_match_len(prompt) > 0        # stream is cached
    b.reload_weights(params)
    assert b.weights_version == 1
    assert b._radix.weights_version == 1
    # every KV byte derived from the old weights is gone
    assert b.prefix_match_len(prompt) == 0
    assert b.fleet["weight_reloads"] == 1
    assert b.fleet["weights_version"] == 1
    # the reloaded engine still serves (programs survived the reload)
    res = b.serve_detailed([Request(tokens=list(prompt), max_new=4)])
    assert all(r.status == OK for r in res)


# ---- weights_version stamps decline, never raise ------------------------


def test_handoff_declines_across_versions(gpt2):
    src = _build(gpt2, weights_version=0, prompt_buf=24)
    dst_new = _build(gpt2, weights_version=1, prompt_buf=24)
    dst_same = _build(gpt2, weights_version=0, prompt_buf=24)
    rng = np.random.default_rng(31)
    prompt = [int(t) for t in rng.integers(0, 256, size=17)]
    first = src.serve([Request(tokens=list(prompt), max_new=1)])[0]
    payload = src.export_prefix(prompt + first)
    assert payload is not None
    assert payload["weights_version"] == 0
    # same version attaches; the new-weights pool DECLINES (no raise)
    assert dst_same.import_prefix(payload)
    assert not dst_new.import_prefix(payload)
    assert dst_new.fleet["version_declined"] == 1
    assert dst_new.prefill["handoff_declined"] == 1
    assert dst_same.fleet["version_declined"] == 0


def test_disk_adoption_declines_across_versions(gpt2, tmp_path):
    tier_kw = dict(slots=1, t_max=32, prompt_buf=24, segment=4,
                   prefix_cache=True, pool_blocks=8,
                   host_cache_blocks=3, disk_cache_dir=str(tmp_path))
    rng = np.random.default_rng(37)
    heads = [[int(t) for t in rng.integers(0, 256, 17)]
             for _ in range(6)]
    old = _build(gpt2, weights_version=1, **tier_kw)
    for h in heads:
        old.serve([Request(tokens=list(h), max_new=6)])
    old._tier.disk.drain()
    assert old.tier["disk_spills"] >= 1
    # same version adopts its predecessor's shards...
    heir = _build(gpt2, weights_version=1, **tier_kw)
    assert heir.tier["disk_adopted"] >= 1
    assert heir.fleet["version_declined"] == 0
    # ...a different version declines every one of them, quietly
    stranger = _build(gpt2, weights_version=0, **tier_kw)
    assert stranger.tier["disk_adopted"] == 0
    assert stranger.fleet["version_declined"] >= 1
    assert stranger.stats_snapshot()["fleet"]["version_declined"] \
        == stranger.fleet["version_declined"]


# ---- journal recovery across a version boundary -------------------------


def _write_journal(root, wv):
    j = serve_journal.ServeJournal(str(root))
    j.config({"kv_dtype": "bf16", "weights_version": wv})
    j.admit("req-0", [5, 6, 7], 4)
    j.delta("req-0", [10, 11, 12, 13])
    j.end("req-0", "ok")
    j.admit("req-1", [8, 9], 5)
    j.delta("req-1", [20, 21])          # crash: no end frame
    j.commit()
    j.close()


@pytest.mark.parametrize("restart_wv", [3, 4])
def test_journal_recovery_same_and_cross_version(gpt2, tmp_path,
                                                 restart_wv):
    """A restart under the SAME version and under a DIFFERENT one both
    recover: completed ids dedup byte-identically, incomplete sessions
    replay from their journaled tokens (token replay never touches
    version-stamped KV, so it is safe on either side)."""
    _write_journal(tmp_path, wv=3)
    manifest = serve_journal.recover(str(tmp_path))
    assert manifest.weights_version == 3
    assert set(manifest.completed) == {"req-0"}
    assert set(manifest.incomplete) == {"req-1"}
    router = ServeRouter([_build(gpt2, weights_version=restart_wv)
                          for _ in range(2)])
    reqs = [Request(tokens=[5, 6, 7], max_new=4, request_id="req-0"),
            Request(tokens=[8, 9], max_new=5, request_id="req-1")]
    res = router.route(reqs, recovery=manifest)
    # exactly-once: the completed stream is emitted from the journal
    assert res[0].status == "ok" and res[0].tokens == [10, 11, 12, 13]
    assert router.stats["journal_deduped"] == 1
    # the incomplete one resumed FROM its journaled prefix
    assert res[1].status == OK
    assert res[1].tokens[:2] == [20, 21] and len(res[1].tokens) == 5
    assert router.stats["journal_recovered"] == 1
    _assert_no_leaks(router)


def test_cli_flag_validation():
    from distributed_compute_pytorch_tpu import cli_serve
    base = ["--ckpt_path", "x", "--requests", "y"]
    with pytest.raises(SystemExit):
        cli_serve.main(base + ["--autoscale", "3:2"])
    with pytest.raises(SystemExit):
        cli_serve.main(base + ["--autoscale", "nope"])
    with pytest.raises(SystemExit):
        cli_serve.main(base + ["--weights_version", "-1"])
    with pytest.raises(SystemExit):
        cli_serve.main(base + ["--autoscale", "1:2", "--mesh", "1x1"])


# ---- the open-loop autoscale drill --------------------------------------


@pytest.mark.slow
def test_poisson_autoscale_drill(gpt2):
    """Offered-load ramp through the elastic fleet: a Poisson stream
    hot enough to trip scale-up, served windowed with the control loop
    live. Every request terminates non-FAILED, the fleet grew, and
    every member — original, added, retired — is leak-free."""
    spec = LoadSpec(n_requests=24, rate_rps=40.0, seed=5,
                    prompt_len=(2, 10), max_new=(4, 10))
    reqs = offered_load(spec)
    router, ctl = _controller(gpt2, n=1, min_replicas=1, max_replicas=3,
                              up_after=1, down_after=3,
                              low_watermark=0.1)
    res = ctl.serve_stream(_copies(reqs), window=6)
    assert len(res) == len(reqs)
    assert all(r.status != FAILED for r in res)
    assert all(r.status == OK for r in res)     # no deadlines set
    assert ctl.fleet["scale_ups"] >= 1
    assert ctl.fleet["current_replicas"] == len(router.active_replicas())
    _assert_no_leaks(router)
