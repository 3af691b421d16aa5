"""Elastic fleet control: load-driven autoscaling, replica
replacement, and zero-drop rolling weight upgrades (ISSUE 20).

PRs 11/15 made the serving fleet crash-durable and self-healing — but
membership was still fixed at startup: a breaker-DEAD replica shrank
capacity forever, traffic swings could not change the fleet size, and
a weight push meant killing the process. This module adds the control
plane over ``ServeRouter`` that makes membership DYNAMIC, built on the
one fact the whole serving stack already guarantees: live sessions and
KV prefixes are replica-portable. Failover-by-migration replays a
session token-identically anywhere (the (seed, tokens-so-far) sampling
key), and the CRC'd export/import wire format moves finished KV
between pools — so a scale event or an upgrade is "just" an
orchestrated migration.

:class:`ElasticFleetController` owns a router and drives three loops:

- **Autoscaling** (:meth:`control_step`): utilisation — queued work
  against ``active_replicas × slots`` capacity, widened by SLO burn
  from the heartbeat snapshots — feeds a :class:`ScaleDecider`
  (hysteresis streaks + cooldown, a pure unit-testable state machine)
  so one noisy observation can never flap the fleet. Scale-up builds
  a replica through the caller's factory: it comes up WARM — the
  shared compiled-program cache (PR 12) means zero recompiles for an
  equal-config member, and ``adopt_disk_index`` (PR 15) re-attaches
  any disk-tier prefixes its directory holds. Scale-down retires the
  chosen member through the router's drain-by-migration: its live
  sessions replay token-identically on survivors and the replica
  leaves leak-free.
- **Replacement** (:meth:`replace_dead`): a breaker-DEAD replica is
  retired and a fresh member added in its place — DEAD is no longer
  terminal capacity loss. Retirement is terminal per-slot
  (``probe_replica`` refuses a RETIRED member; the replacement holds
  its traffic), so the revival/replacement race has one winner by
  construction.
- **Rolling upgrade** (:meth:`upgrade`): walk the fleet one replica at
  a time — retire (live sessions drain to survivors), hot-swap the
  weights in place (``ContinuousBatcher.reload_weights``: compiled
  programs survive, every cached KV byte drops), re-admit. Zero
  requests drop: every cut session is a planned migration. The
  ``weights_version`` stamp threads through radix entries, tier
  sidecars, handoff payloads and the WAL config frame so an
  old-version prefix can never attach to new weights — cross-version
  attach/handoff/adoption DECLINES (``serve.fleet.version_declined``)
  and falls back to token replay, never raises.

``route()`` is synchronous and round-based, so the controller gets its
control points two ways: :meth:`serve_stream` windows an open-loop
request stream into consecutive ``route`` calls with a
:meth:`control_step` between windows (identity and seeds are
materialised globally up front, so the windowed stream is
token-identical to one monolithic ``route`` call); and mid-route,
:meth:`upgrade`/:meth:`retire` work through the router's per-replica
drain latch — safe to drive from a second thread while a route call is
in flight, which is how a weight push lands under live load.

Observability: the controller's ``serve.fleet.*`` MetricDict
(scale_ups / scale_downs / replacements / upgrade_migrations /
version_declined / current_replicas) rides :meth:`stats_snapshot`
beside the router's, and every scale event and upgrade step drops a
flight-recorder instant.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from distributed_compute_pytorch_tpu.obs import flight
from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics
from distributed_compute_pytorch_tpu.obs.tracing import instant
from distributed_compute_pytorch_tpu.serve_router import DEAD, RETIRED


@dataclass(frozen=True)
class ScalePolicy:
    """Autoscaling policy knobs (all pure data — the decision logic
    lives in :class:`ScaleDecider` so it unit-tests without a fleet).

    Utilisation is queued-work-per-capacity (plus SLO burn when
    ``slo_target_ttft_s`` is set): >= ``high_watermark`` for
    ``up_after`` consecutive observations scales up, <=
    ``low_watermark`` for ``down_after`` scales down, and every
    decision opens a ``cooldown_s`` window during which observations
    are ignored entirely — hysteresis keeps one noisy sample from
    deciding, the cooldown keeps back-to-back decisions from flapping
    against their own transient."""

    min_replicas: int = 1
    max_replicas: int = 4
    high_watermark: float = 0.75
    low_watermark: float = 0.25
    up_after: int = 2
    down_after: int = 3
    cooldown_s: float = 0.0
    # optional SLO-burn widening: p99 TTFT from the heartbeat
    # snapshots over this target counts as utilisation >= 1.0
    slo_target_ttft_s: float | None = None

    def __post_init__(self):
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas, got "
                f"{self.min_replicas}..{self.max_replicas}")
        if not 0.0 <= self.low_watermark < self.high_watermark:
            raise ValueError(
                f"need 0 <= low_watermark < high_watermark, got "
                f"{self.low_watermark} / {self.high_watermark}")
        if self.up_after < 1 or self.down_after < 1:
            raise ValueError("up_after/down_after must be >= 1")
        if self.cooldown_s < 0:
            raise ValueError(f"cooldown_s must be >= 0, got "
                             f"{self.cooldown_s}")


class ScaleDecider:
    """The hysteresis + cooldown state machine: feed it one
    utilisation observation at a time, get back ``"up"``, ``"down"``
    or ``None``. Pure host logic — no fleet, no clock of its own —
    so the no-flap properties are pinned by direct unit tests."""

    def __init__(self, policy: ScalePolicy):
        self.policy = policy
        self._high = 0
        self._low = 0
        self._cooldown_until: float | None = None

    def observe(self, utilization: float, now: float) -> str | None:
        p = self.policy
        if (self._cooldown_until is not None
                and now < self._cooldown_until):
            # observations inside the cooldown neither decide nor
            # accumulate: the fleet just changed, the signal is
            # measuring the old capacity
            return None
        if utilization >= p.high_watermark:
            self._high += 1
            self._low = 0
        elif utilization <= p.low_watermark:
            self._low += 1
            self._high = 0
        else:
            self._high = self._low = 0
        decision = None
        if self._high >= p.up_after:
            decision = "up"
        elif self._low >= p.down_after:
            decision = "down"
        if decision is not None:
            self._high = self._low = 0
            self._cooldown_until = now + p.cooldown_s
        return decision


class ElasticFleetController:
    """The elastic control plane over one :class:`~serve_router.
    ServeRouter` (module docstring: autoscaling, replacement, rolling
    upgrade).

    ``build_replica(params, weights_version, slot)`` is the caller's
    replica factory — it must return a ``ContinuousBatcher``-shaped
    engine config-identical to the existing members (so the shared
    compiled-program cache warms it for free) serving ``params``
    stamped ``weights_version``. ``slot`` is the router index the new
    member will occupy (a replacement passes the RETIRED member's
    index is-being-replaced hint instead) — factories keying
    per-replica disk directories on it let a replacement adopt its
    predecessor's spilled prefixes.

    ``params``/``weights_version`` are the fleet's CURRENT weights —
    every scale-up and replacement is built from them, and
    :meth:`upgrade` advances them. The controller holds them for its
    life, and an engine cuts what it is handed into a form of its own
    (``ContinuousBatcher._cut_weights``): where the weights fill a
    device, hand over a HOST tree (``jax.device_get``; ``dcp-serve``
    does) and let the factory and ``reload_weights`` place a copy a
    replica, so that no device holds the fleet's tree beside an
    engine's."""

    def __init__(self, router, build_replica, *, params,
                 weights_version: int = 0,
                 policy: ScalePolicy | None = None,
                 clock=time.monotonic, sleep=time.sleep):
        self.router = router
        self.build_replica = build_replica
        self.params = params
        self.weights_version = int(weights_version)
        self.policy = policy if policy is not None else ScalePolicy()
        self.decider = ScaleDecider(self.policy)
        self._clock = clock
        self._sleep = sleep
        self.obs = obs_metrics.Registry()
        self.fleet = obs_metrics.MetricDict(self.obs, "serve.fleet.", {
            "scale_ups": 0, "scale_downs": 0, "replacements": 0,
            "upgrades": 0, "upgrade_migrations": 0,
            "version_declined": 0,
            "current_replicas": len(router.active_replicas())})

    # ---- load signal -------------------------------------------------------

    def slot_capacity(self) -> int:
        """Decode slots across the active fleet — the denominator of
        the utilisation signal."""
        return sum(getattr(self.router.replicas[i], "B", 1)
                   for i in self.router.active_replicas())

    def _slo_burn(self) -> float:
        """p99 TTFT from the freshest heartbeat snapshots over the
        policy target (0.0 without a target or signal) — the second
        load signal: a fleet can be queue-empty and still burning its
        latency budget."""
        target = self.policy.slo_target_ttft_s
        if target is None:
            return 0.0
        worst = 0.0
        for i in self.router.active_replicas():
            snap = self.router._last_snap[i] or {}
            try:
                ttft = snap["slo"]["ttft_s"]
                if ttft.get("count", 0) > 0 and ttft.get("p99"):
                    worst = max(worst, float(ttft["p99"]) / target)
            except (KeyError, TypeError):
                continue
        return worst

    def observe_load(self, queued: int) -> float:
        """One utilisation sample: queued requests against the active
        fleet's slot capacity, widened by SLO burn."""
        cap = max(1, self.slot_capacity())
        return max(queued / cap, self._slo_burn())

    # ---- scale events ------------------------------------------------------

    def control_step(self, queued: int = 0) -> str | None:
        """One control-loop tick (between :meth:`serve_stream` windows,
        or on any caller's cadence): replace DEAD members first —
        replacement is a health action, never throttled by the scale
        cooldown — then feed one load observation to the decider and
        act on its verdict. Returns ``"up"``/``"down"``/``None``."""
        self.replace_dead()
        decision = self.decider.observe(self.observe_load(queued),
                                        self._clock())
        if decision == "up":
            self.scale_up()
        elif decision == "down":
            self.scale_down()
        return decision

    def replace_dead(self) -> int:
        """Retire every breaker-DEAD member and add a fresh replica
        per retirement — DEAD is capacity to restore, not mourn. The
        retire-then-add order settles the revival/replacement race:
        once RETIRED, an operator ``probe_replica`` refuses to revive
        the old member, so capacity can never double."""
        replaced = 0
        for i in list(self.router.active_replicas()):
            if self.router._breakers[i].state != DEAD:
                continue
            was_prefill = i in self.router._prefill_set
            self.router.retire_replica(i)
            rep = self.build_replica(self.params, self.weights_version,
                                     i)
            j = self.router.add_replica(rep, prefill=was_prefill)
            self.fleet["replacements"] += 1
            replaced += 1
            instant("fleet_replace", dead=i, replacement=j)
            flight.record("fleet_replace", dead=i, replacement=j,
                          weights_version=self.weights_version)
        if replaced:
            self.fleet["current_replicas"] = len(
                self.router.active_replicas())
        return replaced

    def scale_up(self) -> int | None:
        """Add one warm replica (None at ``max_replicas``)."""
        active = self.router.active_replicas()
        if len(active) >= self.policy.max_replicas:
            return None
        slot = len(self.router.replicas)
        rep = self.build_replica(self.params, self.weights_version,
                                 slot)
        i = self.router.add_replica(rep)
        self.fleet["scale_ups"] += 1
        self.fleet["current_replicas"] = len(
            self.router.active_replicas())
        instant("fleet_scale_up", replica=i,
                replicas=self.fleet["current_replicas"])
        flight.record("fleet_scale_up", replica=i,
                      replicas=self.fleet["current_replicas"])
        return i

    def scale_down(self) -> int | None:
        """Retire one replica (None at ``min_replicas`` or no
        candidate): the highest-indexed non-prefill active member, so
        the original fleet core is shed last and prefill-tier capacity
        is never auto-shrunk. Mid-round the router drains it by
        migration (sessions replay token-identically on survivors);
        between rounds it is already idle — either way it leaves
        leak-free, which the drills assert."""
        active = self.router.active_replicas()
        if len(active) <= self.policy.min_replicas:
            return None
        cand = [i for i in active
                if i not in self.router._prefill_set]
        # keep at least one decode replica
        if len(cand) < 2:
            return None
        victim = max(cand)
        self.router.retire_replica(victim)
        self.fleet["scale_downs"] += 1
        self.fleet["current_replicas"] = len(
            self.router.active_replicas())
        instant("fleet_scale_down", replica=victim,
                replicas=self.fleet["current_replicas"])
        flight.record("fleet_scale_down", replica=victim,
                      replicas=self.fleet["current_replicas"])
        return victim

    # ---- rolling upgrade ---------------------------------------------------

    def upgrade(self, params, weights_version: int | None = None, *,
                wait_timeout_s: float = 60.0) -> int:
        """Rolling weight push: walk the ACTIVE fleet one replica at a
        time — retire it (a mid-round member drains: in-flight rows
        finish, cut sessions migrate to survivors), hot-swap the
        weights in place once its worker is out, re-admit. Safe to
        call from a second thread while a ``route``/``serve_stream``
        is in flight — that is the drill: a model push under live load
        drops ZERO requests, because every displaced session is a
        planned migration and the re-admitted replica rejoins dispatch
        warm (compiled programs survive the reload).

        A DEAD member encountered mid-walk is replaced outright (the
        replacement is built at the NEW version). Returns the number
        of replicas now serving ``weights_version`` (defaults to
        current + 1)."""
        wv = (int(weights_version) if weights_version is not None
              else self.weights_version + 1)
        old_wv = self.weights_version
        # advance the fleet's target first: replicas built mid-walk
        # (replacements, concurrent scale-ups) come up at the new
        # version instead of instantly needing their own upgrade
        self.params = params
        self.weights_version = wv
        upgraded = 0
        for step, i in enumerate(list(self.router.active_replicas())):
            if self.router._breakers[i].state == DEAD:
                self.replace_dead()
                upgraded += 1
                continue
            pre = self.router.stats["retire_migrations"]
            self.router.retire_replica(i)
            deadline = self._clock() + wait_timeout_s
            while self.router._busy[i] and self._clock() < deadline:
                self._sleep(0.005)
            if self.router._busy[i]:
                # the worker never drained (wedged replica): leave it
                # RETIRED — the next control_step sees a capacity gap
                # and the breaker machinery/DEAD replacement owns it
                flight.record("fleet_upgrade_skip", replica=i,
                              reason="drain timeout")
                continue
            migrated = self.router.stats["retire_migrations"] - pre
            self.fleet["upgrade_migrations"] += migrated
            self.router.replicas[i].reload_weights(params, wv)
            self.router.readmit_replica(i)
            upgraded += 1
            instant("fleet_upgrade_step", replica=i, step=step,
                    migrated=migrated, old_version=old_wv,
                    new_version=wv)
            flight.record("fleet_upgrade_step", replica=i, step=step,
                          migrated=migrated, old_version=old_wv,
                          new_version=wv)
        self.fleet["upgrades"] += 1
        self.fleet["current_replicas"] = len(
            self.router.active_replicas())
        instant("fleet_upgrade_done", replicas=upgraded,
                old_version=old_wv, new_version=wv)
        flight.record("fleet_upgrade_done", replicas=upgraded,
                      old_version=old_wv, new_version=wv)
        return upgraded

    # ---- windowed serving --------------------------------------------------

    def serve_stream(self, requests, *, window: int = 8, drain=None,
                     drain_deadline_s: float | None = None,
                     chaos: dict | None = None, recovery=None,
                     upgrade_to=None) -> list:
        """Serve an open-loop stream elastically: split ``requests``
        into ``window``-sized batches, ``route`` each, and run one
        :meth:`control_step` between batches (the scale period).
        Identity and the positional seed default are materialised over
        the WHOLE stream up front (the single-``route`` rule), so the
        windowed run is token-identical to a monolithic one — scale
        events can never change a stream. Arrival offsets and
        deadlines shift with elapsed time so window k's requests keep
        their stream-absolute timing.

        ``upgrade_to=(params, weights_version)`` pushes new weights
        via the rolling :meth:`upgrade` walk after the FIRST window —
        the canonical mid-traffic weight push (the remaining windows
        prove zero drops). ``recovery`` (a journal manifest) applies
        to every window: dedup/replay key on request id."""
        from dataclasses import replace as _dc_replace
        reqs = []
        for j, r in enumerate(requests):
            rid = getattr(r, "request_id", None) or f"req-{j}"
            if r.temperature > 0 and r.seed is None:
                r = _dc_replace(r, seed=j, request_id=rid)
            elif r.request_id != rid:
                r = _dc_replace(r, request_id=rid)
            reqs.append(r)
        t0 = self._clock()
        results: list = []
        pushed = upgrade_to is None
        for start in range(0, len(reqs), max(1, window)):
            batch = reqs[start:start + max(1, window)]
            elapsed = self._clock() - t0
            adj = []
            for r in batch:
                kw = {}
                if getattr(r, "arrival_s", 0.0):
                    kw["arrival_s"] = max(0.0, r.arrival_s - elapsed)
                if r.deadline_s is not None:
                    kw["deadline_s"] = max(1e-3,
                                           r.deadline_s - elapsed)
                adj.append(_dc_replace(r, **kw) if kw else r)
            results.extend(self.router.route(
                adj, drain=drain, drain_deadline_s=drain_deadline_s,
                chaos=chaos, recovery=recovery))
            if not pushed:
                self.upgrade(*upgrade_to)
                pushed = True
            if start + window < len(reqs):
                self.control_step(queued=len(reqs) - start - len(batch))
        return results

    # ---- observability -----------------------------------------------------

    def stats_snapshot(self) -> dict:
        """Fleet counters + per-replica weights versions + the
        router's own snapshot — the top of the snapshot hierarchy
        (engine -> router -> fleet) that heartbeats and the metrics
        JSONL carry."""
        self.fleet["current_replicas"] = len(
            self.router.active_replicas())
        declined = 0
        for rep in self.router.replicas:
            eng = getattr(rep, "fleet", None)
            if eng is not None:
                declined += int(eng.get("version_declined", 0))
            tier = getattr(rep, "_tier", None)
            if tier is not None and not isinstance(
                    getattr(tier, "fleet_stats", None),
                    obs_metrics.MetricDict):
                declined += int(tier.fleet_stats.get(
                    "version_declined", 0))
        self.fleet["version_declined"] = declined
        return {
            "fleet": dict(self.fleet),
            "weights_version": self.weights_version,
            "replica_weights_versions": [
                getattr(r, "weights_version", 0)
                for r in self.router.replicas],
            "breakers": self.router.breaker_states(),
            "retired": [i for i, s in
                        enumerate(self.router.breaker_states())
                        if s == RETIRED],
            "router": self.router.stats_snapshot(),
        }
