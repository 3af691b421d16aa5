"""Replica-set serving: a health-checked router over N batcher replicas.

PRs 5-9 made one ``ContinuousBatcher`` a sound, fully instrumented
failure domain — deadlines, shed, drain, token-identical session
reconstruction, SLO histograms, flight recorder. But one batcher is
still one queue and one point of failure; the north star (heavy
traffic from millions of users) needs the failure domain to be *one
replica of N*. :class:`ServeRouter` owns N independent
``ContinuousBatcher`` replicas (each its own compiled programs, block
pool and radix cache — typically each its own mesh on real hardware)
and turns a replica death into a migration instead of an outage.

Dispatch — SLO-aware least-loaded with radix affinity:

- Every routing decision probes each healthy replica's prefix cache
  with the READ-ONLY ``prefix_match_len`` probe
  (``RadixCache.longest_match_len``: no LRU touch, no refcounts — a
  probe that mutated LRU order would let routing evict state the loser
  replicas still want). The replica holding the longest cached prefix
  of the request's prompt wins, because a cache hit skips that much
  prefill — cache hit rate is a CLUSTER property once there is more
  than one pool. With the hierarchical KV tier enabled (kv_tier.py:
  ``--host_cache_mb`` / ``--disk_cache_dir``, each replica owning its
  own host pool) the probe counts HOST/DISK-demoted prefixes as warm
  too: promoting spilled bytes back to device is one H2D copy, far
  cheaper than re-prefilling the prefix on a cold replica.
- Affinity yields to load: each candidate's backlog is estimated in
  ticks (unshared prefill suffix + segment-rounded decode budget of
  everything already assigned this round, scaled by the replica's
  observed mean TPOT from ``stats_snapshot()``), and a warm replica
  more than ``affinity_max_extra_ticks`` ahead of the least-loaded one
  loses the request anyway — bounded queueing skew is worth more than
  a warm prefix (DESIGN.md carries the tradeoff).

Robustness — health, breaker, migration:

- Health per replica: heartbeat recency (each replica's scheduler
  thread beats ``on_heartbeat`` between device calls; the router
  timestamps every beat) and consecutive-fault counters feed a
  :class:`CircuitBreaker` per replica: CLOSED -> OPEN on
  ``fault_threshold`` consecutive faults, OPEN -> HALF_OPEN when the
  deterministic exponential-backoff schedule (``elastic.
  backoff_delays``, jitter-seeded per replica) says to probe,
  HALF_OPEN -> CLOSED on a successful canary / back to OPEN on
  failure, and DEAD once the probe budget is exhausted (only an
  explicit :meth:`ServeRouter.probe_replica` revives it).
- A replica death is observed, never raised: ``serve_detailed`` never
  raises, so a replica that faulted past its own ``max_recoveries``
  budget returns its live rows as ``failed`` with the ``"device lost
  after ..."`` marker (plus anything still queued). The router treats
  that as the failover trigger: every such session is MIGRATED — the
  PR 5 reconstruction argument applied ACROSS replicas. The sampling
  key for a row's t-th token is ``fold_in(key(seed), n_logical + t)``
  — a pure function of (seed, tokens-known-so-far) — so re-admitting
  ``prompt + generated-so-far`` on a DIFFERENT replica with the same
  explicit seed continues the identical token stream (greedy is
  trivially identical). The router materialises ``seed=None`` to the
  request's global index up front, exactly the single-batcher default,
  so placement and migration never change any sampled stream.
- A continuation whose ``prompt + partial`` outgrows the target
  replica's prompt window falls back to FULL REPLAY from the original
  prompt — same seed, so still token-identical, just recomputed.
- Deadline-aware re-shedding: when capacity shrinks, a migrated
  request replays with only its REMAINING wall budget; one already
  past its deadline at failover time is finalised ``timeout`` (with
  its partial tokens) or ``shed`` (queued, nothing generated) instead
  of wasting survivor capacity.
- Heartbeat-staleness takeover (opt-in ``heartbeat_stale_s``): a
  replica wedged so hard its scheduler thread stops beating — and has
  no tick watchdog of its own to convert the hang into a device-lost
  — is declared dead mid-round; its whole assignment replays on the
  survivors and the zombie thread's eventual output is discarded.
- Graceful degradation is policy: with k of N replicas open/dead the
  partitioner simply spreads over the survivors at reduced goodput,
  and with ZERO healthy replicas requests fail fast with a structured
  error instead of wedging. A cluster-wide drain is one SIGTERM: the
  same ``PreemptionGuard`` object is passed to every replica, each
  finishes its in-flight rows and sheds its queue, and the router does
  not re-place the shed work.

Every failover dumps the flight ring (``reason="replica_failover"``)
naming the dead replica and the migrated sessions; all events a
replica records are tagged with its index via ``flight.replica_tag``
wrapped around each worker thread.

Elastic membership (ISSUE 20, driven by ``serve_fleet.
ElasticFleetController``): the replica set is no longer fixed at
construction. :meth:`ServeRouter.add_replica` appends a warm member
(scale-up, or the replacement for a breaker-DEAD one);
:meth:`ServeRouter.retire_replica` removes one — mid-round it drains
that single replica through a per-replica latch ORed into its drain
object, and the cut sessions re-enter the next round on survivors
exactly like a failover, minus the fault. Indices are stable (a
retired slot goes quiet, never reused), RETIRED is terminal to the
probe machinery (``probe_replica`` refuses; only the controller's
``readmit_replica`` — the upgrade walk's re-admission — returns one),
and a transiently mixed-``weights_version`` fleet is legal: handoffs
only target same-version replicas, token replay covers the rest.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace

from distributed_compute_pytorch_tpu.obs import flight
from distributed_compute_pytorch_tpu.obs.tracing import instant
from distributed_compute_pytorch_tpu.serve import Request
from distributed_compute_pytorch_tpu.serve_lifecycle import (
    CANCELLED, FAILED, OK, SHED, TIMEOUT, RequestResult)
from distributed_compute_pytorch_tpu.train.elastic import (
    backoff_delays, retry_with_backoff)

# serve.handle_fault's recovery-budget-exhausted marker: the substring
# that classifies a failed result as "this replica is gone" (migrate)
# vs. a per-request failure (terminal)
DEVICE_LOST_MARKER = "device lost after"

# breaker states
CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"
DEAD = "dead"
# membership state (ISSUE 20): a RETIRED replica has been removed from
# the fleet on purpose — scale-down, replacement of a DEAD member, or
# the drain step of a rolling weight upgrade. Terminal for probing:
# record_ok/record_fault/probe_replica all refuse to flip it (the
# replacement already holds its traffic), only the controller's
# explicit readmit_replica (the upgrade walk's re-admission) does.
RETIRED = "retired"


class CircuitBreaker:
    """Per-replica dispatch gate with deterministic backoff.

    CLOSED admits traffic. ``fault_threshold`` consecutive faults trip
    it OPEN with a retry time from the ``elastic.backoff_delays``
    schedule (explicit ``jitter_seed`` — N replicas seeded ``seed + i``
    desynchronise their probes reproducibly). When the retry time
    arrives the router takes the single HALF_OPEN probe slot
    (:meth:`begin_probe`); the canary's outcome either re-CLOSEs the
    breaker or re-OPENs it with the next (longer) delay. Exhausting
    the ``probe_budget`` schedule leaves the breaker DEAD: the router
    never auto-probes it again, only an explicit
    ``ServeRouter.probe_replica`` (an operator action) can revive it.
    """

    def __init__(self, *, fault_threshold: int = 1, probe_budget: int = 4,
                 probe_base_delay_s: float = 0.25, jitter_seed: int = 0):
        if fault_threshold < 1:
            raise ValueError(f"fault_threshold must be >= 1, got "
                             f"{fault_threshold}")
        self.fault_threshold = fault_threshold
        self.delays = backoff_delays(probe_budget, probe_base_delay_s,
                                     jitter_seed)
        self.state = CLOSED
        self.consecutive = 0      # consecutive observed faults
        self.trips = 0            # times the breaker opened
        self.retry_at: float | None = None
        self._k = 0               # next backoff-schedule index

    @property
    def healthy(self) -> bool:
        return self.state == CLOSED

    def record_ok(self) -> None:
        if self.state == RETIRED:
            return            # membership is the controller's call
        self.consecutive = 0
        self._k = 0
        self.retry_at = None
        self.state = CLOSED

    def record_fault(self, now: float) -> None:
        if self.state == RETIRED:
            return            # already out of the fleet
        self.consecutive += 1
        if self.state == HALF_OPEN or self.consecutive >= self.fault_threshold:
            self.trips += 1
            if self._k < len(self.delays):
                self.retry_at = now + self.delays[self._k]
                self._k += 1
                self.state = OPEN
            else:
                self.retry_at = None
                self.state = DEAD

    def probe_due(self, now: float) -> bool:
        return (self.state == OPEN and self.retry_at is not None
                and now >= self.retry_at)

    def begin_probe(self) -> None:
        self.state = HALF_OPEN


def _longer(a: float | None, b: float | None) -> float | None:
    """The longer of two gaps, either of which may not exist."""
    return max((g for g in (a, b) if g is not None), default=None)


@dataclass
class _Session:
    """Router-side host state for one routed request: everything needed
    to replay it token-identically on another replica, plus the
    metadata accumulated across placements."""

    req: Request                       # original, seed materialised
    arrive_abs: float                  # absolute arrival instant
    deadline_at: float | None          # absolute deadline (None = none)
    tokens: list = field(default_factory=list)   # generated so far
    # "prefill" until the prompt has been prefilled somewhere; with a
    # prefill tier configured, such sessions are placed on prefill
    # replicas and hop to the decode tier right after their first token
    phase: str = "decode"
    migrated: int = 0
    rounds: int = 0                    # placements attempted
    ticks: int = 0
    recoveries: int = 0
    cached_prefix: int = 0
    queue_wait_s: float | None = None
    ttft_s: float | None = None
    deliveries: int = 0
    max_gap_s: float | None = None     # the longest inside a placement

    def bank(self, r: RequestResult) -> None:
        """Keep what a placement that ended short of the request
        reported: its partial stream and the metadata that adds up."""
        self.tokens.extend(r.tokens)
        self.ticks += r.ticks
        self.recoveries += r.recoveries
        self.cached_prefix += r.cached_prefix_tokens
        self.deliveries += r.deliveries
        self.max_gap_s = _longer(self.max_gap_s, r.max_gap_s)


class _ReplicaDrain:
    """The drain object each worker hands its replica: the OR of the
    cluster-wide latch and that replica's retirement flag (ISSUE 20).
    A retirement mid-round looks, to the one replica, exactly like a
    SIGTERM drain — admission stops, in-flight rows finish, the queue
    sheds — but the ROUTER re-places the cut sessions on survivors
    instead of finalising them, because only this member is leaving."""

    def __init__(self, router: "ServeRouter", i: int, drain):
        self._router, self._i, self._drain = router, i, drain

    @property
    def preempted(self) -> bool:
        return bool(self._router._retiring[self._i]
                    or (self._drain is not None
                        and getattr(self._drain, "preempted", False)))


class ServeRouter:
    """Thread-based router over N ``ContinuousBatcher`` replicas
    (module docstring: dispatch policy, breaker, migration).

    ``route`` is the batch surface mirroring ``serve_detailed``: one
    ``RequestResult`` per request, in order, never raising — now with
    ``migrated`` / ``replica`` metadata filled in. Each round the
    partitioner assigns every unfinished request to a healthy replica,
    one worker thread per replica runs ``serve_detailed`` under
    ``flight.replica_tag(i)``, and device-lost sessions re-enter the
    next round on a different replica.

    Replicas must NOT be shared with concurrent callers: the router
    owns their scheduler. ``route`` itself is synchronous and not
    reentrant (one in-flight call per router).

    ``heartbeat_stale_s`` (opt-in): the router re-wires each replica's
    ``on_heartbeat``/``heartbeat_s`` so beats land in router health
    state, and a mid-round replica whose beats stop for this long is
    taken over (module docstring). Leave ``None`` on cold-compile-heavy
    runs — a first-route compile pause is indistinguishable from a
    hang.
    """

    def __init__(self, replicas, *, fault_threshold: int = 1,
                 probe_budget: int = 4, probe_base_delay_s: float = 0.25,
                 jitter_seed: int = 0,
                 affinity_min_tokens: int | None = None,
                 affinity_max_extra_ticks: int | None = None,
                 heartbeat_stale_s: float | None = None,
                 max_failover_rounds: int | None = None,
                 prefill_replicas: int = 0,
                 sleep=time.sleep):
        if not replicas:
            raise ValueError("need at least one replica")
        self.replicas = list(replicas)
        n = len(self.replicas)
        # the fleet must agree on the KV pool dtype (ISSUE 16): a
        # handoff/migration between an int8 and a bf16 replica would
        # decline every payload (import_prefix's kv_dtype stamp), so a
        # mixed fleet silently degrades every migration to full replay
        # — refuse it at construction instead. Prefill and decode
        # tiers are both replicas here, so this covers the
        # disagg-prefill seam too.
        dts = {getattr(r, "kv_dtype", "bf16") for r in self.replicas}
        if len(dts) > 1:
            raise ValueError(
                f"all replicas must share one kv_dtype, got {sorted(dts)}")
        self.kv_dtype = next(iter(dts))
        # disaggregated prefill: replicas [0, prefill_replicas) form the
        # prefill tier — sessions placed there always migrate to a
        # decode replica right after their prompt finishes prefilling,
        # carrying the finished KV blocks as a host-tier handoff
        # (export_prefix -> import_prefix) instead of a token replay.
        # At least one decode replica must remain.
        if not 0 <= prefill_replicas < n:
            raise ValueError(f"prefill_replicas must be in [0, {n}), got "
                             f"{prefill_replicas}")
        self.prefill_replicas = prefill_replicas
        self._prefill_set = frozenset(range(prefill_replicas))
        self.fault_threshold = fault_threshold
        self.probe_budget = probe_budget
        self.probe_base_delay_s = probe_base_delay_s
        self.jitter_seed = jitter_seed
        self.heartbeat_stale_s = heartbeat_stale_s
        self.max_failover_rounds = (max_failover_rounds
                                    if max_failover_rounds is not None else n)
        # affinity knobs: a match shorter than one block can't skip any
        # prefill; a warm replica more than ~one full row of ticks ahead
        # of the least-loaded loses the request (module docstring).
        # t_max stays the right ceiling even though load is accumulated
        # in width-weighted tick equivalents (ISSUE 19) — those only
        # ever price a tick at or below its full-width cost
        self.affinity_min_tokens = (affinity_min_tokens
                                    if affinity_min_tokens is not None
                                    else self.replicas[0].bt)
        self.affinity_max_extra_ticks = (
            affinity_max_extra_ticks if affinity_max_extra_ticks is not None
            else self.replicas[0].t_max)
        self._sleep = sleep
        self._breakers = [CircuitBreaker(
            fault_threshold=fault_threshold, probe_budget=probe_budget,
            probe_base_delay_s=probe_base_delay_s,
            jitter_seed=jitter_seed + i) for i in range(n)]
        self._busy = [False] * n      # a worker (possibly zombie) holds it
        # per-replica retirement latch (ISSUE 20): flipping it mid-round
        # drains that one replica (its serve_detailed sees `preempted`)
        # without touching the cluster drain; the round classifier
        # migrates its cut sessions to survivors
        self._retiring = [False] * n
        self._last_beat: list[float | None] = [None] * n
        self._last_snap: list[dict | None] = [None] * n
        self._threads: list[threading.Thread] = []
        self.routed_per_replica = [0] * n
        self.stats = {"routed": 0, "affinity_routed": 0, "rounds": 0,
                      "failovers": 0, "migrations": 0, "full_replays": 0,
                      "failover_sheds": 0, "takeovers": 0, "probes": 0,
                      "probe_successes": 0, "unplaceable": 0,
                      "prefill_hops": 0, "handoffs": 0,
                      "handoff_fallbacks": 0,
                      # journal recovery at the router layer (ISSUE 15):
                      # sessions resumed from a previous process's log,
                      # completions returned without device work, and
                      # the emitted tokens re-entered as replay prefix
                      "journal_recovered": 0, "journal_deduped": 0,
                      "journal_replay_tokens": 0,
                      # elastic membership (ISSUE 20): replicas retired
                      # from / added to the fleet, and sessions a
                      # retirement drain migrated to survivors (these
                      # also count under "migrations")
                      "retired": 0, "added": 0, "retire_migrations": 0}
        for i, rep in enumerate(self.replicas):
            self._wire_heartbeat(i, rep)

    # ---- health ------------------------------------------------------------

    def _wire_heartbeat(self, i: int, rep) -> None:
        prev = rep.on_heartbeat

        def beat(snap, _i=i, _prev=prev):
            self._last_beat[_i] = time.monotonic()
            self._last_snap[_i] = snap
            if _prev is not None:
                _prev(snap)

        rep.on_heartbeat = beat
        if self.heartbeat_stale_s is not None:
            want = max(0.05, self.heartbeat_stale_s / 4)
            if rep.heartbeat_s is None or rep.heartbeat_s > want:
                rep.heartbeat_s = want

    def breaker_states(self) -> list[str]:
        return [b.state for b in self._breakers]

    def healthy_replicas(self) -> list[int]:
        return [i for i, b in enumerate(self._breakers)
                if b.healthy and not self._busy[i]]

    def active_replicas(self) -> list[int]:
        """Fleet members in ANY state but RETIRED — the set the elastic
        controller sizes, walks, and replaces over. (Healthy is a
        dispatch property; active is a membership property.)"""
        return [i for i, b in enumerate(self._breakers)
                if b.state != RETIRED]

    # ---- membership (ISSUE 20) ---------------------------------------------

    def retire_replica(self, i: int) -> None:
        """Remove replica ``i`` from the fleet: no new placements, no
        probes, and if a round is in flight its worker drains NOW (the
        per-replica latch reads as ``preempted`` inside that replica's
        ``serve_detailed`` only) — in-flight rows finish, queued work
        sheds, and the round classifier re-enters every cut session on
        the survivors, token-identically (``_sub_request``'s
        continuation path: a retirement is a PLANNED failover).
        Retirement is terminal for the probe machinery — an operator
        ``probe_replica`` cannot revive a replaced member (the race the
        unit tests pin); only :meth:`readmit_replica`, the explicit
        re-admission step of the controller's upgrade walk, returns a
        retired replica to dispatch. Idempotent. Indices are stable:
        the slot is never reused, its lists just go quiet."""
        b = self._breakers[i]
        if b.state == RETIRED:
            return
        self._retiring[i] = True
        b.state = RETIRED
        b.retry_at = None
        self.stats["retired"] += 1
        instant("replica_retired", replica=i)
        flight.record("replica_retired", replica=i,
                      busy=self._busy[i])

    def readmit_replica(self, i: int) -> None:
        """Return a RETIRED replica to dispatch (the upgrade walk's
        re-admission: sessions were drained off, weights reloaded, and
        the replica is warm again). No-op unless retired."""
        b = self._breakers[i]
        if b.state != RETIRED:
            return
        self._retiring[i] = False
        b.state = CLOSED
        b.consecutive = 0
        b._k = 0
        b.retry_at = None
        instant("replica_readmitted", replica=i)
        flight.record("replica_readmitted", replica=i)

    def add_replica(self, rep, *, prefill: bool = False) -> int:
        """Grow the fleet by one warm replica (scale-up, or the
        replacement for a retired/DEAD member) and return its index.
        The new member enters with a CLOSED breaker and receives
        traffic from the next placement on. Same-``kv_dtype`` is
        enforced exactly as at construction. Append order matters: the
        breaker lands LAST because ``healthy_replicas``/``_partition``
        enumerate ``self._breakers`` — every parallel per-index list
        must already hold index ``i`` when it becomes visible."""
        if getattr(rep, "kv_dtype", "bf16") != self.kv_dtype:
            raise ValueError(
                f"all replicas must share one kv_dtype, got "
                f"{getattr(rep, 'kv_dtype', 'bf16')!r} vs "
                f"{self.kv_dtype!r}")
        i = len(self.replicas)
        self.replicas.append(rep)
        self._busy.append(False)
        self._retiring.append(False)
        self._last_beat.append(None)
        self._last_snap.append(None)
        self.routed_per_replica.append(0)
        self._wire_heartbeat(i, rep)
        if prefill:
            self._prefill_set = frozenset(self._prefill_set | {i})
        self._breakers.append(CircuitBreaker(
            fault_threshold=self.fault_threshold,
            probe_budget=self.probe_budget,
            probe_base_delay_s=self.probe_base_delay_s,
            jitter_seed=self.jitter_seed + i))
        self.stats["added"] += 1
        instant("replica_added", replica=i, prefill=prefill)
        flight.record("replica_added", replica=i, prefill=prefill)
        return i

    def stats_snapshot(self) -> dict:
        """Router counters + per-replica breaker/health/engine state —
        the cluster-level extension of the per-batcher snapshot."""
        now = time.monotonic()
        return {
            "router": dict(self.stats),
            "routed_per_replica": list(self.routed_per_replica),
            "replicas": [{
                "breaker": b.state,
                "consecutive_faults": b.consecutive,
                "breaker_trips": b.trips,
                "busy": self._busy[i],
                "heartbeat_age_s": (None if self._last_beat[i] is None
                                    else now - self._last_beat[i]),
                "engine": self._last_snap[i],
            } for i, b in enumerate(self._breakers)],
        }

    def join_stragglers(self, timeout: float | None = None) -> None:
        """Join worker threads left behind by takeovers (tests call
        this so a zombie can't race the next route)."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        for t in self._threads:
            t.join(None if deadline is None
                   else max(0.0, deadline - time.monotonic()))
        self._threads = [t for t in self._threads if t.is_alive()]

    # ---- probes ------------------------------------------------------------

    def _canary_request(self) -> Request:
        # single-token greedy probe: head (tokens[:-1]) is empty, so a
        # canary never pollutes the radix cache it is probing
        return Request(tokens=[0], max_new=1)

    def _canary_once(self, i: int) -> None:
        res = self.replicas[i].serve_detailed([self._canary_request()])
        if not res[0].ok:
            raise RuntimeError(res[0].error or res[0].status)

    def _auto_probe(self, now: float) -> None:
        """One canary per OPEN replica whose backoff delay has elapsed —
        the half-open state machine the partitioner consults."""
        for i, b in enumerate(self._breakers):
            if not b.probe_due(now) or self._busy[i]:
                continue
            b.begin_probe()
            self.stats["probes"] += 1
            try:
                self._canary_once(i)
            except Exception as e:   # noqa: BLE001 — any fault re-opens
                flight.record("replica_probe", replica=i, ok=False,
                              error=f"{type(e).__name__}: {e}")
                b.record_fault(time.monotonic())
                continue
            flight.record("replica_probe", replica=i, ok=True)
            self.stats["probe_successes"] += 1
            b.record_ok()

    def probe_replica(self, i: int) -> bool:
        """Blocking operator probe: drive up to ``probe_budget`` canary
        attempts through ``elastic.retry_with_backoff`` (deterministic
        schedule, per-replica jitter seed). Success re-closes the
        breaker — including a DEAD one, which auto-probing never
        revives; failure records a fault and returns False. A RETIRED
        replica always returns False without a canary: it was removed
        on purpose (likely already replaced), so reviving it would
        double capacity behind the controller's back — membership
        changes go through retire/add/readmit, not probes."""
        if self._busy[i] or self._breakers[i].state == RETIRED:
            return False
        self.stats["probes"] += 1
        try:
            retry_with_backoff(
                lambda: self._canary_once(i), budget=self.probe_budget,
                base_delay=self.probe_base_delay_s,
                jitter_seed=self.jitter_seed + i, sleep=self._sleep)
        except Exception as e:   # noqa: BLE001 — budget exhausted
            flight.record("replica_probe", replica=i, ok=False,
                          error=f"{type(e).__name__}: {e}")
            self._breakers[i].record_fault(time.monotonic())
            return False
        flight.record("replica_probe", replica=i, ok=True)
        self.stats["probe_successes"] += 1
        self._breakers[i].record_ok()
        return True

    # ---- dispatch policy ---------------------------------------------------

    def _tpot_scale(self, i: int) -> float:
        """Observed mean TPOT from the replica's last snapshot, as a
        relative speed weight (1.0 with no signal yet) — a straggler
        replica's backlog costs proportionally more."""
        snap = self._last_snap[i] or {}
        try:
            tpot = snap["slo"]["tpot_s"]
            if tpot.get("count", 0) > 0 and tpot.get("mean"):
                return max(tpot["mean"], 1e-9)
        except (KeyError, TypeError):
            pass
        return 1.0

    def _partition(self, order: list[int], sessions: list[_Session]
                   ) -> dict[int, list[int]] | None:
        """Assign every request in ``order`` to a healthy replica:
        radix-affinity first, yielding to least-loaded when the warm
        replica is too far ahead (module docstring). Returns
        ``{replica: [request indices]}`` or None when no replica is
        placeable."""
        healthy = self.healthy_replicas()
        if not healthy:
            return None
        # tier split: prefill-phase sessions go to healthy prefill
        # replicas, everything else to the decode tier; either tier
        # empty degrades to the full healthy set (unified behaviour)
        h_pre = [i for i in healthy if i in self._prefill_set]
        h_dec = [i for i in healthy if i not in self._prefill_set]
        load = {i: 0.0 for i in healthy}    # assigned ticks this round
        scale = {i: self._tpot_scale(i) for i in healthy}
        out: dict[int, list[int]] = {}
        for j in order:
            sess = sessions[j]
            cand = (h_pre if sess.phase == "prefill" and h_pre
                    else (h_dec or healthy))
            cont = list(sess.req.tokens) + list(sess.tokens)
            remaining = max(1, sess.req.max_new - len(sess.tokens))
            best_aff, aff_len = None, 0
            for i in cand:
                m = self.replicas[i].prefix_match_len(cont)
                if m > aff_len:
                    best_aff, aff_len = i, m
            least = min(cand, key=lambda i: (load[i] * scale[i], i))
            target = least
            if (best_aff is not None
                    and aff_len >= self.affinity_min_tokens
                    and load[best_aff] - load[least]
                    <= self.affinity_max_extra_ticks):
                target = best_aff
                self.stats["affinity_routed"] += 1
            rep = self.replicas[target]
            suffix = max(0, len(cont) - 1
                         - (aff_len if target == best_aff else 0))
            # load_estimate, not _rounded_need: a speculating replica's
            # decode cost is verify dispatches (k+1 ticks each) scaled
            # by its measured acceptance rate, not segment-rounded ticks.
            # prefill_cost, not raw suffix length: a chunking replica
            # pays ceil(suffix/chunk) admission waves, not one wave per
            # token — raw tokens would systematically overprice
            # long-prompt placements there (unchunked returns suffix
            # unchanged). Both estimates come back in FULL-WIDTH tick
            # equivalents: each replica weights its tick count by its
            # CURRENT width-bucket rung over the full horizon
            # (ContinuousBatcher._width_fraction, ISSUE 19), so a
            # replica serving short sessions — whose per-tick KV gather
            # is a fraction of t_max — undercuts one already stretched
            # wide by a long session, and the mixed fleet stops pricing
            # every tick as if it gathered the horizon
            load[target] += rep.prefill_cost(suffix) \
                + rep.load_estimate(remaining)
            out.setdefault(target, []).append(j)
            self.routed_per_replica[target] += 1
        return out

    def _sub_request(self, sess: _Session, rep, now: float) -> Request:
        """The Request actually submitted to ``rep`` for this session's
        next placement. First placement submits the original verbatim;
        a migration submits the token-identical continuation (or full
        replay when the continuation outgrows the replica's prompt
        window), with the REMAINING wall budget as its deadline."""
        base = sess.req
        if sess.rounds == 0 and not sess.tokens:
            return base
        cont = list(base.tokens) + list(sess.tokens)
        remaining = base.max_new - len(sess.tokens)
        if sess.tokens and (len(cont) > rep.Tb or remaining < 1):
            # prompt + partial no longer fits this replica's prompt
            # window: discard the partial and replay from the original
            # prompt — same seed, same stream, just recomputed
            self.stats["full_replays"] += 1
            sess.tokens = []
            cont = list(base.tokens)
            remaining = base.max_new
        deadline = None
        if sess.deadline_at is not None:
            deadline = max(1e-3, sess.deadline_at - now)
        return replace(base, tokens=cont, max_new=remaining,
                       deadline_s=deadline,
                       arrival_s=max(0.0, sess.arrive_abs - now))

    # ---- the routing loop --------------------------------------------------

    def route(self, requests: list[Request], *, drain=None,
              drain_deadline_s: float | None = None,
              chaos: dict | None = None,
              recovery=None) -> list[RequestResult]:
        """Serve ``requests`` across the replica set; one
        :class:`RequestResult` per request, in order, never raising.
        ``drain`` is the cluster-wide SIGTERM latch (shared with every
        replica); ``chaos`` maps replica index -> ``ChaosInjector`` for
        drills.

        ``recovery`` — a ``serve_journal.RecoveryManifest`` from a
        previous process's journal: journal-completed requests dedup
        by id (recorded stream, zero device work), journal-incomplete
        ones enter round 0 with their emitted tokens as session state,
        so the normal migration machinery replays them token-
        identically (``_sub_request``'s continuation path — a recovery
        IS a migration whose source replica was the dead process)."""
        t0 = time.monotonic()
        n = len(requests)
        results: list[RequestResult | None] = [None] * n
        rec_sessions = getattr(recovery, "sessions", None) or {}
        sessions: list[_Session] = []
        for j, r in enumerate(requests):
            # materialise identity AND the single-batcher seed default
            # (seed = index in the call) up front, so partitioning,
            # migration and journal replay can never change a stream
            rid = getattr(r, "request_id", None) or f"req-{j}"
            if r.temperature > 0 and r.seed is None:
                r = replace(r, seed=j, request_id=rid)
            elif r.request_id != rid:
                r = replace(r, request_id=rid)
            rsess = rec_sessions.get(rid)
            if (rsess is not None and not rsess.completed
                    and getattr(rsess, "seed", None) is not None
                    and r.seed != rsess.seed):
                # the journaled admission seed is the stream's truth
                r = replace(r, seed=rsess.seed)
            sess = _Session(
                req=r, arrive_abs=t0 + getattr(r, "arrival_s", 0.0),
                deadline_at=(t0 + r.deadline_s
                             if r.deadline_s is not None else None),
                # single-token prompts have nothing to prefill; a
                # max_new=1 request finishes inside its prefill hop
                # anyway, so skipping the tier saves it a migration
                phase=("prefill" if self._prefill_set
                       and len(r.tokens) > 1 and r.max_new > 1
                       else "decode"))
            if rsess is not None and rsess.prompt is not None:
                if rsess.completed:
                    # exactly-once emission across the crash
                    self.stats["journal_deduped"] += 1
                    results[j] = RequestResult(
                        status=rsess.status,
                        tokens=list(rsess.emitted), error=rsess.error,
                        request_id=rid)
                elif rsess.emitted:
                    emitted = [int(t) for t in rsess.emitted]
                    self.stats["journal_recovered"] += 1
                    self.stats["journal_replay_tokens"] += len(emitted)
                    instant("journal_session_replay", request_id=rid,
                            emitted=len(emitted))
                    if len(emitted) >= r.max_new:
                        # budget already filled on disk — the crash hit
                        # between the last delta and the end frame
                        results[j] = RequestResult(
                            status=OK, tokens=emitted[:r.max_new],
                            request_id=rid)
                    else:
                        sess.tokens = emitted
                        sess.recoveries = 1
                        sess.phase = "decode"
            sessions.append(sess)
        self.stats["routed"] += n

        def finalize(j: int, i: int | None, r: RequestResult,
                     now: float) -> None:
            if results[j] is not None:
                return                      # first terminal event wins
            sess = sessions[j]
            if sess.migrated == 0 and not sess.tokens:
                results[j] = replace(r, replica=i,   # untouched fast path
                                     request_id=sess.req.request_id)
                return
            tokens = list(sess.tokens) + list(r.tokens)
            latency = max(0.0, now - sess.arrive_abs)
            ttft = sess.ttft_s
            tpot = ((latency - ttft) / (len(tokens) - 1)
                    if ttft is not None and len(tokens) > 1 else None)
            results[j] = RequestResult(
                status=r.status, tokens=tokens, error=r.error,
                ticks=sess.ticks + r.ticks, latency_s=latency,
                recoveries=sess.recoveries + r.recoveries,
                cached_prefix_tokens=sess.cached_prefix
                + r.cached_prefix_tokens,
                queue_wait_s=sess.queue_wait_s, ttft_s=ttft, tpot_s=tpot,
                max_gap_s=_longer(sess.max_gap_s, r.max_gap_s),
                deliveries=sess.deliveries + r.deliveries,
                migrated=sess.migrated, replica=i,
                request_id=sess.req.request_id)

        def shed_for(j: int, why: str, now: float,
                     drain_cut: bool = False) -> None:
            sess = sessions[j]
            if sess.tokens:
                status = CANCELLED if drain_cut else TIMEOUT
            else:
                status = SHED
            finalize(j, None, RequestResult(status=status, error=why), now)

        pending = [j for j in range(n) if results[j] is None]
        rounds = 0
        while pending:
            now = time.monotonic()
            if drain is not None and getattr(drain, "preempted", False):
                # cluster is stopping: never re-place work after drain
                for j in pending:
                    shed_for(j, "shed: cluster drain", now, drain_cut=True)
                break
            self._auto_probe(now)
            placement = self._partition(pending, sessions)
            if placement is None:
                msg = (f"no healthy replica "
                       f"({self.breaker_states().count(CLOSED)} of "
                       f"{len(self.replicas)} closed)")
                self.stats["unplaceable"] += len(pending)
                for j in pending:
                    # finalize merges sessions[j].tokens in — partial
                    # streams from the dead placement are never lost
                    finalize(j, None,
                             RequestResult(status=FAILED, error=msg), now)
                break
            if rounds > self.max_failover_rounds:
                for j in pending:
                    finalize(j, None, RequestResult(
                        status=FAILED,
                        error=f"failover round budget exhausted "
                              f"({self.max_failover_rounds})"), now)
                break
            pending = self._run_round(placement, sessions, finalize,
                                      shed_for, t0, drain,
                                      drain_deadline_s, chaos or {})
            rounds += 1
            self.stats["rounds"] += 1
        for j in range(n):
            if results[j] is None:      # defensive: never return holes
                finalize(j, None, RequestResult(
                    status=FAILED, error="not routed (router bug)"),
                    time.monotonic())
        return results

    def _run_round(self, placement, sessions, finalize, shed_for, t0,
                   drain, drain_deadline_s, chaos) -> list[int]:
        """Dispatch one placement round (one worker thread per replica,
        each under its ``flight.replica_tag``), classify the results,
        and return the request indices that must re-enter the next
        round (device-lost / taken-over sessions within deadline)."""
        now = time.monotonic()
        outs: dict[int, list] = {}
        errs: dict[int, BaseException] = {}
        threads: dict[int, threading.Thread] = {}
        hops: dict[int, set[int]] = {}
        # retirement state CAPTURED by each worker as it exits: an
        # upgrade thread gating on `not _busy[i]` may readmit (clear
        # the latch) before this round's classification runs, and the
        # replica's shed sessions must still migrate, not finalise
        retired_at_exit: dict[int, bool] = {}
        round_start = now
        for i, idxs in placement.items():
            subs = []
            for j in idxs:
                sub = self._sub_request(sessions[j], self.replicas[i], now)
                if i in self._prefill_set \
                        and sessions[j].phase == "prefill":
                    # prefill-tier placement: run the prompt's prefill
                    # plus ONE decode tick (the token TTFT measures),
                    # then hop the session to the decode tier
                    sub = replace(sub, max_new=1)
                    hops.setdefault(i, set()).add(j)
                subs.append(sub)
            for j in idxs:
                sessions[j].rounds += 1

            def work(_i=i, _subs=subs):
                with flight.replica_tag(_i):
                    try:
                        outs[_i] = self.replicas[_i].serve_detailed(
                            _subs, drain=_ReplicaDrain(self, _i, drain),
                            drain_deadline_s=drain_deadline_s,
                            chaos=chaos.get(_i))
                    except BaseException as e:  # noqa: BLE001
                        errs[_i] = e
                    finally:
                        retired_at_exit[_i] = self._retiring[_i]
                        self._busy[_i] = False

            self._busy[i] = True
            t = threading.Thread(target=work, daemon=True,
                                 name=f"dcp-router-replica{i}")
            threads[i] = t
            self._threads.append(t)
            t.start()

        taken: set[int] = set()
        while True:
            live = {i: t for i, t in threads.items()
                    if i not in taken and t.is_alive()}
            if not live:
                break
            for i, t in live.items():
                t.join(0.02)
                if not t.is_alive() or self.heartbeat_stale_s is None:
                    continue
                beat = self._last_beat[i]
                ref = beat if (beat is not None and beat > round_start) \
                    else round_start
                if time.monotonic() - ref > self.heartbeat_stale_s:
                    # scheduler thread stopped beating and has no
                    # watchdog of its own: declare the replica dead and
                    # take its whole assignment; whatever the zombie
                    # eventually returns is discarded (_busy stays held
                    # until its thread actually exits)
                    taken.add(i)
                    self.stats["takeovers"] += 1

        next_pending: list[int] = []
        # SLO offsets for migrated sessions: a sub-call measures
        # queue-wait/TTFT from ITS OWN start, so shift by the round's
        # offset from the route call (≈0 for round 0)
        slo_base = round_start - t0
        for i, idxs in placement.items():
            now = time.monotonic()
            if i in taken or i in errs:
                why = (f"heartbeat stale > {self.heartbeat_stale_s}s"
                       if i in taken else
                       f"{type(errs[i]).__name__}: {errs[i]}")
                self._fail_over(i, idxs, [], sessions, why, now, slo_base,
                                shed_for, next_pending)
                continue
            res = outs.get(i, [])
            hop = hops.get(i, set())
            # retirement drain (ISSUE 20): the per-replica latch cut
            # this replica's round short. Its SHED/CANCELLED results
            # are not failures — they are the planned half of a
            # migration, so they re-enter the next round on survivors
            # with their partial streams banked (unless the CLUSTER is
            # draining too, in which case finalising wins: nobody will
            # serve them anyway)
            retiring = (retired_at_exit.get(i, self._retiring[i])
                        and not (drain is not None
                                 and getattr(drain, "preempted", False)))
            faulted: list[tuple[int, RequestResult]] = []
            for j, r in zip(idxs, res):
                if (r.status == FAILED and r.error
                        and DEVICE_LOST_MARKER in r.error):
                    faulted.append((j, r))
                    continue
                sess = sessions[j]
                if sess.queue_wait_s is None and r.queue_wait_s is not None:
                    sess.queue_wait_s = slo_base + r.queue_wait_s
                if sess.ttft_s is None and r.ttft_s is not None:
                    sess.ttft_s = slo_base + r.ttft_s
                if (retiring and r.status in (SHED, CANCELLED)
                        and not (sess.deadline_at is not None
                                 and now >= sess.deadline_at)):
                    sess.bank(r)
                    sess.migrated += 1
                    self.stats["migrations"] += 1
                    self.stats["retire_migrations"] += 1
                    next_pending.append(j)
                    continue
                eos = self.replicas[i].eos_id
                if (j in hop and r.status == OK
                        and len(sess.tokens) + len(r.tokens)
                        < sess.req.max_new
                        and not (eos is not None and r.tokens
                                 and r.tokens[-1] == eos)):
                    # prompt prefilled, first token out, budget left:
                    # hop to the decode tier carrying the finished KV
                    # blocks (a planned move — not a migration)
                    sess.bank(r)
                    sess.phase = "decode"
                    self.stats["prefill_hops"] += 1
                    self._handoff(i, sess)
                    next_pending.append(j)
                    continue
                finalize(j, i, r, now)
            if faulted:
                self._fail_over(i, [j for j, _ in faulted],
                                faulted, sessions,
                                faulted[0][1].error, now, slo_base,
                                shed_for, next_pending)
            elif res:
                self._breakers[i].record_ok()
        return next_pending

    def _fail_over(self, i: int, idxs: list[int], faulted, sessions,
                   why: str, now: float, slo_base: float, shed_for,
                   next_pending) -> None:
        """Replica ``i`` is gone mid-round: record the fault, open its
        breaker, accumulate the partial streams the dead replica
        reported, and queue every in-deadline session for migration —
        dumping a flight artifact that names the dead replica and the
        migrated sessions."""
        self.stats["failovers"] += 1
        self._breakers[i].record_fault(now)
        partials = dict(faulted)
        migrated: list[int] = []
        for j in idxs:
            sess = sessions[j]
            r = partials.get(j)
            if r is not None:
                # the dead replica's partial stream is host-known and
                # exact — migration continues from it
                if sess.ttft_s is None and r.ttft_s is not None:
                    sess.ttft_s = slo_base + r.ttft_s
                sess.bank(r)
            if sess.deadline_at is not None and now >= sess.deadline_at:
                self.stats["failover_sheds"] += 1
                shed_for(j, f"deadline expired during failover of "
                            f"replica {i}", now)
                continue
            sess.migrated += 1
            self.stats["migrations"] += 1
            migrated.append(j)
            next_pending.append(j)
        flight.record("replica_failover", replica=i, error=why,
                      sessions=migrated)
        flight.dump_on_fault("replica_failover", fault=why, replica=i,
                             migrated=migrated,
                             breaker=self._breakers[i].state)

    def _handoff(self, i: int, sess: _Session) -> None:
        """Move prefill replica ``i``'s finished KV blocks for this
        session to a decode replica: export the prompt-prefix entry
        from ``i``'s radix/tier (D2H or straight from its spill tier),
        import it into the warmest — then least-routed — healthy decode
        replica, whose own radix now holds the prefix so the next
        round's affinity probe routes the continuation there. Any miss
        (no exportable entry, CRC/shape decline, pool pressure) is a
        fallback, not an error: the decode replica simply re-prefills
        the token-identical continuation (replay)."""
        cont = list(sess.req.tokens) + list(sess.tokens)
        # version-aware dispatch (ISSUE 20): mid-rolling-upgrade the
        # fleet transiently holds two weights_versions, and a
        # cross-version import would decline anyway (the payload
        # stamp) — skip those targets up front so the export D2H is
        # never wasted; no same-version target just means replay
        src_wv = getattr(self.replicas[i], "weights_version", 0)
        targets = [t for t in self.healthy_replicas()
                   if t not in self._prefill_set
                   and getattr(self.replicas[t], "weights_version", 0)
                   == src_wv]
        ok, target = False, None
        if targets:
            target = max(targets, key=lambda t: (
                self.replicas[t].prefix_match_len(cont),
                -self.routed_per_replica[t], -t))
            try:
                payload = self.replicas[i].export_prefix(cont)
                ok = self.replicas[target].import_prefix(payload)
            except Exception:  # noqa: BLE001 — handoff is best-effort
                ok = False
        if ok:
            self.stats["handoffs"] += 1
            flight.record("prefill_handoff", src=i, dst=target,
                          n_tokens=len(cont) - 1)
        else:
            self.stats["handoff_fallbacks"] += 1
            instant("prefill_handoff_fallback", src=i, dst=target,
                    n_tokens=len(cont) - 1)
            flight.record("prefill_handoff_fallback", src=i, dst=target,
                          n_tokens=len(cont) - 1)
