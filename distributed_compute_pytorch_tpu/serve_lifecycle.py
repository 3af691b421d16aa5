"""Serve fault-tolerance primitives: request lifecycle, chaos injection,
and the tick watchdog (the serving-side half of ``train/elastic.py``).

The continuous batcher (``serve.ContinuousBatcher``) was — until this
module — all-or-nothing: one ``serve(requests)`` call, and a single
device error, hung tick, or poison request destroyed every in-flight
session. The ROADMAP's north star (heavy traffic) needs the serving
layer to degrade PER REQUEST, not per process. The pieces here give the
batcher's scheduler the vocabulary for that:

- :class:`RequestResult` — the structured per-request outcome
  (``status: ok | failed | timeout | cancelled | shed``, partial tokens,
  error text, tick/latency metadata) that ``serve_detailed`` returns
  instead of raising away a whole call. A result always carries
  whatever tokens were harvested before the terminal event, so no
  completed work is discarded.
- :class:`ChaosInjector` — injectable tick exceptions, hangs, slow
  ticks, and poison rows: the serving extension of the trainer's
  ``--fault_at_step``/``--fault_mode`` pattern (``train/elastic.py``),
  gated by SEGMENT count instead of step count. Every recovery path in
  the batcher is exercised through these hooks in tests
  (``tests/test_serve_faults.py``); production runs never construct one.
- :func:`fetch_with_timeout` (via ``train/elastic.call_with_timeout``)
  — the tick watchdog: the per-segment token harvest is the only
  device->host read in the serve loop, so a dead or wedged device
  surfaces there. Bounding that fetch turns "hung forever" into a
  typed :class:`TickTimeout` the scheduler can recover from by
  reconstruction (``serve.py`` module docstring, "Serving under
  failure" in DESIGN.md).

Status vocabulary (``RequestResult.status``):

``ok``          completed (eos or budget), tokens are the full stream.
``failed``      validation failure, horizon infeasibility, or an
                unrecoverable device fault attributed to the request.
``timeout``     the request's wall-clock ``deadline_s`` expired; tokens
                hold the partial stream generated before expiry.
``cancelled``   ``ContinuousBatcher.cancel()`` or the drain deadline
                cut it off; tokens hold the partial stream.
``shed``        rejected cheaply at submission (bounded admission
                ``max_pending`` overflow) or at drain start — zero
                device work was spent on it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

from distributed_compute_pytorch_tpu.obs import flight

# terminal request states (RequestResult.status)
OK = "ok"
FAILED = "failed"
TIMEOUT = "timeout"
CANCELLED = "cancelled"
SHED = "shed"
STATUSES = (OK, FAILED, TIMEOUT, CANCELLED, SHED)


class InjectedFault(RuntimeError):
    """A chaos-injected device failure (stands in for the XLA runtime
    error a real dead chip raises at the harvest fetch)."""


class TickTimeout(RuntimeError):
    """The per-segment token harvest exceeded the tick watchdog budget —
    the serving-side signature of a hung device/collective (from inside
    the process a hang is indistinguishable from a long tick, exactly
    the failure-detection gap ``train/elastic.Heartbeat`` closes for
    training; the watchdog closes it for serving)."""


@dataclass
class RequestResult:
    """Structured outcome of one request through ``serve_detailed``.

    ``tokens`` is ALWAYS meaningful: the full stream for ``ok``, the
    partial stream already harvested for ``timeout``/``cancelled``, and
    ``[]`` for requests that never produced device work (``shed``,
    validation ``failed``). ``ticks`` counts decode ticks charged to the
    request (plan-attributed at dispatch, so overlap tail waste after
    eos is excluded); ``latency_s`` is wall time from submission to the
    terminal event; ``recoveries`` counts how many session
    reconstructions this request's row lived through (0 on a clean
    run); ``cached_prefix_tokens`` is how many prompt tokens ATTACHED
    to the radix prefix cache instead of re-running prefill (0 with
    the cache off — the paged KV pool's per-request observability,
    surfaced as ``"cached_prefix"`` on every ``dcp-serve`` output
    line).

    SLO timing (ISSUE 8 / the ROADMAP-3 router's dispatch signals; all
    wall-clock seconds, measured from the request's ARRIVAL — its
    ``arrival_s`` offset into the serve call, 0 for the legacy
    everything-at-submission shape, so ``latency_s`` is unchanged for
    existing callers): ``queue_wait_s`` is arrival -> admission (its
    prefill wave's dispatch); ``ttft_s`` is arrival -> the first
    harvested token reaching the host (``None`` when no token was ever
    produced); ``tpot_s`` is the mean per-token interval AFTER the
    first token, ``(latency_s - ttft_s) / (len(tokens) - 1)``
    (``None`` below 2 tokens). Every admitted request's values also
    land in the batcher's SLO histograms
    (``ContinuousBatcher.stats_snapshot()["slo"]``).

    What ``tpot_s`` averages away: ``deliveries`` counts the harvests
    that handed this request new tokens (a DELIVERY: up to a segment's
    tokens reach the host at one instant) and ``max_gap_s`` is the
    longest interval between two consecutive ones (``None`` with fewer
    than two; the interval before the first is ``ttft_s``'s) — the
    freeze a streaming client saw behind somebody else's prefill, which
    a request that then caught up hides in its mean. Every gap also
    lands in the ``stats`` counters ``deliveries_*`` / ``delivery_gap_*``,
    split by whether the device ran admission between the two segments
    that delivered; ``stats_snapshot()["slo"]["delivery_gap_s"]`` is
    their digest. Through the router both cover the request's placements
    (the gap ACROSS a migration or hop is not stamped).

    Replica-set metadata (set by ``serve_router.ServeRouter``; inert
    for direct single-batcher callers): ``migrated`` counts how many
    times the request's session was replayed onto a DIFFERENT replica
    after its original replica died (0 = never left its first
    placement), and ``replica`` is the replica index that produced the
    terminal result (``None`` outside the router)."""

    status: str = OK
    tokens: list = field(default_factory=list)
    error: str | None = None
    ticks: int = 0
    latency_s: float = 0.0
    recoveries: int = 0
    cached_prefix_tokens: int = 0
    queue_wait_s: float | None = None
    ttft_s: float | None = None
    tpot_s: float | None = None
    max_gap_s: float | None = None
    deliveries: int = 0
    migrated: int = 0
    replica: int | None = None
    # the request's stable identity (ISSUE 15): set from
    # ``Request.request_id`` (or the engine's positional default) so
    # journal recovery can dedup completed work by id, not by position
    request_id: str | None = None

    @property
    def ok(self) -> bool:
        return self.status == OK


@dataclass
class ChaosInjector:
    """Deterministic fault injection for the serve loop.

    ``fault_at_segment`` arms the injector: when the batcher has
    dispatched that many segments, the NEXT harvest trips ``fault_mode``
    (``--fault_at_step`` for serving, counted in segments because the
    segment is the serve loop's unit of device work):

    - ``raise``: the harvest raises :class:`InjectedFault` — a crashed
      device program. Recoverable by session reconstruction.
    - ``hang``: the harvest blocks for ``hang_s`` seconds INSIDE the
      fetch (so the tick watchdog, waiting outside, fires first). A real
      hang is unbounded; the finite ``hang_s`` keeps leaked watchdog
      threads from wedging the test process — see
      ``elastic.call_with_timeout``.
    - ``slow``: the harvest sleeps ``slow_s`` then succeeds — a
      stragglers/preemption-pressure tick. Must NOT trigger recovery
      when it stays under the watchdog budget.
    - ``poison``: every harvest whose dispatched plan contains the
      ``poison_request``-th request raises. Reconstruction alone cannot
      recover (the row re-poisons every incarnation); the scheduler's
      eviction policy has to isolate the row (``serve.py``).

    ``fault_count`` bounds how many times the injector trips (default 1:
    one transient fault, then a healthy device — the recovery drill's
    shape). ``on_segment`` is a host-side observation hook called after
    every dispatch with the running segment index; tests use it to flip
    drain flags or cancel requests mid-stream at a deterministic point.
    """

    fault_at_segment: int | None = None
    fault_mode: str = "raise"
    fault_count: int = 1
    slow_s: float = 0.05
    hang_s: float = 2.0
    poison_request: int | None = None
    on_segment: Callable[[int], None] | None = None

    def __post_init__(self):
        modes = ("raise", "hang", "slow", "poison")
        if self.fault_mode not in modes:
            raise ValueError(f"fault_mode must be one of {modes}, got "
                             f"{self.fault_mode!r}")
        if self.fault_mode == "poison" and self.poison_request is None:
            raise ValueError("fault_mode 'poison' needs poison_request")
        self.trips = 0

    def _armed(self, segments: int) -> bool:
        if self.trips >= self.fault_count:
            return False
        return (self.fault_at_segment is not None
                and segments >= self.fault_at_segment)

    def pre_fetch(self, segments: int, plan_requests: list[int]) -> None:
        """Called in the scheduler thread immediately before the harvest
        fetch. May raise (``raise``/``poison``) or sleep (``slow``)."""
        if self.fault_mode == "poison":
            if (self.trips < self.fault_count
                    and self.poison_request in plan_requests):
                self.trips += 1
                self._record(segments)
                raise InjectedFault(
                    f"injected poison row (request {self.poison_request}) "
                    f"at segment {segments}")
            return
        if not self._armed(segments):
            return
        if self.fault_mode == "raise":
            self.trips += 1
            self._record(segments)
            raise InjectedFault(f"injected tick fault at segment {segments}")
        if self.fault_mode == "slow":
            self.trips += 1
            self._record(segments)
            time.sleep(self.slow_s)

    def in_fetch(self, segments: int) -> None:
        """Called INSIDE the watchdogged fetch worker (``hang`` mode
        only), so the watchdog observes a genuinely blocked fetch."""
        if self.fault_mode == "hang" and self._armed(segments):
            self.trips += 1
            self._record(segments)
            time.sleep(self.hang_s)

    def _record(self, segments: int) -> None:
        # chaos trips land in the flight ring even for the modes that
        # never raise (slow/hang) — the dump must name the injected
        # fault no matter how the run ends
        flight.record("chaos_injection", mode=self.fault_mode,
                      segment=segments, trip=self.trips)
