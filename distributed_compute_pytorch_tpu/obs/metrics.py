"""Metrics registry: counters, gauges, fixed-log-bucket histograms.

The serve loop produces a handful of numbers per SEGMENT and four per
REQUEST; the trainer a few per log cadence. What was missing is any
notion of a DISTRIBUTION — a mean TTFT hides exactly the p99 the
ROADMAP-3 router must dispatch on. Histograms here use fixed
logarithmic buckets (``growth = 10**(1/per_decade)``): recording is a
C-level ``bisect`` into precomputed bounds plus an integer increment —
no samples stored, no allocation on the record path — and percentiles
are read back by walking the cumulative counts and interpolating
geometrically inside the landing bucket, clamped to the observed
min/max. The relative error is bounded by one bucket's width
(~15% at the default 16 buckets/decade over 1 µs..10 ks — plenty for
latency SLOs; ``tests/test_obs.py`` pins the bound vs numpy
quantiles).

Thread safety: the serve scheduler, its watchdogged fetch workers, and
``cancel()`` callers may touch the same instruments; every mutating
path takes the instrument's lock (a ``with lock:`` on an existing lock
object allocates nothing). Creation of instruments takes the registry
lock; lookups are dict reads.

Disable semantics (module flag, seeded from ``DCP_TELEMETRY``):
``Counter.inc`` and ``Histogram.record`` return before locking when
disabled; ``Gauge.set`` always works because :class:`MetricDict` — the
dict-compatible view that keeps ``ContinuousBatcher.stats``/``waste``
backwards-compatible — mirrors FUNCTIONAL scheduler counters through
gauges, and those must stay correct with telemetry off.
"""

from __future__ import annotations

import math
import os
import threading
from bisect import bisect_right

_ENABLED = os.environ.get("DCP_TELEMETRY", "1") not in ("0", "false", "off")


def enabled() -> bool:
    return _ENABLED


def set_enabled(flag: bool) -> None:
    """Flip the global record-path switch (tests; ``DCP_TELEMETRY=0``
    seeds it before import)."""
    global _ENABLED
    _ENABLED = bool(flag)


class Counter:
    """Monotonic counter. ``inc`` is a no-op when telemetry is off."""

    __slots__ = ("name", "value", "_mu")

    def __init__(self, name: str):
        self.name = name
        self.value = 0
        self._mu = threading.Lock()

    def inc(self, n=1) -> None:
        if not _ENABLED:
            return
        with self._mu:
            self.value += n


class Gauge:
    """Last-write-wins value. NOT gated on the enable flag: the
    ``MetricDict`` views route functional scheduler state through
    gauges, which must keep working with telemetry disabled."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def set(self, v) -> None:
        self.value = v


class Histogram:
    """Fixed-log-bucket histogram over ``(lo, hi)`` with
    ``per_decade`` buckets per decade, plus underflow/overflow ends.

    ``record`` is the zero-allocation hot path: one global check, one
    lock, one bisect, three adds. ``percentile``/``summary`` are read
    paths (snapshot cadence) and may allocate freely.
    """

    __slots__ = ("name", "lo", "hi", "per_decade", "_bounds", "counts",
                 "count", "sum", "min", "max", "_mu")

    def __init__(self, name: str, lo: float = 1e-6, hi: float = 1e4,
                 per_decade: int = 16):
        if not (0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got ({lo}, {hi})")
        if per_decade < 1:
            raise ValueError(f"per_decade must be >= 1, got {per_decade}")
        self.name = name
        self.lo, self.hi, self.per_decade = lo, hi, per_decade
        n = math.ceil((math.log10(hi) - math.log10(lo)) * per_decade)
        # bucket i (1..n) covers [bounds[i-1], bounds[i]); 0 underflows,
        # n+1 overflows. Bounds precomputed so record() is pure bisect.
        self._bounds = [lo * 10.0 ** (i / per_decade) for i in range(n + 1)]
        self.counts = [0] * (n + 2)
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._mu = threading.Lock()

    def record(self, v) -> None:
        if not _ENABLED:
            return
        with self._mu:
            self.counts[bisect_right(self._bounds, v)] += 1
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v

    def percentile(self, q: float) -> float:
        """The q-quantile (``q`` in [0, 1]); ``nan`` when empty.
        Geometric interpolation inside the landing bucket, clamped to
        the observed extremes (so p0 == min and p100 == max exactly)."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        with self._mu:
            if self.count == 0:
                return math.nan
            rank = q * (self.count - 1)
            cum = 0
            for i, c in enumerate(self.counts):
                if c == 0:
                    continue
                if cum + c > rank:
                    frac = (rank - cum + 0.5) / c
                    if i == 0:                      # underflow: below lo
                        est = self.min
                    elif i == len(self.counts) - 1:  # overflow: above hi
                        est = self.max
                    else:
                        b0, b1 = self._bounds[i - 1], self._bounds[i]
                        est = b0 * (b1 / b0) ** frac
                    return min(max(est, self.min), self.max)
                cum += c
            return self.max

    def summary(self) -> dict:
        """The serialisable digest embedded in ``stats_snapshot()``."""
        if self.count == 0:
            return {"count": 0}
        return {"count": self.count,
                "mean": self.sum / self.count,
                "min": self.min, "max": self.max,
                "p50": self.percentile(0.50),
                "p90": self.percentile(0.90),
                "p95": self.percentile(0.95),
                "p99": self.percentile(0.99)}


class Registry:
    """Get-or-create home for instruments, keyed by name. One global
    default (:data:`REGISTRY`) serves the trainer; each
    ``ContinuousBatcher`` owns a private one so concurrent batchers
    (tests build dozens) never cross-contaminate."""

    def __init__(self):
        self._mu = threading.Lock()
        self._instruments: dict = {}

    def _get(self, name: str, cls, *args, **kw):
        inst = self._instruments.get(name)
        if inst is None:
            with self._mu:
                inst = self._instruments.get(name)
                if inst is None:
                    inst = cls(name, *args, **kw)
                    self._instruments[name] = inst
        if not isinstance(inst, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{type(inst).__name__}, not {cls.__name__}")
        return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, **kw) -> Histogram:
        return self._get(name, Histogram, **kw)

    def snapshot(self) -> dict:
        """{name: value} for counters/gauges, {name: summary-dict} for
        histograms — everything JSON-serialisable."""
        with self._mu:
            items = list(self._instruments.items())
        out = {}
        for name, inst in sorted(items):
            out[name] = (inst.summary() if isinstance(inst, Histogram)
                         else inst.value)
        return out

    def reset(self) -> None:
        with self._mu:
            self._instruments.clear()


REGISTRY = Registry()   # process-default (trainer, MetricLogger)


class MetricDict(dict):
    """A real ``dict`` whose entries are mirrored into registry gauges.

    This is how ``ContinuousBatcher.stats``/``waste`` stay byte-for-
    byte compatible (indexing, ``dict(...)``, ``json.dumps``, ``==``)
    while becoming VIEWS over the telemetry registry: every
    ``d[k] = v`` (including the ``d[k] += 1`` pattern all over the
    scheduler) lands in ``registry.gauge(prefix + k)`` too, so
    ``Registry.snapshot()`` and the legacy dicts can never disagree.
    Mirroring uses gauges deliberately — these are functional scheduler
    counters that must keep counting with telemetry disabled."""

    def __init__(self, registry: Registry, prefix: str, init: dict):
        super().__init__(init)
        self._reg = registry
        self._prefix = prefix
        for k, v in init.items():
            registry.gauge(prefix + k).set(v)

    def __setitem__(self, k, v):
        super().__setitem__(k, v)
        self._reg.gauge(self._prefix + k).set(v)


def device_memory_gauges(registry: Registry,
                         prefix: str = "mem.") -> dict:
    """Record per-device memory stats (bytes in use / peak / limit)
    into gauges at call time and return them. Backends without
    ``memory_stats`` (CPU) contribute nothing — callers at log cadence
    pay one try/except, never a crash."""
    out = {}
    if not _ENABLED:
        return out
    import jax
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:       # noqa: BLE001 — backend-optional API
            stats = None
        if not stats:
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                name = f"{prefix}{d.id}.{key}"
                registry.gauge(name).set(int(stats[key]))
                out[name] = int(stats[key])
    return out
