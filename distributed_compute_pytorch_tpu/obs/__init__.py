"""Telemetry substrate shared by train and serve (ISSUE 8 / ROADMAP 3).

Three small host-side layers, none of which touch compiled code:

- :mod:`.metrics` — a thread-safe registry of counters, gauges and
  fixed-log-bucket histograms (p50/p90/p99 without storing samples).
  ``ContinuousBatcher.stats``/``.waste`` are dict-compatible VIEWS over
  a per-batcher registry; the SLO histograms (queue-wait, TTFT, TPOT,
  e2e) live beside them and ``stats_snapshot()`` serialises the lot.
- :mod:`.tracing` — the program's names on one timeline. Nestable
  ``span("admit_wave", rids=...)`` context managers through the serve
  scheduler's decision points and the trainer's
  data-wait/step/eval/checkpoint phases: each drives a
  ``jax.profiler.TraceAnnotation``, so any running profile holds it on
  the host plane on the device events' clock, and with a ``Tracer``
  installed it is also Chrome-trace-event JSON (Perfetto-loadable).
  ``scope(name)`` is the device side: ``jax.named_scope`` restricted to
  the vocabulary ``SCOPES`` — ``embed``, ``attn``, ``mlp``, ``dropout``,
  ``head``, ``loss``, ``optimizer``, ``grad_reduce`` in the train step;
  ``admit``, ``decode`` and under them ``kv_gather``, ``kv_write``,
  ``sample`` in the serve programs — which a profile's device ops then
  carry in their ``op_name`` (metadata only: the compiled code is the
  same).
- :mod:`.loadgen` — the open-loop Poisson load harness: requests that
  carry their arrival time, served in one call (the router, fleet and
  telemetry drills under ``tests/`` drive it).
- :mod:`.flight` — a bounded ring buffer of structured events fed from
  the span/instant call sites, dumped as a schema-versioned JSON
  artifact on every failure path (watchdog, chaos, drain, nonfinite
  raise, crash hook) — the forensics layer (ISSUE 10).
- :mod:`.sentinel` — the dp-replica divergence check (u32 fingerprint
  compared via pmax-pmin inside the mesh) and the per-step hash chain
  for bitwise run diffing.

The whole layer is a no-op when disabled (``metrics.set_enabled(False)``
or ``DCP_TELEMETRY=0``): record paths return before taking any lock and
``span()`` hands back a shared null context, as it does whenever no
profile is running and no tracer is installed — the cost is then a few
global reads per call site (the <1% guard in ``tests/test_obs.py``).
The ``stats``/``waste`` views stay live even when telemetry is off:
they are functional scheduler counters, not optional diagnostics.
"""

from distributed_compute_pytorch_tpu.obs import (
    flight, loadgen, metrics, tracing)
from distributed_compute_pytorch_tpu.obs.flight import (
    FlightRecorder, configure_flight, current_flight, dump_on_fault)
from distributed_compute_pytorch_tpu.obs.metrics import (
    Counter, Gauge, Histogram, MetricDict, Registry, enabled, set_enabled)
from distributed_compute_pytorch_tpu.obs.tracing import (
    SCOPES, Tracer, configure_tracer, current_tracer, scope, span)

__all__ = [
    "Counter", "FlightRecorder", "Gauge", "Histogram", "MetricDict",
    "Registry", "SCOPES", "Tracer", "configure_flight",
    "configure_tracer", "current_flight", "current_tracer",
    "dump_on_fault", "enabled", "flight", "loadgen", "metrics", "scope",
    "set_enabled", "span", "tracing",
]
