"""Spans and scopes: the program's own names on one timeline.

Two kinds of name, one module:

- :func:`span` / :func:`instant` — HOST phases. The serve scheduler
  interleaves admit/dispatch/harvest/reconstruct decisions with
  overlapped device work; the trainer interleaves
  data-wait/step/eval/checkpoint. Every span drives a
  ``jax.profiler.TraceAnnotation``, so whatever profile is running (the
  benchmark's traced slice, ``--profile_dir``, ``--profile_segments``)
  holds it on the host plane, on the clock of the device events. With a
  :class:`Tracer` installed (``--trace_path``) the same span is also a
  Chrome-trace-event pair (``"ph": "B"/"E"`` per thread, microsecond
  ``ts``), Perfetto-loadable on its own.
- :func:`scope` — DEVICE work. ``jax.named_scope`` restricted to the
  declared vocabulary :data:`SCOPES`: the name becomes part of the
  ``op_name`` of every operation traced under it, so a profile's device
  ops say which layer of the program they belong to. Metadata only: the
  compiled code is the same with and without.

Design points:

- Spans are plain objects, not generator context managers. Nesting is
  implicit in the B/E ordering per ``tid``
  (``threading.get_native_id``), so spans opened in the scheduler thread
  and the watchdogged fetch worker interleave correctly in one trace.
- Tracer timestamps come from ``time.perf_counter_ns`` relative to the
  tracer's epoch — monotonic by construction; ``dump`` records the
  epoch's wall-clock time (``epoch_unix_ns``) so the JSON can be laid
  beside a profile.
- The three sinks are independent checks: the flight recorder's ring,
  the profiler's TraceMe and the :class:`Tracer`. With no profile
  running and no tracer installed — or with telemetry disabled
  (``metrics.set_enabled(False)``) — :func:`span` hands back a shared
  null context: instrumented code pays two global reads and the
  profiler's one atomic read.

Spans measure HOST decision time. JAX dispatch is asynchronous, so a
``dispatch_segment`` span covers tracing + enqueue, not device
execution; the device's side of the same instant is the scoped ops of
the profile the span landed in.
"""

from __future__ import annotations

import json
import os
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from distributed_compute_pytorch_tpu.obs import flight, metrics

# The device-side vocabulary, one name per layer boundary of PERF.md
# section 3. ``dropout`` nests (``attn/dropout``, ``mlp/dropout``,
# ``embed/dropout``); ``kv_gather``/``kv_write``/``sample`` nest under
# ``admit``/``decode`` (``kv_write`` also inside a mixer of
# ``models/hybrid.py``, round what lays a leaf out for its write); ``router``/``experts``/``shared_expert`` nest in
# ``mlp`` (routed-expert layers, ``models/moe.py::HeldExperts``) and
# ``attn_local`` in ``attn`` (a window layer's attention: the banded
# prefill and the ring read); ``attn_latent`` in ``attn`` too (everything
# a latent-attention mixer does, ``models/hybrid.py``) and
# ``latent_absorb`` inside it (the products with the up-projection of the
# compressed K/V: its expansion in prefill, its absorption into query and
# output in a decode tick); ``attn_cca`` in ``attn`` likewise
# (everything a compressed-convolutional-attention mixer does) and
# ``cca_mix`` inside it (what it adds round the projections and the
# attention product: the two convolutions over the sequence, the query-key
# mean, the norms and the temperature, the rotation, the value shift, the
# tail's read and write); ``attn_linear`` in ``attn`` (everything a KDA
# linear-attention mixer does) with ``linear_scan`` inside it (the
# convolutions, the decays, the chunked recurrence or the one step, the
# state's read and write); ``attn_sparse`` in ``attn`` (everything a sparse
# latent mixer does) with ``index_select`` inside it (index queries and
# keys, pooling, scores, top-k, the pooled keys' read and write) and
# ``latent_absorb`` where a latent mixer has it; ``hyper_mix`` under
# ``attn`` and ``mlp`` alike (a hyper-connection's maps, Sinkhorn and stream
# products); ``attn_gate`` in ``attn`` (a full layer's output gate: its
# projection, the sigmoid and the product with the heads). The benchmark's scope metrics (``perfbench/layer_metrics``)
# name these and nothing else.
SCOPES = ("embed", "attn", "mlp", "dropout", "head", "loss",
          "optimizer", "grad_reduce",
          "admit", "decode", "kv_gather", "kv_write", "sample",
          "router", "experts", "shared_expert", "attn_local",
          "attn_latent", "latent_absorb", "attn_cca", "cca_mix",
          "attn_linear", "linear_scan", "attn_sparse", "index_select",
          "hyper_mix", "attn_gate", "block_pass", "unmask")


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`; any other
    name raises while the program is traced. Operations traced under it
    carry ``.../<name>/...`` in their ``op_name`` (backward operations
    ``transpose(jvp(<name>))``), which is what a profile's reader sees."""
    if name not in SCOPES:
        raise ValueError(f"scope {name!r} is not declared in "
                         f"obs.tracing.SCOPES {SCOPES}")
    return jax.named_scope(name)


class _NullSpan:
    """Shared no-op context for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args):
        pass


_NULL_SPAN = _NullSpan()


def _packed(args: dict) -> dict:
    """Arguments as the profiler can hold them. TraceMe packs them as
    ``name#k=v,k=v#``: a ``,`` or ``#`` inside a value would cut it short
    in the profile, so in anything but a number they are written ``;``."""
    return {k: v if isinstance(v, (int, float))
            else str(v).replace(",", ";").replace("#", ";")
            for k, v in args.items()}


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_late", "_ann")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._late = None

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **_packed(self._args or {}))
        self._ann.__enter__()
        if self._tracer is not None:
            self._tracer._emit("B", self._name, self._args)
        return self

    def note(self, **args):
        """Arguments known only inside the span (which requests a
        harvest finished): they ride its end."""
        self._ann.set_metadata(**_packed(args))
        self._late = {**(self._late or {}), **args}

    def __exit__(self, *exc):
        if self._tracer is not None:
            self._tracer._emit("E", self._name, self._late)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Collects trace events in memory; ``dump`` writes them."""

    def __init__(self):
        self._mu = threading.Lock()
        self._events: list[dict] = []
        self._epoch_ns = time.perf_counter_ns()
        self._epoch_unix_ns = time.time_ns()
        self._pid = os.getpid()

    def _emit(self, ph: str, name: str, args, **extra) -> None:
        ev = {"name": name, "ph": ph, **extra, "pid": self._pid,
              "tid": threading.get_native_id(),
              "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3}
        if args:
            ev["args"] = args
        with self._mu:
            self._events.append(ev)

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (``ph: "i"`` — drain start, fault)."""
        self._emit("i", name, args, s="t")

    def events(self) -> list[dict]:
        with self._mu:
            return list(self._events)

    def dump(self, path: str) -> None:
        """Write the Perfetto/chrome://tracing-loadable trace object.
        ``epoch_unix_ns`` is the wall-clock time of ``ts`` 0: a profile's
        events (``profile_start_time`` of its ``Task Environment`` plane
        + an event's offset) lie on the same wall clock."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events(),
                       "displayTimeUnit": "ms",
                       "epoch_unix_ns": self._epoch_unix_ns}, f)


_GLOBAL: Tracer | None = None


def configure_tracer(tracer: Tracer | None) -> Tracer | None:
    """Install (or clear, with ``None``) the process-global tracer used
    by :func:`span`; returns the previous one so tests can restore."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = tracer
    return prev


def current_tracer() -> Tracer | None:
    return _GLOBAL


def span(name: str, **args):
    """Module-level span — the form the serve scheduler and trainer
    call. It feeds three independent sinks: the installed
    :mod:`~distributed_compute_pytorch_tpu.obs.flight` ring (every
    span/instant name that flows through here lands there with no extra
    instrumentation), the profiler (a ``TraceAnnotation`` opened at
    ``__enter__``: in any running profile the span lies on the host
    plane), and the global :class:`Tracer` when one is installed. Each
    works without the others. With neither a profile running nor a
    tracer installed (or with telemetry disabled) this is the shared
    null context: one atomic read, nothing allocated."""
    f = flight._GLOBAL
    if f is not None:
        f.record(name, **args)
    t = _GLOBAL
    if not metrics.enabled() or (
            t is None and not TraceAnnotation.is_enabled()):
        return _NULL_SPAN
    return _Span(t, name, args or None)


def instant(name: str, **args) -> None:
    f = flight._GLOBAL
    if f is not None:
        f.record(name, **args)
    if not metrics.enabled():
        return
    if TraceAnnotation.is_enabled():
        with TraceAnnotation(name, **_packed(args)):
            pass
    t = _GLOBAL
    if t is not None:
        t.instant(name, **args)


def validate_chrome_trace(events: list[dict]) -> list[str]:
    """Structural validity of a trace-event list: every ``B`` has a
    matching same-name ``E`` on the same (pid, tid) in LIFO order, and
    timestamps are monotonically non-decreasing per (pid, tid). Returns
    the list of violations (empty == valid) — used by
    ``tests/test_obs.py``."""
    problems: list[str] = []
    stacks: dict = {}
    last_ts: dict = {}
    for i, ev in enumerate(events):
        key = (ev.get("pid"), ev.get("tid"))
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i}: missing/bad ts {ts!r}")
            continue
        if key in last_ts and ts < last_ts[key]:
            problems.append(f"event {i}: ts {ts} < previous "
                            f"{last_ts[key]} on tid {key}")
        last_ts[key] = ts
        ph = ev.get("ph")
        if ph == "B":
            stacks.setdefault(key, []).append(ev.get("name"))
        elif ph == "E":
            stack = stacks.get(key) or []
            if not stack:
                problems.append(f"event {i}: E {ev.get('name')!r} "
                                f"without open B on tid {key}")
            else:
                top = stack.pop()
                if top != ev.get("name"):
                    problems.append(
                        f"event {i}: E {ev.get('name')!r} closes "
                        f"B {top!r} on tid {key}")
    for key, stack in stacks.items():
        if stack:
            problems.append(f"unclosed span(s) {stack} on tid {key}")
    return problems
