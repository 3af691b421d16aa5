"""Counts what JAX compiles or fetches from its persistent cache, with the
time of each event, so a run can show that nothing compiled inside its
measured window."""

from __future__ import annotations

import threading
import time

_COMPILE = "/jax/core/compile/backend_compile_duration"
_RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileWatch:
    def __init__(self):
        import jax.monitoring as mon
        self._mu = threading.Lock()
        self.events = []           # (monotonic time, kind, seconds)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name, secs, **kw):
        if name == _COMPILE or name == _RETRIEVE:
            kind = "compile" if name == _COMPILE else "cache_fetch"
            with self._mu:
                self.events.append((time.monotonic(), kind, float(secs)))

    def between(self, t0: float, t1: float) -> list:
        with self._mu:
            return [e for e in self.events if t0 <= e[0] <= t1]

    def summary(self) -> dict:
        with self._mu:
            ev = list(self.events)
        return {"compiles": sum(1 for e in ev if e[1] == "compile"),
                "compile_s": sum(e[2] for e in ev if e[1] == "compile"),
                "cache_fetches": sum(1 for e in ev if e[1] == "cache_fetch")}
