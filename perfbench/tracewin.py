"""A few steady seconds of the measured window under ``jax.profiler``,
started and stopped from a side thread so that the loop being measured is
not touched. Only the ``--trace 1`` run does this; its end-to-end numbers
are not reported."""

from __future__ import annotations

import shutil
import threading
import time


class TraceWindow:
    def __init__(self, env, start_frac: float = 0.3):
        self.on = bool(env.trace)
        self.dir = str(env.scratch / "trace")
        self.start_frac = start_frac
        self.length = float(env.traffic.get("trace_seconds", 4.0))
        self.seconds = env.seconds
        self._thread = None
        self._stop = threading.Event()
        self.t_start = self.t_stop = None
        self.error = None

    def _body(self, t0: float):
        import jax
        try:
            delay = t0 + self.start_frac * self.seconds - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.monotonic()
            self._stop.wait(self.length)
            self.t_stop = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:   # noqa: BLE001 — reported, not swallowed
            self.error = e

    def arm(self, t0: float):
        if not self.on:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(target=self._body, args=(t0,),
                                        daemon=True)
        self._thread.start()

    def close(self):
        if self._thread is not None:
            self._stop.set()
            self._thread.join()

    def result(self):
        """The reduced trace, or None when this run was not traced."""
        if not self.on:
            return None
        if self.error is not None:
            raise self.error
        if self.t_start is None:
            print("WARNING: the window closed before the trace began")
            return None
        from perfbench.trace_reduce import TraceSummary, load_xplane
        events = load_xplane(self.dir)
        self._digest(events)
        return TraceSummary(events, window_s=self.t_stop - self.t_start)

    def _digest(self, events):
        """What the trace holds, for a reader who has not seen one: every
        (plane, line) with its event count and commonest names, and the
        first events of each device line with their stats."""
        import collections
        import json
        import os
        lines = collections.OrderedDict()
        for e in events:
            d = lines.setdefault((e["plane"], e["line"]),
                                 {"n": 0, "names": collections.Counter(),
                                  "first": []})
            d["n"] += 1
            d["names"][e["name"][:80]] += 1
            if len(d["first"]) < 400 and e["plane"].endswith(":0"):
                d["first"].append(e)
        out = [{"plane": p, "line": l, "events": d["n"],
                "common": d["names"].most_common(25), "first": d["first"]}
               for (p, l), d in lines.items()]
        with open(os.path.join(os.path.dirname(self.dir),
                               "trace_digest.json"), "w") as f:
            json.dump(out, f)
