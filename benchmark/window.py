"""Readings the program keeps on the host, taken as deltas over a window.

* `snapshot` and `Delta` read the program's metrics registry (histogram sum
  and count per label set, counter values) so that a window's delta is
  exact, whatever buckets a histogram has.
* `CompileLog` records JAX's own compile telemetry (jax.monitoring): when
  tracing, lowering and backend compilation ran, split by the phase of the
  run they fell in. A jitted function traced inside another one reports a
  duration of its own that lies inside its caller's, so seconds are taken
  as the union of the events' intervals, never as their sum.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def snapshot(registry) -> dict:
    """{'name{label="v"}': (sum, count)} for histograms and
    {'name{label="v"}': value} for counters and gauges, read through the
    registry's public snapshot views."""
    out: dict = {k: (v["sum"], v["count"])
                 for k, v in registry.snapshot_quantiles().items()}
    out.update(registry.snapshot())
    return out


def key(name: str, **labels: str) -> str:
    lbl = ",".join(f'{n}="{v}"' for n, v in labels.items())
    return f"{name}{{{lbl}}}" if lbl else name


class Delta:
    """The change of a registry between two snapshots."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after

    def hist(self, name: str, **labels: str) -> tuple[float, float]:
        """(sum, count) observed in the window."""
        k = key(name, **labels)
        s1, c1 = self.after.get(k, (0.0, 0.0))
        s0, c0 = self.before.get(k, (0.0, 0.0))
        return s1 - s0, c1 - c0

    def mean(self, name: str, **labels: str) -> float | None:
        s, c = self.hist(name, **labels)
        return s / c if c else None

    def counter(self, name: str, **labels: str) -> float:
        """Change of one counter child, or of all its children summed when
        no labels are given."""
        def total(snap):
            if labels:
                v = snap.get(key(name, **labels), 0.0)
                return 0.0 if isinstance(v, tuple) else v
            return sum(v for k, v in snap.items() if not isinstance(v, tuple)
                       and (k == name or k.startswith(name + "{")))
        return total(self.after) - total(self.before)


class CompileLog:
    """JAX compile telemetry by phase of the run ("setup", "window", ...)."""

    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.phase = "setup"
        self.intervals: list = []  # (phase, event, start, end)
        self.events: dict = defaultdict(int)      # (phase, event) -> count
        self.timeline: list = []  # (perf_counter, phase, event, fun, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        fun = str(kw.get("fun_name", "?"))
        end = time.perf_counter()  # the listener runs as the event ends
        with self.lock:
            if event in (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT):
                self.intervals.append((self.phase, event, end - duration, end))
                self.events[(self.phase, event)] += 1
            if event in (COMPILE_EVENT, RETRIEVAL_EVENT) or (
                    event in (TRACE_EVENT, LOWER_EVENT) and duration > 1.0):
                self.timeline.append((time.perf_counter(), self.phase,
                                      event.rsplit("/", 1)[-1], fun, duration))

    def _on_event(self, event: str, **kw) -> None:
        if event in (CACHE_HIT_EVENT, CACHE_MISS_EVENT):
            with self.lock:
                self.events[(self.phase, event)] += 1
                self.timeline.append((time.perf_counter(), self.phase,
                                      event.rsplit("/", 1)[-1], "", 0.0))

    def total(self, phase: str, *events: str) -> float:
        """Seconds of `phase` in which any of `events` was running."""
        with self.lock:
            spans = sorted((a, b) for p, e, a, b in self.intervals
                           if p == phase and e in events)
        out, reach = 0.0, float("-inf")
        for a, b in spans:
            if b > reach:
                out += b - max(a, reach)
                reach = b
        return out

    def count(self, phase: str, event: str) -> int:
        with self.lock:
            return self.events.get((phase, event), 0)
