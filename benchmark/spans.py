"""The program's own spans in a traced run, moved onto the device's clock.

charon_tpu's tracer (`utils/tracer.py`) enters a `jax.profiler.TraceAnnotation`
of each span's name and attributes for the span's lifetime, so a traced run's
`.xplane.pb` holds the program's spans (`tbls/verify_batch`, `ops/verify/*`,
`ops/device_wait`, ...) as events of its host plane, on the host's clock. A
TPU's events are on the device's clock. The shift between the two comes from
one of two anchors, each exact up to one latency:

* `launch`: the profiler's own link between a host event and a device
  program, a `run_id` stat that both carry. The device cannot start a
  program before the host began to launch it, so the shift is at most
  (program start - launch start); the least over the programs is taken.
* `wait`: the ends of the `ops/device_wait` spans, each naming the jitted
  function it waits on. The host leaves a wait only after its program ended
  on the device, so the shift is at least (program end - wait end); the
  most over the waits is taken. The i-th wait on a function is paired with
  the i-th run of its program.

A launch anchor that falls below the wait anchor contradicts it and is not
used. Only the spans' placement against the device needs the shift (the idle
gaps' labels); a span's duration and what nests in it are read on the host.

A trace with no program spans (a build of the program without them) gives
no reading and raises nothing.
"""

from __future__ import annotations

import os
import re
import sys
from dataclasses import dataclass, field

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
WAIT = "ops/device_wait"
# a tracer span's name: lower-case words joined by "/" (JAX's own host
# events are "PjitFunction(f)", "PjRtCpuExecutable::Execute", ...)
_SPAN = re.compile(r"^[a-z][a-z0-9_]*(/[A-Za-z0-9_.{}-]+)+$")


@dataclass
class Spans:
    spans: list[tuple[str, int, int, dict]]   # (name, t0, t1, attrs), host clock
    window: tuple[int, int] | None = None     # bench.window, host clock
    shift: int | None = None                  # host -> device clock, ns
    anchor: str | None = None                 # "launch" | "wait" | None
    bounds: dict = field(default_factory=dict)  # each anchor's shift, ns
    submit_shift: int | None = None           # trace_reduce's bench.submit shift

    def in_window(self, name: str) -> list[tuple[int, int, dict]]:
        """The named spans that lie inside the traced window."""
        lo, hi = self.window if self.window else (-(1 << 62), 1 << 62)
        return [(t0, t1, a) for n, t0, t1, a in self.spans
                if n == name and t0 >= lo and t1 <= hi]

    def ms(self, name: str) -> float:
        """Milliseconds of the named spans inside the window."""
        return sum(t1 - t0 for t0, t1, _a in self.in_window(name)) / 1e6

    def less_waits_ms(self, name: str) -> float:
        """Milliseconds of the named spans inside the window, less the union
        of the `ops/device_wait` spans inside each: its host work."""
        waits = [(t0, t1) for n, t0, t1, _a in self.spans if n == WAIT]
        out = 0
        for t0, t1, _a in self.in_window(name):
            inner = trace_reduce._union(waits, t0, t1)
            out += (t1 - t0) - sum(b - a for a, b in inner)
        return out / 1e6

    def label(self, a: int, b: int) -> str | None:
        """The innermost span that covers the middle of the device-clock
        interval [a, b], or None (or with no anchor)."""
        if self.shift is None:
            return None
        mid = (a + b) // 2 - self.shift
        covering = [(t1 - t0, n) for n, t0, t1, _a in self.spans
                    if t0 <= mid <= t1]
        return min(covering)[1] if covering else None


def _program_pattern(program: str) -> re.Pattern:
    # as trace_reduce.Summary.module_seconds matches a program's name
    return re.compile(
        rf"(^|[^A-Za-z0-9_])jit_{re.escape(program)}($|[^A-Za-z0-9_])")


def launch_shift(modules, launches: dict) -> int | None:
    """Least (device start - host launch start) over the programs whose
    run_id a host event carries. `modules` is [(name, t0, t1, run_id)] on
    the device clock, `launches` {run_id: host start}."""
    d = [t0 - launches[r] for _n, t0, _t1, r in modules
         if r is not None and r in launches]
    return min(d) if d else None


def wait_shift(modules, spans) -> int | None:
    """Most (device end - host wait end) over the `ops/device_wait` spans
    paired, in order, with the runs of the program each names."""
    waits: dict[str, list[int]] = {}
    for n, _t0, t1, attrs in sorted(spans, key=lambda s: s[1]):
        program = attrs.get("program")
        if n == WAIT and program:
            waits.setdefault(str(program), []).append(t1)
    best = None
    for program, ends in waits.items():
        pat = _program_pattern(program)
        runs = sorted(t1 for name, _t0, t1, _r in modules if pat.search(name))
        if len(runs) != len(ends):
            continue  # a wait or a run outside the trace: no safe pairing
        for dev_end, host_end in zip(runs, ends):
            d = dev_end - host_end
            best = d if best is None or d > best else best
    return best


def align(spans: Spans, modules, launches: dict) -> Spans:
    """Fill the shift from the launch anchor, or the wait anchor where the
    trace has no launch link or the two disagree."""
    lo = wait_shift(modules, spans.spans)
    hi = launch_shift(modules, launches)
    spans.bounds = {k: v for k, v in (("launch", hi), ("wait", lo))
                    if v is not None}
    if hi is not None and (lo is None or hi >= lo):
        spans.shift, spans.anchor = hi, "launch"
    elif lo is not None:
        spans.shift, spans.anchor = lo, "wait"
    return spans


def read(path: str) -> Spans:
    """The program spans of an `.xplane.pb`, aligned to its device clock.

    Device programs are the "XLA Modules" events of the first TPU plane; on
    a host with no device plane (the CPU), the runs of the host events that
    carry an `hlo_op` stat, one per (hlo_module, run_id), as trace_reduce
    reads them. A launch is the earliest other host event with a run_id."""
    from jax.profiler import ProfileData

    found: list = []
    devices = False
    window = None
    submits: list[int] = []
    launches: dict = {}
    modules: list = []
    cpu_runs: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            if not plane.name.endswith(":0"):
                continue
            devices = True
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.name, int(e.start_ns), int(e.end_ns),
                                 dict(e.stats).get("run_id"))
                                for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name, t0, t1 = e.name, int(e.start_ns), int(e.end_ns)
                    if _SPAN.match(name):
                        found.append((name, t0, t1, dict(e.stats)))
                        continue
                    if name == trace_reduce.WINDOW:
                        window = (t0, t1)
                    elif name == "bench.submit":
                        submits.append(t0)
                    if name.startswith("bench."):
                        continue
                    stats = dict(e.stats)
                    rid = stats.get("run_id")
                    if rid is None:
                        continue
                    if "hlo_op" in stats:
                        k = (str(stats.get("hlo_module")), rid)
                        a, b = cpu_runs.get(k, (t0, t1))
                        cpu_runs[k] = (min(a, t0), max(b, t1))
                    else:
                        launches[rid] = min(t0, launches.get(rid, t0))
    if not devices:
        modules = [(m, a, b, r) for (m, r), (a, b) in cpu_runs.items()]
    out = align(Spans(found, window), modules, launches)
    if devices and submits and modules:
        # what trace_reduce shifts its bench.* annotations by
        out.submit_shift = min(t0 for _n, t0, _t1, _r in modules) - min(submits)
    return out


def idle_gaps(trace: trace_reduce.Trace) -> list[tuple[int, int]]:
    """The idle intervals of the first device that ran anything, on the
    device clock, bounded as trace_reduce.summarize bounds its window."""
    spans = [(t0, t1) for n, t0, t1 in trace.host if n == trace_reduce.WINDOW]
    all_ops = [(t0, t1) for dev in trace.ops.values() for _n, t0, t1 in dev]
    used = sorted((name, dev) for name, dev in trace.ops.items() if dev)
    if not used:
        return []
    lo, hi = min(a for a, _ in all_ops), max(b for _, b in all_ops)
    if spans:
        lo = min(lo, min(a for a, _ in spans))
        hi = max(hi, max(b for _, b in spans))
    if trace.dropped_from is not None and trace.dropped_from < hi:
        hi = trace.dropped_from
    merged = trace_reduce._union(((t0, t1) for _n, t0, t1 in used[0][1]),
                                 lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def labelled_gaps(trace: trace_reduce.Trace, spans: Spans,
                  top: int = 10) -> list[list]:
    """The longest idle gaps, each named by the innermost program span over
    its middle, else by trace_reduce's label (a `bench.*` annotation)."""
    longest = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return [[spans.label(a, b) or trace_reduce._label(trace.host, a, b),
             (b - a) / 1e9] for a, b in longest]


_CACHE: dict[str, Spans | None] = {}


def _log(msg: str) -> None:
    print(f"# spans: {msg}", file=sys.stderr, flush=True)


def of(run) -> Spans | None:
    """The aligned program spans of a traced run (read once a run), or None
    where the run was not traced or its trace holds no program span."""
    if run.trace is None:
        return None
    tdir = os.path.join(HERE, ".trace", run.cell.workload["name"])
    try:
        path = trace_reduce.find_xplane(tdir)
    except FileNotFoundError:
        return None
    if path in _CACHE:
        return _CACHE[path]
    spans = read(path)
    if not spans.spans:
        _log("the trace holds no program span")
        _CACHE[path] = None
        return None
    _log(f"{len(spans.spans)} program spans; anchor {spans.anchor}, "
         f"shifts (ns) {spans.bounds}; the bench.submit shift "
         f"{spans.submit_shift} ns")
    if spans.shift is not None and spans.submit_shift is not None:
        _log(f"the bench.submit anchor is "
             f"{(spans.submit_shift - spans.shift) / 1e6:.6f} ms from the "
             f"{spans.anchor} anchor")
    if spans.shift is not None:
        _log("idle gaps by program span: " + repr(
            labelled_gaps(trace_reduce.load(path), spans)))
    _CACHE[path] = spans
    return spans
