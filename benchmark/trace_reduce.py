"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's device numbers.

What is read:

* device operations: on a TPU, the events of each device plane's "XLA Ops"
  line, and the executions of whole programs on its "XLA Modules" line.
  The benchmark runs libtpu with its per-operation trace off (run.py), so
  that a trace of these long scans stays small; a device plane with no
  "XLA Ops" events then counts its programs as its operations. On a host
  with no device plane (the CPU), the host events that carry an `hlo_op`
  stat, grouped into programs by their `hlo_module` and `run_id`;
* the benchmark's own host annotations (`bench.*`), which name what the host
  was doing and bound the traced window (`bench.window`);
* where the profiler dropped device buffers (it keeps a trace under 2 GB),
  the event that marks it: the window then ends there, and the summary
  says it is truncated, since what follows is not recorded.

What comes out (`Summary`): the busy time (the union of the operation
intervals, averaged over the devices that ran any), the window, the idle share, device
seconds per program, the operations that took most self time (a loop's
time less that of the operations inside it), and the longest idle gaps
labelled with the innermost annotation around them.

A TPU's events are on the device's clock, which the profiler does not align
with the host's. The host annotations are moved onto the device clock by
the offset between the first program's start on the device and the first
`bench.submit` on the host, which is exact up to one launch latency.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"
# the event a TPU plane carries where the profiler dropped its buffers
DROPPED = "Trace Buffers Dropped"


@dataclass
class Trace:
    ops: dict[str, list[tuple[str, int, int]]]       # device -> (name, t0, t1)
    modules: list[tuple[str, int, int]]               # (name, t0, t1)
    host: list[tuple[str, int, int]]                  # bench.* annotations
    dropped_from: int | None = None   # where the profiler began dropping


@dataclass
class Summary:
    busy_s: float
    window_s: float
    module_s: dict[str, float] = field(default_factory=dict)
    device_ops: list[list] = field(default_factory=list)
    idle_gaps: list[list] = field(default_factory=list)
    truncated: bool = False   # the window ends where the device record ends

    @property
    def idle_share(self) -> float | None:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, *functions: str) -> float:
        """Device seconds of the programs built from the named jitted
        functions (matched as `jit_<function>` in the program's name)."""
        pats = [re.compile(rf"(^|[^A-Za-z0-9_])jit_{re.escape(f)}($|[^A-Za-z0-9_])")
                for f in functions]
        return sum(s for name, s in self.module_s.items()
                   if any(p.search(name) for p in pats))


def _stat(event, name: str):
    for k, v in event.stats:
        if k == name:
            return v
    return None


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict = defaultdict(list)
    modules: list = []
    host: list = []
    cpu_ops: dict = {}
    names: dict[str, str] = {}  # one string per distinct operation text
    devices = False
    dropped: list[int] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices = True
            programs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name].extend(
                        (names.setdefault(e.name, e.name), int(e.start_ns),
                         int(e.end_ns))
                        for e in line.events)
                elif line.name == "XLA Modules":
                    programs = [(e.name, int(e.start_ns), int(e.end_ns))
                                for e in line.events]
                else:
                    dropped += [int(e.start_ns) for e in line.events
                                if e.name == DROPPED]
            if not ops[plane.name]:
                ops[plane.name] = programs
            if plane.name.endswith(":0"):
                modules = programs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, int(e.start_ns), int(e.end_ns)))
                    elif _stat(e, "hlo_op") is not None:
                        cpu_ops[(e.name, int(e.start_ns))] = (
                            e, _stat(e, "hlo_module"), _stat(e, "run_id"))
    if devices:
        return _moved_to_device_clock(Trace(
            dict(ops), modules, host, min(dropped) if dropped else None))
    runs: dict = {}
    for (name, t0), (e, module, run) in cpu_ops.items():
        t1 = int(e.end_ns)
        ops["/host:CPU"].append((name, t0, t1))
        k = (module, run)
        a, b = runs.get(k, (t0, t1))
        runs[k] = (min(a, t0), max(b, t1))
    modules = [(str(m), a, b) for (m, _r), (a, b) in runs.items()]
    return Trace(dict(ops), modules, host)


def _moved_to_device_clock(trace: Trace) -> Trace:
    """The host annotations shifted so that the first `bench.submit` starts
    with the first program on the device."""
    submits = [t0 for n, t0, _t1 in trace.host if n == "bench.submit"]
    if not (submits and trace.modules):
        return trace
    shift = min(t0 for _n, t0, _t1 in trace.modules) - min(submits)
    return Trace(trace.ops, trace.modules,
                 [(n, t0 + shift, t1 + shift) for n, t0, t1 in trace.host],
                 trace.dropped_from)


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def short_name(text: str) -> str:
    """An operation's name from its HLO text ("%while.3 = (...) while(...)"
    -> "while.3"); Mosaic kernels are marked as such."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return f"{name} (mosaic)" if "tpu_custom_call" in text else name


def _self_times(ops) -> dict[str, float]:
    """Seconds per operation name, less the time of the operations nested
    inside each one (a while loop's body runs inside the loop's event)."""
    out: dict = defaultdict(float)
    stack: list[list] = []                     # [name, end, self_ns]
    for name, t0, t1 in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] < t1:
            done = stack.pop()
            out[done[0]] += done[2] / 1e9
        if stack:
            stack[-1][2] -= t1 - t0
        stack.append([short_name(name), t1, t1 - t0])
    for name, _end, self_ns in stack:
        out[name] += self_ns / 1e9
    return dict(out)


def _label(host, a: int, b: int) -> str:
    """The innermost benchmark annotation that covers the gap's middle."""
    mid = (a + b) // 2
    covering = [(t1 - t0, n) for n, t0, t1 in host
                if t0 <= mid <= t1 and n != WINDOW]
    return min(covering)[1] if covering else "host"


def summarize(trace: Trace, top: int = 10) -> Summary:
    spans = [(t0, t1) for n, t0, t1 in trace.host if n == WINDOW]
    all_ops = [(t0, t1) for dev in trace.ops.values() for _n, t0, t1 in dev]
    if not all_ops:
        return Summary(0.0, 0.0)
    lo, hi = min(a for a, _ in all_ops), max(b for _, b in all_ops)
    if spans:
        lo = min(lo, min(a for a, _ in spans))
        hi = max(hi, max(b for _, b in spans))
    truncated = trace.dropped_from is not None and trace.dropped_from < hi
    if truncated:
        hi = trace.dropped_from
    busy_ns = []
    gaps: list[tuple[int, int]] = []
    used = sorted((name, dev) for name, dev in trace.ops.items() if dev)
    for k, (_name, dev) in enumerate(used):
        merged = _union(((t0, t1) for _n, t0, t1 in dev), lo, hi)
        busy_ns.append(sum(b - a for a, b in merged))
        if k == 0:  # the idle gaps are read on the first device
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    busy = sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0
    module_s: dict = defaultdict(float)
    for name, t0, t1 in trace.modules:
        a, b = max(t0, lo), min(t1, hi)
        if b > a:
            module_s[name] += (b - a) / 1e9
    top_ops = sorted(_self_times(used[0][1]).items(),
                     key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        busy_s=busy, window_s=(hi - lo) / 1e9, module_s=dict(module_s),
        device_ops=[[n, s] for n, s in top_ops],
        idle_gaps=[[_label(trace.host, a, b), (b - a) / 1e9]
                   for a, b in longest],
        truncated=truncated)
