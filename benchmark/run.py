"""Run one benchmark cell and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json at the checkout's root: the
cell names its configuration (benchmark/configs/<config>.json) and its
traffic mix (benchmark/traffic/<mix>.json); each metric is read by the
module of its own name (benchmark/end_to_end/<metric>.py with --trace 0,
benchmark/layers/<metric>.py with --trace 1), whose `read(run)` returns a
number or None. Adding a cell, a mix or a metric adds files and entries and
edits none.

The mix's `entry` names the program's entry point that the closed loop
drives (systems.ENTRIES): a peer set's bulk verify, or core/sigagg's
aggregate+verify of one duty of a slot.

A run: set-up (keys and every request's inputs made from --seed; the mix's
warm requests through the real path, which traces and compiles every shape
the window uses), then the closed loop for --seconds and on to the end of
the slot in progress (with --trace 1: the mix's `trace_requests` requests
under the profiler instead), then every answered request compared with the
reference. The last line of stdout is the JSON result. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result; so does a mix whose entry is unknown, before any set-up.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import resource
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field

T_START = time.perf_counter()
HLO_TRACE_OFF = "--xla_enable_hlo_trace=false"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import check  # noqa: E402
import inputs  # noqa: E402
import load  # noqa: E402
import window  # noqa: E402


def log(msg: str) -> None:
    print(f"# [{time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


# --------------------------------------------------------------------------
# discovery
# --------------------------------------------------------------------------


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    spec: dict
    workload: dict
    config: dict
    mix: dict
    metrics: list[dict]            # the metrics this run reports, in order
    root: str = ROOT


def _applies(metric: dict, name: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(root: str, name: str, traced: bool) -> Cell:
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = read_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = read_json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    if traced:
        metrics = [m for m in spec["per_layer"] if _applies(m, name, names)]
    else:
        metrics = e2e
    return Cell(spec, w, config, mix, metrics, root)


def reader(metric: str, traced: bool):
    """The `read(run)` function of a metric's own module."""
    sub = "layers" if traced else "end_to_end"
    path = os.path.join(HERE, sub, f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    window: load.Window
    delta: window.Delta
    compiles: window.CompileLog
    trace: object = None                      # trace_reduce.Summary
    failures: list[str] = field(default_factory=list)


def _program_registry():
    from charon_tpu.utils import metrics

    return metrics.default_registry


class _NoRegistry:
    def snapshot_quantiles(self):
        return {}

    def snapshot(self):
        return {}


def path_failures(delta: window.Delta, compiles: window.CompileLog) -> list[str]:
    """Ways the window left the device path (each fails every request)."""
    out = []
    n = compiles.count("window", window.COMPILE_EVENT) + compiles.count(
        "window", window.CACHE_HIT_EVENT)
    if n:
        out.append(f"{n} compiles inside the window")
    fb = delta.counter("ops_sigagg_fallback_total")
    if fb:
        out.append(f"{fb:g} guard fallbacks")
    native = delta.counter("ops_pairing_total", path="native")
    if native:
        out.append(f"{native:g} pairings on the native rung")
    if not delta.counter("ops_pairing_total", path="device"):
        out.append("no pairing ran on the device")
    from charon_tpu.ops import guard

    if guard.BREAKER.state != guard.CLOSED:
        out.append(f"plane breaker {guard.BREAKER.state}")
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, system,
             devices, chips: int, program: bool = True) -> dict:
    """Set up, drive and check one run; returns the result line's dict."""
    import jax

    mix, config = cell.mix, cell.config
    compiles = window.CompileLog()
    log("imports and device set up")
    gen = inputs.Generator(config, mix, seed)
    warm = gen.warm()
    prepared = [(r, system.prepare(r)) for r in gen.pool()]
    log(f"keys and {len(warm)} warm and {len(prepared)} pool sets made")
    warm_win = load.closed_loop(
        system, [(r, system.prepare(r)) for r in warm],
        int(mix["in_flight"]), math.inf, max_requests=len(warm),
        late_wait=1200.0)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.1f}s; warm sets took "
        f"{[round(o.done - o.submitted, 3) for o in warm_win.completed]}s")
    for t, phase, event, fn, d in compiles.timeline:
        log(f"compile timeline {t - T_START:8.1f}s {phase} {event} {fn} "
            f"{d:.3f}s")
    log(f"cache hits {compiles.count('setup', window.CACHE_HIT_EVENT)}, "
        f"misses {compiles.count('setup', window.CACHE_MISS_EVENT)}")

    registry = _program_registry() if program else _NoRegistry()
    compiles.phase = "window"
    before = window.snapshot(registry)
    summary = None
    if traced:
        import trace_reduce

        tdir = os.path.join(HERE, ".trace", cell.workload["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                win = load.closed_loop(
                    system, prepared, int(mix["in_flight"]), math.inf,
                    max_requests=int(mix["trace_requests"]))
        finally:
            jax.profiler.stop_trace()
    else:
        win = load.closed_loop(system, prepared, int(mix["in_flight"]),
                               seconds)
    compiles.phase = "after"
    delta = window.Delta(before, window.snapshot(registry))
    failures = path_failures(delta, compiles) if program else []

    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    system.close()

    if traced:
        t0 = time.perf_counter()
        path = trace_reduce.find_xplane(tdir)
        summary = trace_reduce.summarize(trace_reduce.load(path))
        log(f"trace {path} ({os.path.getsize(path)} bytes) read in "
            f"{time.perf_counter() - t0:.1f}s: busy {summary.busy_s:.6f}s "
            f"of {summary.window_s:.6f}s; truncated {summary.truncated}; "
            f"programs "
            + json.dumps({k: round(v, 6) for k, v in sorted(
                summary.module_s.items(), key=lambda kv: -kv[1])[:12]}))

    lat = [round(o.done - o.submitted, 3) for o in win.completed]
    log(f"window: {len(win.outcomes)} requests submitted, "
        f"{len(win.completed)} answered, in "
        f"{win.end - win.t0:.3f}s; latencies {lat}s")
    if win.lags:
        log(f"client lag from a completion to the next submit: mean "
            f"{statistics.fmean(win.lags) * 1e3:.3f} ms, max "
            f"{max(win.lags) * 1e3:.3f} ms")
    log(f"peak_bytes_in_use {peak}; host peak RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f} GiB")
    for f in failures:
        log(f"path failure: {f}")

    run = Run(cell, setup_s, win, delta, compiles, summary, failures)
    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"], traced)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t0 = time.perf_counter()
    counts = check.compare(warm_win.outcomes + win.outcomes)
    log(f"reference check of {len(warm_win.outcomes) + len(win.outcomes)} "
        f"requests took {time.perf_counter() - t0:.1f}s")
    correct, checks = check.report(counts)
    errors = [o for o in win.outcomes if o.error is not None]
    for o in errors[:5]:
        log(f"request {o.request.index} failed: {o.error}")
    failed = len(win.outcomes) if failures else len(errors)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(win.outcomes),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    traced = bool(args.trace)
    cell = resolve(ROOT, args.workload, traced)
    chips = int(cell.workload["chips"])
    import systems

    program = systems.program_system(cell.mix)

    # the compile cache lives at a fixed path inside this checkout, with no
    # size cap, so that only a checkout's first run compiles: a cache placed
    # and capped from outside would be shared with other checkouts, and an
    # executable over its cap would compile in every run. libtpu writes no
    # logs. Every run, traced or not, compiles with libtpu's per-operation
    # trace off: the programs' scans run millions of tiny operations, whose
    # trace overflows the profiler's buffers within the first seconds. The
    # flag is part of the compile cache's key, so it is the same in every run.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(
        os.environ.get("LIBTPU_INIT_ARGS", "").split()
        + [HLO_TRACE_OFF])
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"benchmark: JAX found no TPU (backend {backend!r})",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_max_size", -1)
    sys.path.insert(0, ROOT)
    from charon_tpu.utils import jaxcache

    cache = jaxcache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device {devices[0].device_kind} x{len(devices)}, using {chips}; "
        f"compile cache {cache}")
    system = program(chips)
    result = run_cell(cell, args.seed, args.seconds, traced, system, devices,
                      chips)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
