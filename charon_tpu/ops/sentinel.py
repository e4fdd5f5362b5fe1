"""Runtime compile/transfer sentinel: prove the steady state never recompiles.

The whole pipeline's per-slot budget rests on an invariant nothing used to
enforce at runtime: after warmup, a slot must trigger ZERO new XLA compiles
and ZERO implicit host<->device transfers. One cold pairing compile costs
minutes on TPU and would blow every duty deadline in the 12 s slot. This
module makes the invariant observable and enforced:

  * install() hooks jax's compile telemetry. Primary path: the
    jax.monitoring event stream — `/jax/core/compile/backend_compile_duration`
    fires exactly once per XLA backend compile (nothing fires on a warm
    same-shape call; a shape change re-fires), and
    `/jax/compilation_cache/cache_hits` marks a persistent-cache
    deserialize, which still means the in-memory jit cache missed and the
    program was re-traced — a steady-state hazard all the same. Fallback
    path (older/stripped jax builds without jax.monitoring): a logging
    handler intercepting the "Compiling <fn> ..." records jax's dispatch
    and compiler modules emit.

  * Every observed compile increments ops_jit_compiles_total{region}.
    The region label comes from the thread-local region() context the warm
    paths, the slot pipeline and the bulk verify wrap themselves in —
    "warm" during AOT warmup, "slot" inside SigAggPipeline dispatch,
    "verify" inside plane_agg.rlc_verify_batch's device path, "other" when
    nobody declared a region. Each backend compile (persistent-cache loads
    included) also adds a `jax_compile` event (fun, seconds, region) to the
    tracer span open in the compiling thread, so a recompile inside a
    served duty shows on that duty's span in the flight recorder.

  * steady_state() arms a process-global armed-window flag and (in the
    entering thread) jax.transfer_guard("disallow"). Any compile observed
    anywhere in the process while a window is armed increments
    ops_steady_recompile_total, strikes the plane circuit breaker
    (ops/guard.py — a recompiling steady state is a failing device plane),
    and trips the sigagg_steady_state_recompile health rule. Implicit
    transfers in the arming thread raise XlaRuntimeError immediately
    (jax's transfer guard is thread-local; worker threads that must be
    covered wrap their stage in transfer_guarded()).

Benches and dryruns call compiles_summary() after their run to emit the
`compiles: {warmup: N, steady: 0}` JSON-tail key the budget tests assert on.

The sentinel is PER-PROCESS by design: on a multi-host mesh every host
process installs its own and the steady-state invariant must hold on each
host independently (the multihost dryrun asserts steady == 0 in every
worker's tail). Cross-host HostLink waits are network time, not compiles
— they never arm or strike anything here.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Iterator

from ..utils import log, metrics, tracer

_log = log.with_topic("sentinel")

_compiles_c = metrics.counter(
    "ops_jit_compiles_total",
    "XLA compiles observed since sentinel install (backend compiles plus "
    "persistent-cache deserializes)", ("region",))
_steady_c = metrics.counter(
    "ops_steady_recompile_total",
    "compiles observed while a steady-state window was armed — the "
    "steady state recompiled; always a bug")

# jax.monitoring event names (as emitted by jax 0.9):
#   backend_compile_duration fires once per real XLA compile;
#   cache_hits fires when the persistent compilation cache serves a miss
#   of the in-memory jit cache (a re-trace — still a steady-state hazard).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_tls = threading.local()
_lock = threading.Lock()
_installed = False
_mode = "off"  # "monitoring" | "logger" | "off"
_total = 0
_steady_total = 0
_armed_windows = 0  # process-global count of armed steady_state windows


def _current_region() -> str:
    return getattr(_tls, "region", "other")


@contextlib.contextmanager
def region(name: str) -> Iterator[None]:
    """Label compiles observed in this thread with `name` (the monitoring
    events carry no function names, so attribution is declared, not
    inferred). Nests; inner-most wins."""
    prev = getattr(_tls, "region", None)
    _tls.region = name
    try:
        yield
    finally:
        if prev is None:
            del _tls.region
        else:
            _tls.region = prev


def _on_compile(reg: str | None = None) -> str:
    global _total, _steady_total
    if reg is None:
        reg = _current_region()
    armed = False
    with _lock:
        _total += 1
        if _armed_windows > 0:
            _steady_total += 1
            armed = True
    _compiles_c.inc(reg)
    if armed:
        _steady_c.inc()
        _log.warn("steady-state recompile", region=reg)
        from . import guard  # local: guard pulls in the breaker machinery

        guard.BREAKER.record_failure()
    return reg


def _duration_listener(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        reg = _on_compile()
        tracer.event("jax_compile", fun=str(kwargs.get("fun_name", "?")),
                     seconds=duration, region=reg)


def _event_listener(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _on_compile()


class _CompileLogHandler(logging.Handler):
    """Fallback compile detector for jax builds without jax.monitoring:
    jax's compiler/dispatch modules log 'Compiling <fn> ...' once per
    compile request."""

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # noqa: BLE001 — a broken record is not a compile
            return
        if msg.startswith("Compiling "):
            _on_compile()


_FALLBACK_LOGGERS = ("jax._src.compiler", "jax._src.dispatch")


def install() -> str:
    """Idempotently hook compile telemetry. Returns the active mode
    ("monitoring" or "logger"). Safe to call from every entry point —
    benches, dryruns, the pipeline, and tests all funnel through here."""
    global _installed, _mode
    with _lock:
        if _installed:
            return _mode
        _installed = True
    try:
        from jax import monitoring as _mon

        _mon.register_event_duration_secs_listener(_duration_listener)
        _mon.register_event_listener(_event_listener)
        _mode = "monitoring"
    except Exception:  # noqa: BLE001 — stripped builds fall back to logs
        handler = _CompileLogHandler()
        for name in _FALLBACK_LOGGERS:
            lg = logging.getLogger(name)
            lg.addHandler(handler)
            if lg.getEffectiveLevel() > logging.DEBUG:
                lg.setLevel(logging.DEBUG)
        _mode = "logger"
    _log.info("compile sentinel installed", mode=_mode)
    return _mode


def mode() -> str:
    return _mode


class SteadyWindow:
    """Handle yielded by steady_state(): exposes how many compiles landed
    inside THIS window (the counters are process-global and monotonic)."""

    def __init__(self) -> None:
        with _lock:
            self._entry_steady = _steady_total

    @property
    def compiles(self) -> int:
        with _lock:
            return _steady_total - self._entry_steady


@contextlib.contextmanager
def steady_state(transfer: str | None = "disallow") -> Iterator[SteadyWindow]:
    """Arm the steady-state invariant: while the context is live, any
    compile observed on ANY thread counts as a steady recompile (metric +
    breaker strike + health rule), and — with transfer != None — jax's
    transfer guard disallows implicit host<->device transfers in the
    entering thread. Pass transfer=None when arming from a coordinator
    thread whose worker threads do the device work (the guard is
    thread-local; wrap workers in transfer_guarded() instead)."""
    global _armed_windows
    install()
    win = SteadyWindow()
    with _lock:
        _armed_windows += 1
    try:
        if transfer is None:
            yield win
        else:
            import jax

            with jax.transfer_guard(transfer):
                yield win
    finally:
        with _lock:
            _armed_windows -= 1


@contextlib.contextmanager
def transfer_guarded(level: str = "disallow") -> Iterator[None]:
    """Thread-scoped transfer guard for worker threads covered by a
    steady_state() armed elsewhere (jax's guard is thread-local)."""
    import jax

    with jax.transfer_guard(level):
        yield


def steady_armed() -> bool:
    with _lock:
        return _armed_windows > 0


def compiles_summary() -> dict[str, int]:
    """The benches' JSON-tail key: compiles observed outside any armed
    steady window ("warmup") vs inside one ("steady" — must be 0 on a
    warm cache)."""
    with _lock:
        return {"warmup": _total - _steady_total, "steady": _steady_total}


def counts() -> tuple[int, int]:
    """(total, steady) raw compile counts — test hook."""
    with _lock:
        return _total, _steady_total


def reset_for_testing() -> None:
    """Zero the window accounting (NOT the listener hooks — those are
    process-lifetime). Metrics counters stay monotonic; health rules read
    deltas, tests read counts()."""
    global _total, _steady_total, _armed_windows
    with _lock:
        _total = 0
        _steady_total = 0
        _armed_windows = 0


_STEADY_AFTER_ENV = "CHARON_TPU_STEADY_AFTER"


def steady_after_default() -> int | None:
    """Pipeline opt-in knob: arm steady_state after N dispatched slots
    (0/unset = never — existing callers that legitimately vary shapes
    across slots must not strike the breaker)."""
    raw = os.environ.get(_STEADY_AFTER_ENV, "").strip()
    if not raw:
        return None
    try:
        n = int(raw)
    except ValueError:
        return None
    return n if n > 0 else None
