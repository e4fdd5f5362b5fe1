"""The closed loop that offers a mix's requests to the system under test.

A client keeps `in_flight` requests outstanding: it submits the next one as
soon as one completes, for `seconds` from the first submission, and then
goes on to the end of the slot it is in, so that every run measures one
whole slot or more (or stops after `max_requests`). Then it waits for every submitted
request to finish. Each request's latency runs from the moment the client
starts its submit call to the moment its answer is set.
"""

from __future__ import annotations

import math
import time
from concurrent.futures import FIRST_COMPLETED, Future, wait
from dataclasses import dataclass, field

import jax


class PoolExhausted(RuntimeError):
    """The set-up made fewer requests than the window consumed."""


@dataclass
class Outcome:
    request: object
    submitted: float
    future: Future | None = None
    done: float | None = None
    answer: object = None
    error: str | None = None


@dataclass
class Window:
    t0: float
    outcomes: list[Outcome] = field(default_factory=list)
    # how long a freed slot waited for the client's next submit call
    lags: list[float] = field(default_factory=list)

    @property
    def completed(self) -> list[Outcome]:
        return [o for o in self.outcomes
                if o.done is not None and o.error is None]

    @property
    def end(self) -> float:
        done = [o.done for o in self.completed]
        return max(done) if done else self.t0

    def rate(self) -> float | None:
        """Work of every completed request over the time from the first
        submission to the last completion."""
        span = self.end - self.t0
        if not self.completed or span <= 0:
            return None
        return sum(o.request.size for o in self.completed) / span


def _collect(rec: Outcome) -> None:
    """Fill a finished request's answer or error from its future."""
    fut = rec.future
    if fut is None or not fut.done():
        if rec.error is None and rec.done is None:
            rec.error = "no answer"
        return
    while rec.done is None:  # its done-callback is still being invoked
        time.sleep(0.001)
    exc = fut.exception()
    if exc is not None:
        rec.error = f"{type(exc).__name__}: {exc}"
    else:
        rec.answer = fut.result()


def closed_loop(system, prepared, in_flight: int, seconds: float,
                max_requests: int | None = None, late_wait: float = 60.0,
                clock=time.perf_counter) -> Window:
    """Drive `system.submit` with `in_flight` outstanding requests.

    `prepared` yields (request, args) pairs made in set-up; running out of
    them inside the window raises PoolExhausted."""
    it = iter(prepared)
    win = Window(t0=clock())
    end = win.t0 + seconds
    outstanding: dict[Future, Outcome] = {}
    freed: list[float] = []       # completion times of slots not yet refilled
    call_end = win.t0             # when the client's last submit call returned
    slot_open = False             # the last request submitted does not end its slot
    while True:
        now = clock()
        if max_requests is not None:
            if len(win.outcomes) >= max_requests:
                break
        elif now >= end and not slot_open and win.outcomes:
            break
        if len(outstanding) < in_flight:
            try:
                req, args = next(it)
            except StopIteration:
                raise PoolExhausted(
                    f"the set-up made {len(win.outcomes)} requests and the "
                    f"window wanted more") from None
            slot_open = not req.last_of_slot
            if freed:
                win.lags.append(now - max(freed.pop(0), call_end))
            rec = Outcome(req, now)
            win.outcomes.append(rec)
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    rec.future = system.submit(args)
            except Exception as exc:  # noqa: BLE001 — a failed request
                rec.done, rec.error = clock(), f"{type(exc).__name__}: {exc}"
                freed.append(rec.done)
                continue
            finally:
                call_end = clock()
            rec.future.add_done_callback(
                lambda _f, r=rec: setattr(r, "done", clock()))
            outstanding[rec.future] = rec
            continue
        with jax.profiler.TraceAnnotation("bench.wait"):
            # an endless loop (the warm-up's) waits with no timeout: a
            # lock's wait takes none longer than the platform's time_t
            done, _ = wait(list(outstanding),
                           timeout=end - now if now < end < math.inf
                           else None,
                           return_when=FIRST_COMPLETED)
        for f in done:
            rec = outstanding.pop(f)
            while rec.done is None:  # its done-callback is being invoked
                time.sleep(0.0001)
            freed.append(rec.done)
        freed.sort()
    with jax.profiler.TraceAnnotation("bench.drain"):
        wait(list(outstanding), timeout=late_wait)
    for rec in win.outcomes:
        _collect(rec)
    return win
