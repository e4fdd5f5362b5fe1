"""pk_planes_ms.parsigex: milliseconds per traced peer-set verify of the
program's `ops/verify/pk_planes` spans (PlaneStore digest and lookup, and on
a miss the pubkeys' parse, device decode and subgroup check with their
waits), from the profiler trace."""

import spans


def read(run):
    s = spans.of(run)
    n = len(run.window.completed)
    if s is None or not n or not s.in_window("ops/verify/pk_planes"):
        return None
    return s.ms("ops/verify/pk_planes") / n
