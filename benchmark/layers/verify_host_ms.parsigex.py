"""verify_host_ms.parsigex: host milliseconds per traced peer-set verify: the
program's `tbls/verify_batch` spans less the union of the `ops/device_wait`
spans inside them, from the profiler trace. With one set in flight this is
the host work the chip waits for."""

import spans


def read(run):
    s = spans.of(run)
    n = len(run.window.completed)
    if s is None or not n or not s.in_window("tbls/verify_batch"):
        return None
    return s.less_waits_ms("tbls/verify_batch") / n
