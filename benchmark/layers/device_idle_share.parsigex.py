"""device_idle_share.parsigex: 1 - (union of device operation intervals) /
(traced window), from the profiler trace of the parsigex cell's traced
peer-set verifies."""


def read(run):
    return run.trace.idle_share if run.trace and run.trace.busy_s > 0 else None
