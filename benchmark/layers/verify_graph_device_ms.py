"""verify_graph_device_ms: device milliseconds per traced peer-set verify of
the bulk verify program (plane_agg._verify_slot_jit: decompress, subgroup
check, RLC MSMs), from the profiler trace."""


def read(run):
    if run.trace is None or run.trace.truncated:
        return None  # no device record of every traced call
    n = len(run.window.completed)
    s = run.trace.module_seconds("_verify_slot_jit")
    return s * 1e3 / n if n and s > 0 else None
