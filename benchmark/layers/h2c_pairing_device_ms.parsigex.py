"""h2c_pairing_device_ms.parsigex: device milliseconds per traced peer-set
verify of the verify programs (ops/h2c.py map_to_g2 and ops/pairing.py
pairing_check), from the profiler trace."""


def read(run):
    if run.trace is None or run.trace.truncated:
        return None  # no device record of every traced call
    n = len(run.window.completed)
    s = run.trace.module_seconds("map_to_g2", "pairing_check")
    return s * 1e3 / n if n and s > 0 else None
