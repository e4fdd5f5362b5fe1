"""setup_trace_s: seconds of set-up in which the process was tracing or
lowering, which no compile cache saves (the union of jax.monitoring's
jaxpr_trace_duration and jaxpr_to_mlir_module_duration events)."""

import window


def read(run):
    return run.compiles.total("setup", window.TRACE_EVENT, window.LOWER_EVENT)
