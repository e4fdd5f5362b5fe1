"""setup_compile_s: seconds of set-up in which a backend compile ran,
persistent cache reads included (the union of jax.monitoring's
backend_compile_duration events)."""

import window


def read(run):
    return run.compiles.total("setup", window.COMPILE_EVENT)
