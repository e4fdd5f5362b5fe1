"""Record `spans_trace.xplane.pb`: a CPU profile of three closed-loop calls,
each a `tbls/verify_batch` span holding program spans around two tiny
jitted functions (`step`, `other`) and an `ops/device_wait` on each, inside
`bench.window` / `bench.submit` as benchmark/load.py annotates.

    JAX_PLATFORMS=cpu python3 benchmark/tests/data/record_spans_trace.py
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from charon_tpu.utils import tracer  # noqa: E402


@jax.jit
def step(x):
    return jnp.sin(x) @ x


@jax.jit
def other(x):
    return (x * 2.0 + 1.0).sum()


def main() -> None:
    # keep only the file's name in the programs' source metadata
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    x = jnp.ones((64, 64), jnp.float32)
    step(x).block_until_ready()
    other(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"), \
                    tracer.start_span("tbls/verify_batch", n=i, path="device"):
                with tracer.start_span("ops/verify/pk_planes", chunks=1):
                    y = step(x)
                    with tracer.start_span("ops/device_wait", program="step"):
                        y.block_until_ready()
                with tracer.start_span("ops/verify/pack", lanes=64):
                    time.sleep(0.002)  # host work the device waits for
                    z = other(x)
                with tracer.start_span("ops/device_wait", program="other"):
                    z.block_until_ready()
            time.sleep(0.001)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(HERE, "spans_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
