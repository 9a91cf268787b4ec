"""Record the small trace that bench/tests/test_trace.py reduces.

    python bench/tests/record_trace.py <out-dir>      (on a TPU)

Three calls of one small jitted program (``bench_probe_toy``), each inside
a host span named ``probe``, with about 20 ms of host sleep between them
inside spans named ``idle wait``: so the trace has device operations, a
named program, host spans and idle gaps that the spans explain.
"""

import pathlib
import sys
import time

import jax
import jax.numpy as jnp


@jax.jit
def bench_probe_toy(a, b):
    return jnp.tanh(a @ b) + a.sum()


def main(out):
    a = jnp.ones((512, 512), jnp.float32)
    b = jnp.ones((512, 512), jnp.float32)
    bench_probe_toy(a, b).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("probe"):
            bench_probe_toy(a, b).block_until_ready()
        with jax.profiler.TraceAnnotation("idle wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(pathlib.Path(sys.argv[1]))
