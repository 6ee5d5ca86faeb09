"""How ``data/small_trace.xplane.pb`` was recorded (one TPU v5e chip).

    python benchmarks/tests/record_small_trace.py <out-dir>

Two small named programs run a few times under the profiler, with
``bench.*`` host spans around them and a pause in one of them, so that the
trace holds device operations, modules, annotated host spans and idle gaps.
``harness/selfcheck.py`` holds the reducer to what this trace contains.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def small_matmul(x):
        return (x @ x).sum()

    @jax.jit
    def small_scan(x):
        return jnp.cumsum(x * 2 + 1, axis=0)

    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready([small_matmul(x), small_scan(x)])
    logdir = os.path.join(out_dir, "_trace")
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1        # annotations only: a small file
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.matmul"):
            jax.block_until_ready(small_matmul(x))
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.01)
        with jax.profiler.TraceAnnotation("bench.scan"):
            jax.block_until_ready(small_scan(x))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out_dir, "small_trace.xplane.pb"))
    shutil.rmtree(logdir)


if __name__ == "__main__":
    main(sys.argv[1])
