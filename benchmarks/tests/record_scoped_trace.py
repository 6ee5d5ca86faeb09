"""How ``data/scoped_trace.xplane.pb`` was recorded (one TPU v5e chip).

    python benchmarks/tests/record_scoped_trace.py <out-dir>

Two small programs named the way the program names its own (module names
through ``__name__``, device scopes through ``jax.named_scope``), run three
times under the program's own ``obs.profile`` session and ``obs.phase`` host
spans:

- ``hg_test_a``: one matmul under scope ``hg.test.a``;
- ``hg_test_b``: a ``lax.scan`` under scope ``hg.test.b`` whose body holds a
  cumulative sum — the compiler rewrites it into operations that carry no
  path, which have to inherit the loop's scope — and, OUTSIDE every scope, a
  transposed copy, which has to stay unscoped.

``check_scope_reduce.py`` holds ``harness/scope_reduce.py`` to what this
trace contains.
"""

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from hypergraphdb_tpu.obs import phase, profile  # noqa: E402


def hg_test_a(x):
    with jax.named_scope("hg.test.a"):
        return (x @ x).sum()


def hg_test_b(x):
    with jax.named_scope("hg.test.b"):
        def body(carry, row):
            return carry + jnp.cumsum(row * 2 + 1, axis=0), None

        out, _ = jax.lax.scan(body, jnp.zeros_like(x[0]), x)
    return out.T * 3


def main(out_dir: str) -> None:
    a, b = jax.jit(hg_test_a), jax.jit(hg_test_b)
    x = jnp.ones((512, 512), jnp.float32)
    xs = jnp.ones((8, 512, 256), jnp.float32)
    jax.block_until_ready([a(x), b(xs)])
    logdir = os.path.join(out_dir, "_trace")
    with profile(logdir) as on:
        if not on:
            raise RuntimeError("the profiler did not start")
        for _ in range(3):
            with phase("hg.test.run_a"):
                jax.block_until_ready(a(x))
            with phase("hg.test.run_b"):
                jax.block_until_ready(b(xs))
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out_dir, "scoped_trace.xplane.pb"))
    shutil.rmtree(logdir)


if __name__ == "__main__":
    main(sys.argv[1])
