"""Seconds of ``warm_s`` JAX spent LOWERING the warm-up's programs to MLIR
modules (a Pallas kernel to Mosaic among it): paid by every run, because the
compile cache is looked up by the lowered text.
The ``jit.lower_s`` seconds of the phase records under the warm-up's
operations (``harness/phase_log.py``; one ``jax.monitoring`` listener in
the program's ``obs/device.py`` puts JAX's seconds on the phase that paid
them). None under a program without it."""

from harness import phase_log


def read(ctx):
    return phase_log.warm_jit_s(ctx, "lower_s")
