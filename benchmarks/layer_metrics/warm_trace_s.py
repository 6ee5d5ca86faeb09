"""Seconds of ``warm_s`` JAX spent TRACING the warm-up's programs to jaxprs,
a Pallas kernel's trace at each of its call sites among them: paid by every
run, compile cache or no.
The ``jit.trace_s`` seconds of the phase records under the warm-up's
operations (``harness/phase_log.py``; one ``jax.monitoring`` listener in
the program's ``obs/device.py`` puts JAX's seconds on the phase that paid
them). None under a program without it."""

from harness import phase_log


def read(ctx):
    return phase_log.warm_jit_s(ctx, "trace_s")
