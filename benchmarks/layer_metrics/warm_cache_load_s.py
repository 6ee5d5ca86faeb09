"""Seconds of ``warm_s`` JAX spent READING executables from the persistent
compile cache (every program a seen seed needs, all but the stage programs
on an unseen one).
The ``jit.load_s`` seconds of the phase records under the warm-up's
operations (``harness/phase_log.py``; one ``jax.monitoring`` listener in
the program's ``obs/device.py`` puts JAX's seconds on the phase that paid
them). None under a program without it."""

from harness import phase_log


def read(ctx):
    return phase_log.warm_jit_s(ctx, "load_s")
