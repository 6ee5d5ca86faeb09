"""Seconds of ``warm_s`` in the backend's COMPILER — the stage programs whose
shapes follow an unseen seed's plan — LESS ``warm_cache_load_s``: JAX's
compile event holds the load of an executable the persistent cache had.
The ``jit.compile_s`` seconds of the phase records under the warm-up's
operations (``harness/phase_log.py``; one ``jax.monitoring`` listener in
the program's ``obs/device.py`` puts JAX's seconds on the phase that paid
them). None under a program without it."""

from harness import phase_log


def read(ctx):
    return phase_log.warm_jit_s(ctx, "compile_s")
