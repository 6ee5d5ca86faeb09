"""``warm_s`` less ``warm_trace_s``, ``warm_lower_s``, ``warm_compile_s``
and ``warm_cache_load_s``: the warm-up's one operation on the device, its
host work, and whatever no JAX event holds. None under a program without
the records the four are read from."""

from harness import phase_log


def read(ctx):
    parts = [phase_log.warm_jit_s(ctx, s) for s in phase_log.JIT_STAGES]
    warm_s = ctx["setup"].get("warm_s")
    if warm_s is None or None in parts:
        return None
    return warm_s - sum(parts)
