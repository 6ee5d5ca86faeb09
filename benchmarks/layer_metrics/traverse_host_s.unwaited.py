"""Seconds an operation's calling thread spent NOT waiting for the device:
over the window's operations, (the operation's wall less every ``wait``
step under it) ÷ operations — expansion, uploads, dispatches, frees, the
host's decisions and the glue between phases, whether the device ran beside
them or not (what it did not overlap is ``breakdown.idle_gaps``). From the
program's own records (``harness/phase_log.py``); None under a program that
keeps none."""

from harness import phase_log


def read(ctx):
    return phase_log.per_operation(ctx, lambda w: sum(
        map(phase_log.wall, w.ops))
        - sum(phase_log.step_s(r, "wait") for r in w.below))
