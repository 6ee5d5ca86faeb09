"""An operation's SELF time: its wall less what its direct children cover —
the first hop's rule, ``_seed_links``, pairs made ahead, the block loop's
glue, anything the host does under no phase of its own — over the window's
operations ÷ operations. With the children's seconds it adds up to the
operations' wall. None under a program that keeps no record of a phase
instance."""

from harness import phase_log


def read(ctx):
    return phase_log.per_operation(ctx, lambda w: sum(
        map(phase_log.wall, w.ops)) - sum(map(phase_log.wall, w.children())))
