"""Seconds an operation spends making and placing a sparse first hop on
the host: steps ``expand`` (``_seed_pairs``) and ``place`` (the pairs'
upload and the placement's dispatches) of phase ``hg.bfs.hop.sparse`` over
the window's operations ÷ operations (pairs a caller made ahead of the
phase, as a pair search does, are not in it: they are the operation's self
time). None under a program that keeps no record of a phase instance."""

from harness import phase_log


def read(ctx):
    return phase_log.per_operation(ctx, lambda w: sum(
        phase_log.step_s(r, "expand", "place") for r in w.below
        if r["name"] == "hg.bfs.hop.sparse"))
