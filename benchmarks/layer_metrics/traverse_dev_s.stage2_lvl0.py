"""Device seconds a traversal under scope ``hg.bfs.stage2.lvl0``: the level-0
row gather + OR of stage 2 (incidence: which atoms a live link reaches)."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.bfs.stage2.lvl0")
