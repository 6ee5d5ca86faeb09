"""Device seconds a traversal under scope ``hg.bfs.stage1.lvl0``: the level-0
row gather + OR of stage 1 (targets: which links touch a visited atom)."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.bfs.stage1.lvl0")
