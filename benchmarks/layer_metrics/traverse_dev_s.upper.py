"""Device seconds a traversal in the upper levels of both reduction pyramids
(hub rows; XLA gather): scopes ``hg.bfs.stage1.upper`` + ``hg.bfs.stage2.upper``."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(
        ctx, "hg.bfs.stage1.upper", "hg.bfs.stage2.upper")
