"""Device seconds a traversal under scope ``hg.bfs.visited_update``: the
visited bitmap OR the hop's reach rows, in place."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.bfs.visited_update")
