"""Device seconds a match under scope ``hg.bfs.frontier_replace``: the new
frontier ``reach[out_map]`` written over the old one, once a dense step.
None under a program without the scope."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.bfs.frontier_replace")
