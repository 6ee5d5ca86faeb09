"""Device seconds a PageRank run under scope ``hg.pr.update``: every
iteration's fold ``y[v] = buf[out_map[v]]`` by replacement over the plan's
active row blocks, the dangling mass, and the elementwise pass that makes
the new ranks and their sum. None under a program without the scope."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.pr.update")
