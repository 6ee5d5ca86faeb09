"""Device seconds a traversal in what only counts: scopes
``hg.bfs.seed_bitmap`` + ``hg.bfs.deg_sum`` + ``hg.bfs.reach_counts``."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(
        ctx, "hg.bfs.seed_bitmap", "hg.bfs.deg_sum", "hg.bfs.reach_counts")
