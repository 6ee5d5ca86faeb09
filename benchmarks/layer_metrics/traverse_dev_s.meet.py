"""Device seconds a batch under scope ``hg.bfs.meet``: the meet test of a
pair search's two balls (an AND and an OR-fold down both bitmaps), once an
expansion. None under a program without the scope."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.bfs.meet")
