"""Device seconds a PageRank run under scopes ``hg.pr.stage1`` and
``hg.pr.stage2``: the two sum pyramids of every iteration — the XLA gather
of one float32 share an index and the sum over each chunk, level 0 and the
upper levels, with the shares and the link weights they are scaled by.
None under a program without the scopes."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.pr.stage1",
                                              "hg.pr.stage2")
