"""Device seconds a connected-components run under scopes ``hg.wcc.stage1``
and ``hg.wcc.stage2``: the two min pyramids of every round — the XLA gather
of one int32 label an index and the min over each chunk, level 0 and the
upper levels. None under a program without the scopes."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.wcc.stage1",
                                              "hg.wcc.stage2")
