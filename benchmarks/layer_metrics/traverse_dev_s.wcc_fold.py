"""Device seconds a connected-components run under scope ``hg.wcc.fold``:
every round's ``label[v] = min(label[v], buf[out_map[v]])`` over the plan's
active row blocks, and the count of the rows it lowered. None under a
program without the scope."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.wcc.fold")
