"""Device seconds a traversal placing the sparse first hop's bits: scope
``hg.bfs.sparse_hop``. None under a program whose first hop is a pull hop."""

from harness import scope_reduce


def read(ctx):
    return scope_reduce.seconds_per_traversal(ctx, "hg.bfs.sparse_hop")
