"""The share of the rows the updates ending a hop fold that ``hg_gather_or``
fetched, in percent: the program's counter ``bfs.update.rows_kernel`` (the
listed rows of an update dispatch whose fetch took the kernel at width 1,
0 for one that took the XLA gather) over ``bfs.update.rows_visited`` (the
listed rows of every update dispatch), over the process, warm-up included.
100 where every update's state is a 4096-seed bitmap on a TPU; 0 on a
backend without the kernel. None under a program without the counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    kernel, visited = (reg.get("bfs.update.rows_kernel"),
                       reg.get("bfs.update.rows_visited"))
    if kernel is None or visited is None or not visited.value:
        return None
    return 100.0 * kernel.value / visited.value
