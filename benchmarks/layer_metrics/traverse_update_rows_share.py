"""The share of the bitmap's rows that the updates ending a hop fold, in
percent: the program's counter ``bfs.update.rows_visited`` (the listed row
blocks × a block's rows, once an update dispatch) over
``bfs.update.rows_total`` (the bitmap's rows, once an update dispatch), over
the process, warm-up included — every dispatch of a run folds the same
blocks, so the window's ratio is the process's. 100 where every block holds
a row a hop can reach; a fifth where a store lays its entities out before
its links and nothing targets a link. None under a program without the
counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    visited, total = (reg.get("bfs.update.rows_visited"),
                      reg.get("bfs.update.rows_total"))
    if visited is None or total is None or not total.value:
        return None
    return 100.0 * visited.value / total.value
