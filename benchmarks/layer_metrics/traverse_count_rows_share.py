"""The share of the bitmap's rows that the counting passes fold, in percent:
the program's counter ``bfs.count.rows_visited`` (the listed row blocks × a
block's rows, once a dispatch of ``_deg_sum`` or ``_reach_counts``) over
``bfs.count.rows_total`` (the bitmap's rows, once such a dispatch), over the
process, warm-up included — every operation of a run lists the same blocks,
so the window's ratio is the process's. The blocks listed are those in which
the counted state can hold a bit: the plan's active blocks and the seeds'
own. 100 where every block holds a row a hop can reach; a fifth where a
store lays its entities out before its links and nothing targets a link.
None under a program without the counters, or in a cell that runs no
counting pass."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    visited, total = (reg.get("bfs.count.rows_visited"),
                      reg.get("bfs.count.rows_total"))
    if visited is None or total is None or not total.value:
        return None
    return 100.0 * visited.value / total.value
