"""The share of a hop's plan indices that the upper pyramid levels gather,
in percent: the program's gauge ``bfs.plan.upper_indices`` over
``bfs.plan.total_indices`` (both set where a plan is built; the newest plan
is the window's — the restricted one in a typed cell). What is left for
rows that did not finish in the chunk they were gathered in: 0 where every
row fits a level-0 width class. None under a program that sets no such
gauge."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    upper, indices = (reg.get("bfs.plan.upper_indices"),
                      reg.get("bfs.plan.total_indices"))
    if upper is None or indices is None or not indices.value:
        return None
    return 100.0 * upper.value / indices.value
