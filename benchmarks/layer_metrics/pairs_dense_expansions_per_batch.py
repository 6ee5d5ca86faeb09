"""Dense expansions a batch of ``ops.pair_distances``: the program's counter
``bfs.pairs.expansions.dense`` over ``bfs.pairs.batches``, over the process,
warm-up included (every batch of a cell's run costs the same). 2.0 where a
batch is two sparse first hops and a dense hop on each ball; a forward-only
search to the same depth runs 3. None under a program without the
counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    dense, batches = (reg.get("bfs.pairs.expansions.dense"),
                      reg.get("bfs.pairs.batches"))
    if batches is None or not batches.value:
        return None
    return (0 if dense is None else dense.value) / batches.value
