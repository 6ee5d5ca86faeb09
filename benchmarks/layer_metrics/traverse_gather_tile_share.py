"""The share of a hop's level-0 plan indices that ``hg_gather_or`` reduces
a sublane tile of chunks a loop step, in percent: the program's gauge
``bfs.gather.tile_share``, set where a plan's device arrays are made (the
newest plan's — the restricted one in a typed cell). 100 where every class
width of the plan is the kernel's on this backend; 0 where the XLA gather
serves instead. None under a program that sets no such gauge."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    share = default_registry().get("bfs.gather.tile_share")
    return None if share is None else float(share.value)
