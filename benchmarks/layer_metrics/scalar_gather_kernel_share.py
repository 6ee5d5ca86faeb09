"""The share of a whole-graph operator's level-0 gather indices that the
scalar form of the row-gather kernel (``hg_gather_scalar``) served, in
percent: the program's counter ``scalar.gather.indices_kernel`` (the
level-0 indices of a WCC round's or a PageRank iteration's two pyramids
that took the kernel, counted at dispatch from the plan's lengths) over
``scalar.gather.indices`` (all of their level-0 indices), over the process,
warm-up included. Under 100 only by the ragged tails of a class under
``pallas_gather.MIN_INDICES``; 0 on a backend without the kernel. None
under a program without the counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    kernel, total = (reg.get("scalar.gather.indices_kernel"),
                     reg.get("scalar.gather.indices"))
    if kernel is None or total is None or not total.value:
        return None
    return 100.0 * kernel.value / total.value
