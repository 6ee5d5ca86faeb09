"""The share of the rows a whole-graph operator's fold visits whose fetch
the scalar form of the row-gather kernel (``hg_gather_scalar`` at width 1)
served, in percent: the program's counter ``scalar.fold.rows_kernel`` (the
listed rows of a WCC round's or a PageRank iteration's fold whose fetch
``buf[out_map[v]]`` took the kernel, counted at dispatch) over
``scalar.fold.rows`` (every listed row of those folds), over the process,
warm-up included. 100 on a TPU where the stage buffer fits the kernel's
table; 0 on a backend without the kernel. None under a program without the
counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    kernel, rows = (reg.get("scalar.fold.rows_kernel"),
                    reg.get("scalar.fold.rows"))
    if kernel is None or rows is None or not rows.value:
        return None
    return 100.0 * kernel.value / rows.value
