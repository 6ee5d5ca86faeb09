"""Plan indices a hop gathers per relation entry of the plan the window
ran over: the program's gauges ``bfs.plan.total_indices`` over
``bfs.plan.entries`` (set where a plan is built; the newest plan is the
window's — the restricted one in a typed cell). The padding the gathers
pay: 1.0 would be no padding. None under a program that sets no such
gauge."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    indices, entries = (reg.get("bfs.plan.total_indices"),
                        reg.get("bfs.plan.entries"))
    if indices is None or entries is None or not entries.value:
        return None
    return indices.value / entries.value
