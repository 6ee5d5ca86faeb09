"""Iterations a PageRank run: the program's counter ``pr.iterations`` over
``pr.runs``, over the process, warm-up included (every run of the cell asks
for the same count). The traffic's count, 10, where every run dispatches
every iteration; a loop that stopped early, or ran on, would move it. None
under a program without the counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    iterations, runs = reg.get("pr.iterations"), reg.get("pr.runs")
    if runs is None or not runs.value or iterations is None:
        return None
    return iterations.value / runs.value
