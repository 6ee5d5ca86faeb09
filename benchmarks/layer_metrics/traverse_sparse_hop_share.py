"""The share of the process's hops that took the sparse side: the count of
phase ``hg.bfs.hop.sparse`` over that plus the count of
``hg.bfs.hop.stage1`` (one per pull hop), warm-up included. None under a
program that records no sparse hop."""


def _count(name: str) -> int:
    from hypergraphdb_tpu.obs import default_registry

    hist = default_registry().get(f"phase.{name}")
    return 0 if hist is None else hist.count


def read(ctx):
    sparse = _count("hg.bfs.hop.sparse")
    if not sparse:
        return None
    return 100.0 * sparse / (sparse + _count("hg.bfs.hop.stage1"))
