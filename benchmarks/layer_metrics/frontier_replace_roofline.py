"""The least time to write a match's frontiers — one bit per seed and row,
once a step (``harness/bytes_path.frontier_write_bytes``, from shapes only)
— at the chip's HBM peak, over the device seconds a match spends under the
two scopes that write a step's frontier: ``hg.bfs.frontier_replace`` (a
dense step's end) and ``hg.bfs.sparse_hop`` (the sparse first step's
placement). Bound by bytes: HBM bandwidth. None under a program without
``hg.bfs.frontier_replace``, or a driver that reports no such bytes."""

from harness import bytes_model, scope_reduce


def read(ctx):
    n_bytes = ctx["window"].get("frontier_write_bytes")
    if (n_bytes is None or scope_reduce.seconds_per_traversal(
            ctx, "hg.bfs.frontier_replace") is None):
        return None
    device_s = scope_reduce.seconds_per_traversal(
        ctx, "hg.bfs.frontier_replace", "hg.bfs.sparse_hop")
    return bytes_model.roofline_share_pct(n_bytes, device_s,
                                          ctx["device"]["kind"])
