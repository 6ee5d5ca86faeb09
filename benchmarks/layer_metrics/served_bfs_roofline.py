"""The dense served BFS programs' device time against the least time for
the bytes the window's BFS questions need (``harness/bytes_model.py``).
Bound by bytes: HBM bandwidth."""

from harness import bytes_model

MODULE = "bfs_serve_batch"


def read(ctx):
    modules = ctx["trace"].get("modules") or {}
    device_s = sum(s for name, s in modules.items() if MODULE in name)
    if not device_s or not ctx["window"].get("bfs_bytes"):
        return None
    return bytes_model.roofline_share_pct(
        ctx["window"]["bfs_bytes"], device_s, ctx["device"]["kind"])
