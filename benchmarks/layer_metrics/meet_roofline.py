"""The least time to read a batch's meet tests' bitmaps — two of one bit per
pair and row, once a test (``harness/bytes_pairs.meet_bytes``, from shapes
only) — at the chip's HBM peak, over the device seconds a batch spends
under scope ``hg.bfs.meet``. Bound by bytes: HBM bandwidth. None under a
program without the scope, or a driver that reports no such bytes."""

from harness import bytes_model, scope_reduce


def read(ctx):
    n_bytes = ctx["window"].get("meet_bytes")
    device_s = scope_reduce.seconds_per_traversal(ctx, "hg.bfs.meet")
    if n_bytes is None or not device_s:
        return None
    return bytes_model.roofline_share_pct(n_bytes, device_s,
                                          ctx["device"]["kind"])
