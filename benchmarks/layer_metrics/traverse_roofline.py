"""The traversals' device time against the least time for their bytes
(``harness/bytes_model.py``). Everything that runs on the device in a
traversal cell is the traversal, so the time is the device's busy time.
Bound by bytes: HBM bandwidth."""

from harness import bytes_model


def read(ctx):
    busy_s, w = ctx["trace"].get("busy_s"), ctx["window"]
    if not busy_s or not w.get("traversals"):
        return None
    return bytes_model.roofline_share_pct(
        w["bytes_per_traversal"] * w["traversals"], busy_s,
        ctx["device"]["kind"])
