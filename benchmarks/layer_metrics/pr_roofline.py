"""The least time for a PageRank run's bytes — the traffic's iterations,
each reading both relations and the ranks once and writing the ranks once
(``harness/bytes_pagerank.py``, from shapes only) — at the chip's HBM peak,
over the device seconds a run spends under every ``hg.pr.*`` scope. Bound
by bytes: HBM bandwidth, though an iteration's gathers of 4-byte scalars
are bound by the indices they issue. None under a program without the
scopes, or a driver that reports no such bytes."""

from harness import bytes_model, scope_reduce

SCOPES = ("hg.pr.init", "hg.pr.stage1", "hg.pr.stage2", "hg.pr.update")


def read(ctx):
    n_bytes = ctx["window"].get("pr_bytes_per_run")
    device_s = scope_reduce.seconds_per_traversal(ctx, *SCOPES)
    if n_bytes is None or not device_s:
        return None
    return bytes_model.roofline_share_pct(n_bytes, device_s,
                                          ctx["device"]["kind"])
