"""The least time for a connected-components run's bytes — the reference's
rounds, each reading both relations and the labels once and writing the
labels once (``harness/bytes_wcc.py``, from shapes only) — at the chip's
HBM peak, over the device seconds a run spends under every ``hg.wcc.*``
scope. Bound by bytes: HBM bandwidth, though a round's gathers of 4-byte
scalars are bound by the indices they issue. None under a program without
the scopes, or a driver that reports no such bytes."""

from harness import bytes_model, scope_reduce

SCOPES = ("hg.wcc.init", "hg.wcc.stage1", "hg.wcc.stage2", "hg.wcc.fold",
          "hg.wcc.count")


def read(ctx):
    n_bytes = ctx["window"].get("wcc_bytes_per_run")
    device_s = scope_reduce.seconds_per_traversal(ctx, *SCOPES)
    if n_bytes is None or not device_s:
        return None
    return bytes_model.roofline_share_pct(n_bytes, device_s,
                                          ctx["device"]["kind"])
