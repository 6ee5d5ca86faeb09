"""1 - (union of the device's operation intervals / traced window)."""


def read(ctx):
    share = ctx["trace"].get("idle_share")
    return None if share is None else 100.0 * share
