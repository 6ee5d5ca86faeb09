"""The union of every operation under an ``hg.*`` scope over the device's
busy time: the guard that the ``traverse_dev_s.*`` rows account for what the
device did. A program without a named scope lowers it."""

from harness import scope_reduce


def read(ctx):
    got, busy_s = scope_reduce.of_run(ctx), ctx["trace"].get("busy_s")
    if got is None or not got["scopes"] or not busy_s:
        return None
    return 100.0 * got["scoped_s"] / busy_s
