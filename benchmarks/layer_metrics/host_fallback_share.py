"""Requests answered by the exact host path, of those completed (counters)."""


def read(ctx):
    c = ctx["window"].get("counters") or {}
    if not c.get("completed"):
        return None
    return 100.0 * c["host_fallbacks"] / c["completed"]
