"""Real lanes over padded lanes of the window's batches (runtime counters)."""


def read(ctx):
    c = ctx["window"].get("counters") or {}
    if not c.get("lanes_padded"):
        return None
    return 100.0 * c["lanes_real"] / c["lanes_padded"]
