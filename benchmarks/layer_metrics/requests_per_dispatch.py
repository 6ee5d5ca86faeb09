"""Requests the runtime completed per device dispatch (runtime counters)."""


def read(ctx):
    c = ctx["window"].get("counters") or {}
    if not c.get("device_dispatches"):
        return None
    return c["completed"] / c["device_dispatches"]
