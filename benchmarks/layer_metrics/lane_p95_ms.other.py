"""95th percentile of the pattern, range, join and planned requests' time
to an answer, caller's clock."""


def read(ctx):
    return (ctx["window"].get("lane_p95_ms") or {}).get("other")
