"""95th percentile of the BFS requests' time to an answer, caller's clock."""


def read(ctx):
    return (ctx["window"].get("lane_p95_ms") or {}).get("bfs")
