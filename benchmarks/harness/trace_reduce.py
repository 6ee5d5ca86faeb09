"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

Device planes are named ``/device:TPU:<n>``. Their ``XLA Ops`` line holds
one event per operation that ran on the chip, their ``XLA Modules`` line one
per executed program. Host planes hold one line per thread; the program's
``obs.device.annotate`` spans (``hg.serve.*``) and the drivers' own
``bench.*`` spans are ``TraceAnnotation`` events there, on the same clock.

busy = the union of the device's operation intervals; idle share =
1 - busy / window; a gap is charged to the annotated host span that
covers its middle.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIXES = ("hg.", "bench.")
#: gaps shorter than this are the device's own turn-around between two
#: operations, not something the host did: summed under one name
SHORT_GAP_NS = 20_000
SHORT_GAP = "between operations (<20us each)"


def newest_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


def union(intervals: list) -> list:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_key(name: str) -> str:
    """``jit_bfs_serve_batch(1234567)`` -> ``jit_bfs_serve_batch``: the
    trailing id changes with every compile."""
    return re.sub(r"\(\d+\)$", "", name)


def op_key(name: str) -> str:
    """``%fusion.3 = u32[...] fusion(...)`` -> ``fusion.3``: an operation
    goes by its HLO name, without its shapes."""
    return name.split(" = ", 1)[0].lstrip("%")


def _top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_planes(planes, window_s: float | None = None) -> dict:
    """The reduction proper, over ``ProfileData.planes`` (or test doubles
    with ``name``/``lines``/``events``/``start_ns``/``duration_ns``)."""
    per_device_busy, modules, ops, host_spans = [], {}, {}, []
    module_runs: dict = {}
    merged_all: list = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if line is None:
                continue
            iv = []
            for ev in line.events:
                iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                if line.name == OPS_LINE:
                    op = op_key(ev.name)
                    ops[op] = ops.get(op, 0.0) + ev.duration_ns / 1e9
            merged = union(iv)
            merged_all.append(merged)
            per_device_busy.append(sum(e - s for s, e in merged) / 1e9)
            for ev in getattr(lines.get(MODULES_LINE), "events", ()):
                key = module_key(ev.name)
                modules[key] = modules.get(key, 0.0) + ev.duration_ns / 1e9
                module_runs[key] = module_runs.get(key, 0) + 1
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_SPAN_PREFIXES):
                        host_spans.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             re.sub(r",slot=\d+", "", ev.name)))
    if not per_device_busy:
        return {"devices": 0, "host_spans": len(host_spans)}
    busy_s = sum(per_device_busy) / len(per_device_busy)
    gaps: dict = {}
    first = merged_all[0]
    for (_, e0), (s1, _) in zip(first, first[1:]):
        if s1 - e0 < SHORT_GAP_NS:
            gaps[SHORT_GAP] = gaps.get(SHORT_GAP, 0.0) + (s1 - e0) / 1e9
            continue
        mid = (e0 + s1) // 2
        # the innermost annotated span over the gap's middle
        cover = [sp for sp in host_spans if sp[0] <= mid < sp[1]]
        name = (min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover
                else "no annotated span")
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0) / 1e9
    span = (first[-1][1] - first[0][0]) / 1e9 if first else 0.0
    out = {
        "devices": len(per_device_busy),
        "busy_s": busy_s,
        "traced_span_s": span,
        "modules": modules,
        "module_runs": module_runs,
        "host_spans": len(host_spans),
        "breakdown": {"device_ops": _top(ops), "idle_gaps": _top(gaps)},
    }
    if window_s is not None:
        out["window_s"] = window_s
        out["idle_share"] = 1.0 - busy_s / window_s
    return out


def reduce_file(path: str, window_s: float | None = None) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_s)
