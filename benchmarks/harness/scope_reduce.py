"""Device time by the program's own names, from a trace's raw bytes.

``trace_reduce`` goes by what the compiler calls an operation (``while.24``,
``closed_call.16``): those names change with every edit of the program, and
a loop nest around a call counts its time three times. The program names its
device work itself, with ``jax.named_scope("hg.<layer>.<step>")``: the scope
is a component of the ``op_name`` that JAX gives every operation traced
inside it, and the profiler keeps that path in the trace, as the stat
``tf_op`` of the operation's ``XEventMetadata`` (``jit(f)/hg.a.b/while/...``).
``jax.profiler.ProfileData`` does not show metadata stats, so this reads the
protobuf wire format itself (tsl ``xplane.proto``; field numbers below).

Per scope: the UNION of its events' intervals, so a nest counts once. A
container without a path (a ``while``, on the chip) adopts the one scope its
contents share; an event without a path (the compiler's own copies and
slices) takes the scope of the innermost event that contains it in time.

    python3 benchmarks/harness/scope_reduce.py <file.xplane.pb>

prints the table and the largest operations that no scope claims: look at
one trace by hand before trusting the numbers.
"""

from __future__ import annotations

import functools
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness.trace_reduce import (  # noqa: E402
    DEVICE_PLANE,
    OPS_LINE,
    newest_xplane,
    op_key,
    union,
)

SCOPE_PREFIX = "hg."
PATH_STAT = "tf_op"

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped (none of the fields read here is one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _message(buf) -> dict:
    """The last value of each field (enough for the scalar messages)."""
    return dict(_fields(buf))


def scope_of(path: str | None) -> str | None:
    """``jit(f)/hg.bfs.stage1.lvl0/while/body/or:`` -> ``hg.bfs.stage1.lvl0``:
    the first component that is one of the program's scopes."""
    for part in (path or "").split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part.split(":", 1)[0]
    return None


def device_ops(data) -> list | None:
    """``[(start_ps, end_ps, op name, op path or None)]`` of the first TPU
    plane's ``XLA Ops`` line; None when the file holds no such plane."""
    for field, plane in _fields(memoryview(data)):
        if field != _SPACE_PLANES:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for f, v in _fields(plane):
            if f == _PLANE_NAME:
                name = _text(v)
            elif f == _PLANE_LINES:
                lines.append(v)
            elif f == _PLANE_EVENT_META:
                entry = _message(v)
                event_meta[entry[_MAP_KEY]] = entry[_MAP_VALUE]
            elif f == _PLANE_STAT_META:
                entry = _message(v)
                stat_names[entry[_MAP_KEY]] = _text(
                    _message(entry[_MAP_VALUE]).get(_META_NAME, b""))
        if not name.startswith(DEVICE_PLANE):
            continue
        line = next((ln for ln in lines
                     if _text(_message(ln).get(_LINE_NAME, b"")) == OPS_LINE),
                    None)
        if line is None:
            continue
        named = {}                      # metadata id -> (op name, op path)
        for mid, meta in event_meta.items():
            op, path = "", None
            for f, v in _fields(meta):
                if f == _META_NAME:
                    op = op_key(_text(v))
                elif f == _META_STATS:
                    stat = _message(v)
                    if stat_names.get(stat.get(_STAT_META_ID)) != PATH_STAT:
                        continue
                    if _STAT_STR in stat:
                        path = _text(stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        path = stat_names.get(stat[_STAT_REF])
            named[mid] = (op, path)
        events = []
        for f, v in _fields(line):
            if f != _LINE_EVENTS:
                continue
            e = _message(v)
            start = e.get(_EVENT_OFFSET_PS, 0)
            op, path = named.get(e.get(_EVENT_META_ID), ("", None))
            events.append((start, start + e.get(_EVENT_DURATION_PS, 0),
                           op, path))
        return events
    return None


def _union_s(intervals: list) -> float:
    """Seconds that picosecond intervals cover together."""
    return sum(e - s for s, e in union(intervals)) / 1e12


def reduce_ops(events: list) -> dict:
    """``scopes``: seconds per scope (union); ``scoped_s``: the union over
    every event that has a scope, its own or taken; ``busy_s``: the union
    over all events; ``unscoped``: the ten largest operations outside every
    scope, by summed duration.

    An event's scope is the first ``hg.`` component of its own path. On the
    chip a ``while`` carries no path though its body's operations do, so a
    container without a scope ADOPTS the one scope that all its scoped
    contents share; then an event that still has none INHERITS its
    container's."""
    # a container before what it contains: earlier start, then later end
    order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    scope = [scope_of(path) for _, _, _, path in order]
    parent, stack = [], []              # stack: indices of the open events
    for i, (start, end, _, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= start:
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    inside: dict = {}                   # container -> scopes of its contents
    for i in reversed(range(len(order))):   # contents before containers
        if scope[i] is None and len(inside.get(i, ())) == 1:
            (scope[i],) = inside[i]
        if scope[i] is not None and parent[i] is not None:
            inside.setdefault(parent[i], set()).add(scope[i])
    by_scope: dict = {}
    loose: dict = {}
    for i, (start, end, op, _) in enumerate(order):
        if scope[i] is None and parent[i] is not None:
            scope[i] = scope[parent[i]]
        if scope[i] is None:
            loose[op] = loose.get(op, 0) + (end - start)
        else:
            by_scope.setdefault(scope[i], []).append((start, end))
    return {
        "scopes": {k: _union_s(v) for k, v in by_scope.items()},
        "scoped_s": _union_s([iv for v in by_scope.values() for iv in v]),
        "busy_s": _union_s([(s, e) for s, e, _, _ in events]),
        "unscoped": [[k, v / 1e12] for k, v in sorted(
            loose.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce_bytes(data) -> dict | None:
    events = device_ops(data)
    return None if events is None else reduce_ops(events)


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int) -> dict | None:
    with open(path, "rb") as f:
        return reduce_bytes(f.read())


def of_run(ctx: dict) -> dict | None:
    """The reduction of the run's own trace (the newest ``.xplane.pb`` under
    ``<checkout>/.bench_trace``), parsed once per process however many
    readers ask; None without a trace file or a TPU plane in it (the CPU
    rehearsal)."""
    try:
        path = newest_xplane(os.path.join(ctx["setup"]["checkout"],
                                          ".bench_trace"))
    except FileNotFoundError:
        return None
    return _reduce_file(path, os.stat(path).st_mtime_ns)


def seconds_per_traversal(ctx: dict, *scopes: str) -> float | None:
    """The device seconds of ``scopes`` (disjoint by construction: a scope
    is a leaf) in the traced window, per traversal; None where the trace
    holds none of them, as under a program that does not name its work."""
    got = of_run(ctx)
    n = ctx["window"].get("traversals")
    if got is None or not n:
        return None
    found = [got["scopes"][s] for s in scopes if s in got["scopes"]]
    return sum(found) / n if found else None


def main(path: str) -> int:
    with open(path, "rb") as f:
        got = reduce_bytes(f.read())
    if got is None:
        print(f"no {DEVICE_PLANE}* plane with an {OPS_LINE!r} line in {path}")
        return 1
    for scope, s in sorted(got["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"{s:12.6f} s  {scope}")
    print(f"{got['scoped_s']:12.6f} s  all scopes (union)")
    print(f"{got['busy_s']:12.6f} s  busy (union of all operations)")
    for op, s in got["unscoped"]:
        print(f"{s:12.6f} s  unscoped: {op}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
