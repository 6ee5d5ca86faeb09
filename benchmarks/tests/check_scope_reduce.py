"""``harness/scope_reduce.py`` against known answers. Needs no chip and nothing
of the program.

    python3 benchmarks/tests/check_scope_reduce.py

- on hand-made trace bytes (``xspace`` below writes the few fields of
  ``xplane.proto`` that the reducer reads) whose answers are worked out by
  hand: a nest counts once, a loop without a path adopts, an operation
  without a path inherits, a path's first scope wins, what no scope claims
  lowers the coverage;
- on one small trace recorded on a TPU v5e (``data/scoped_trace.xplane.pb``,
  made by ``record_scoped_trace.py``), whose answers were read off it by hand
  with ``scope_reduce.py <file>`` and ``trace_summary.py``;
- the readers' None where there is nothing to read.
"""

from __future__ import annotations

import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import scope_reduce  # noqa: E402

SCOPED_TRACE = os.path.join(HERE, "data", "scoped_trace.xplane.pb")

#: read off the recorded trace by hand (see the module docstring), in
#: picoseconds, three runs of each program. ``hg.test.a``: the three matmul
#: fusions (1818750 + 1818750 + 1821250). ``hg.test.b``: the three
#: ``while.1`` (17490000 + 17543828 + 17607500), which carry no path and
#: adopt their bodies' scope — the body's ``reduce_window_sum:`` operations
#: have a path without a scope and inherit — plus the three ``copy.4`` after
#: the loop (363750 + 364844 + 363828). Outside every scope, all at top
#: level: the transposed copy (``multiply_bitcast_fusion``, path
#: ``jit(hg_test_b)/mul:``), ``copy-start``/``copy-done`` and a
#: ``custom-call`` without a path. Nothing overlaps, so busy is the sum.
SCOPED_TRACE_KNOWN = {
    "events": 237,
    "scopes_ps": {"hg.test.a": 5458750, "hg.test.b": 52641328 + 1092422},
    "scoped_ps": 59192500,
    "unscoped_ps": {"copy-done": 5336094, "multiply_bitcast_fusion": 3131172,
                    "copy-start": 41250, "custom-call": 6172},
    "busy_ps": 67707188,
}


# ------------------------------------------------------- hand-made bytes


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def xspace(ops: list, plane: str = "/device:TPU:0",
           path_by_ref: bool = False) -> bytes:
    """One plane with an ``XLA Ops`` line of ``ops``: ``(name, path or None,
    start_ps, duration_ps)``. ``path_by_ref`` stores a path the other way
    the format allows: as the name of a stat metadata entry, by id."""
    stat_meta = {1: "tf_op"}
    event_meta: dict = {}
    events = b""
    for name, path, start, dur in ops:
        mid = event_meta.setdefault((name, path), len(event_meta) + 1)
        events += _field(4, _field(1, mid) + _field(2, start)
                         + _field(3, dur))
    body = _field(2, plane)
    body += _field(3, _field(2, "XLA Modules"))
    body += _field(3, _field(2, "XLA Ops") + events)
    for (name, path), mid in event_meta.items():
        meta = _field(1, mid) + _field(2, f"%{name} = u32[8]{{0}} op()")
        if path is not None and path_by_ref:
            ref = len(stat_meta) + 1
            stat_meta[ref] = path
            meta += _field(5, _field(1, 1) + _field(7, ref))
        elif path is not None:
            meta += _field(5, _field(1, 1) + _field(5, path))
        body += _field(4, _field(1, mid) + _field(2, meta))
    for sid, name in stat_meta.items():
        body += _field(5, _field(1, sid)
                       + _field(2, _field(1, sid) + _field(2, name)))
    return _field(1, _field(2, "/host:CPU")) + _field(1, body)


US = 1_000_000  # picoseconds

#: (name, path, start, duration), microseconds
BY_HAND = [
    # a loop nest around a call: three events, one stretch of time
    ("while.1", "jit(f)/hg.x.a/while", 0, 100),
    ("closed_call.2", "jit(f)/hg.x.a/while/body/closed_call", 0, 40),
    ("fusion.3", "jit(f)/hg.x.a/while/body/closed_call/or:", 5, 30),
    # the compiler's own copy inside the loop: no path, inherits
    ("copy.4", None, 50, 40),
    # the same at top level: nothing to inherit from
    ("copy.5", None, 100, 30),
    # a loop without a path, as the chip records them: adopts the one scope
    # of its contents
    ("while.6", None, 150, 100),
    # a path without a scope (a nested helper's) inside it: inherits what
    # the loop adopted
    ("fusion.7", "reduce_window_sum:", 160, 40),
    # a scope inside a scope: the first one wins
    ("fusion.8", "jit(g)/hg.x.b/while/body/hg.x.c/mul:", 210, 30),
    # a path without a scope at top level
    ("fusion.9", "jit(h)/mul:", 300, 20),
    # a loop without a path whose contents disagree: adopts nothing
    ("while.10", None, 400, 100),
    ("fusion.11", "jit(k)/hg.x.a/or:", 410, 20),
    ("fusion.12", "jit(k)/hg.x.b/or:", 440, 40),
]


def check_by_hand() -> None:
    ops = [(n, p, s * US, d * US) for n, p, s, d in BY_HAND]
    for by_ref in (False, True):
        got = scope_reduce.reduce_bytes(xspace(ops, path_by_ref=by_ref))
        assert {k: round(v * 1e6) for k, v in got["scopes"].items()} == \
            {"hg.x.a": 120, "hg.x.b": 140}, got["scopes"]
        assert round(got["scoped_s"] * 1e6) == 260, got
        # [0,130] + [150,250] + [300,320] + [400,500]
        assert round(got["busy_s"] * 1e6) == 350, got
        assert [(k, round(v * 1e6)) for k, v in got["unscoped"]] == \
            [("while.10", 100), ("copy.5", 30), ("fusion.9", 20)], \
            got["unscoped"]
    # a file with no TPU plane (the CPU rehearsal) is nothing, not zero
    assert scope_reduce.reduce_bytes(
        xspace(ops, plane="/device:CPU:0")) is None
    assert scope_reduce.scope_of("jit(f)/hg.a.b/while/body/or:") == "hg.a.b"
    assert scope_reduce.scope_of("jit(hg_bfs_stage1)/while") is None
    assert scope_reduce.scope_of(None) is None


def check_readers() -> None:
    ops = [(n, p, s * US, d * US) for n, p, s, d in BY_HAND]
    with tempfile.TemporaryDirectory() as checkout:
        ctx = {"setup": {"checkout": checkout}, "window": {"traversals": 2}}
        # no trace yet
        assert scope_reduce.of_run(ctx) is None
        assert scope_reduce.seconds_per_traversal(ctx, "hg.x.a") is None
        run_dir = os.path.join(checkout, ".bench_trace", "plugins", "profile",
                               "2026_01_01")
        os.makedirs(run_dir)
        with open(os.path.join(run_dir, "host.xplane.pb"), "wb") as f:
            f.write(xspace(ops))
        per = scope_reduce.seconds_per_traversal
        assert round(per(ctx, "hg.x.a") * 1e6) == 60
        assert round(per(ctx, "hg.x.a", "hg.x.b") * 1e6) == 130
        # a program that names none of the scopes asked for: nothing
        assert per(ctx, "hg.x.c") is None
        assert round(per(ctx, "hg.x.c", "hg.x.b") * 1e6) == 70
        assert scope_reduce.of_run(ctx) is scope_reduce.of_run(ctx)


def check_on_recorded_trace() -> None:
    known = SCOPED_TRACE_KNOWN
    with open(SCOPED_TRACE, "rb") as f:
        data = f.read()
    assert len(scope_reduce.device_ops(data)) == known["events"]
    got = scope_reduce.reduce_bytes(data)
    assert {k: round(v * 1e12) for k, v in got["scopes"].items()} == \
        known["scopes_ps"], got["scopes"]
    assert round(got["scoped_s"] * 1e12) == known["scoped_ps"], got
    assert round(got["busy_s"] * 1e12) == known["busy_ps"], got
    assert {k: round(v * 1e12) for k, v in got["unscoped"]} == \
        known["unscoped_ps"], got["unscoped"]
    # the scopes are leaves and nothing overlaps: the parts add up
    assert sum(known["scopes_ps"].values()) == known["scoped_ps"]
    assert known["scoped_ps"] + sum(known["unscoped_ps"].values()) == \
        known["busy_ps"]


CHECKS = (check_by_hand, check_readers, check_on_recorded_trace)


def main() -> int:
    for check in CHECKS:
        check()
        print(f"check_scope_reduce: {check.__name__} ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
