"""The program's record of every ``obs.phase`` INSTANCE, cut into a run's
operations.

``hypergraphdb_tpu.obs.phase_log()`` is a bounded ring of ``(t, kind,
fields)``: kind ``phase`` — ``name``, ``id``, ``parent``, ``op`` (the id of
the outermost enclosing phase: one operation's records share it), ``t0``,
``t1``, ``cpu_s``, the thread's ``nivcsw`` / ``minflt`` / ``majflt``,
``step.<sub>`` seconds, ``jit.trace_s`` / ``.lower_s`` / ``.compile_s`` /
``.load_s``, ``stall`` — and kind ``jit``, one a JAX compile event. An
operation is a phase named ``hg.bfs.pull``, ``hg.bfs.match`` or
``hg.bfs.pairs`` (one call of the operator). The window's operations are
the LAST ``window["attempted"]`` of them (``collect()`` and ``check()`` call
none); the warm-up's are those before.

Everything here returns None under a program without the ring (a parent
commit), and where the ring no longer reaches back to what is asked for.
"""

from __future__ import annotations

import statistics

OP_NAMES = ("hg.bfs.pull", "hg.bfs.match", "hg.bfs.pairs")
JIT_STAGES = ("trace_s", "lower_s", "compile_s", "load_s")


def wall(rec: dict) -> float:
    return rec["t1"] - rec["t0"]


def step_s(rec: dict, *subs: str) -> float:
    return sum(rec.get(f"step.{sub}", 0.0) for sub in subs)


class Operations:
    """Some operations' records: ``ops`` (the operations' own, oldest
    first) and ``below`` (every phase record under one of them)."""

    def __init__(self, ops: list, phases: list):
        ids = {op["id"] for op in ops}
        self.ops = ops
        self.below = [r for r in phases
                      if r["op"] in ids and r["id"] not in ids]

    def children(self) -> list:
        """The records whose parent is an operation itself."""
        ids = {op["id"] for op in self.ops}
        return [r for r in self.below if r["parent"] in ids]

    def jit_s(self, stage: str) -> float:
        return sum(r.get(f"jit.{stage}", 0.0) for r in self.ops + self.below)


def _cut(ctx: dict):
    """(window's operations, warm-up's) of this run; None where the program
    keeps no ring, or the ring has lost the start of the window's first
    operation."""
    try:
        from hypergraphdb_tpu.obs import phase_log
    except ImportError:
        return None
    ring = phase_log()
    records = ring.records()
    phases = [fields for _, kind, fields in records if kind == "phase"]
    ops = [r for r in phases if r["name"] in OP_NAMES]
    n = ctx["window"]["attempted"]
    if not n or len(ops) < n:
        return None
    window, warm = ops[-n:], ops[:-n]
    whole = len(records) < ring.capacity  # nothing has ever fallen out

    def held(some: list) -> bool:
        return whole or (bool(some) and records[0][0] <= some[0]["t0"])

    if not held(window):
        return None
    return (Operations(window, phases),
            Operations(warm, phases) if held(warm) else None)


def window_of(ctx: dict) -> Operations | None:
    cut = _cut(ctx)
    return None if cut is None else cut[0]


def warm_of(ctx: dict) -> Operations | None:
    """The operations before the window's (``driver.warm()``'s); None
    where there is none on record."""
    cut = _cut(ctx)
    return None if cut is None or cut[1] is None or not cut[1].ops \
        else cut[1]


def per_operation(ctx: dict, seconds) -> float | None:
    """``seconds(window)`` ÷ the window's operations."""
    window = window_of(ctx)
    return None if window is None else seconds(window) / len(window.ops)


def warm_jit_s(ctx: dict, stage: str) -> float | None:
    """Seconds JAX spent in ``stage`` inside the warm-up's operations.
    ``compile_s`` comes LESS ``load_s``: JAX's compile event holds the load
    of an executable the persistent cache had."""
    warm = warm_of(ctx)
    if warm is None:
        return None
    secs = warm.jit_s(stage)
    return secs - warm.jit_s("load_s") if stage == "compile_s" else secs


def stall_s(ctx: dict) -> float | None:
    """Over the window's records flagged ``stall``: wall less the median
    wall of that name in the window. 0.0 in a window without one."""
    window = window_of(ctx)
    if window is None:
        return None
    total = 0.0
    for rec in window.ops + window.below:
        if rec.get("stall"):
            same = [wall(r) for r in window.ops + window.below
                    if r["name"] == rec["name"]]
            total += wall(rec) - statistics.median(same)
    return total
