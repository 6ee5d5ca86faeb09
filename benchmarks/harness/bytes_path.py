"""A path match's bytes, from shapes only (the rule of ``bytes_model.py``:
nothing here reads a plan, a layout or a counter of the program).

A match of H steps is H single hops, each over the entries its own family
admits; what ends a step writes the step's frontier, one bit per seed and
row, whatever the step reached.
"""

from __future__ import annotations

from harness import bytes_model


def match_bytes(n_rows: int, step_entries: list, seeds: int) -> int:
    """A ``seeds``-wide match over ``n_rows`` atoms whose step h admits
    ``step_entries[h]`` target entries (each one incidence entry): per
    step, one hop of ``bytes_model.traverse_bytes`` over those entries —
    the least an implementation with an index by type must read."""
    return sum(bytes_model.traverse_bytes(n_rows, e, e, seeds, hops=1)
               for e in step_entries)


def frontier_write_bytes(n_rows: int, seeds: int, steps: int) -> int:
    """The frontiers a match writes: one bit per seed and row, once a step."""
    return steps * (n_rows * seeds // 8)
