"""A pair-distance batch's bytes, from shapes only (the rule of
``bytes_model.py``: nothing here reads a plan, a layout or a counter of the
program).

A batch under a cap of ``max_hops`` is at most ``max_hops`` single hops —
whichever end's ball an implementation grows — each over the entries the
family admits, and a meet test after each: both balls read once, one bit
per pair and row.
"""

from __future__ import annotations

from harness import bytes_model


def meet_bytes(n_rows: int, seeds: int, tests: int) -> int:
    """``tests`` meet tests of a ``seeds``-wide batch over ``n_rows`` atoms:
    each reads two bitmaps of one bit per pair and row."""
    return tests * 2 * (n_rows * seeds // 8)


def pair_bytes(n_rows: int, entries: int, seeds: int, max_hops: int) -> int:
    """A ``seeds``-wide batch capped at ``max_hops`` over ``n_rows`` atoms
    whose family admits ``entries`` target entries (each one incidence
    entry): ``max_hops`` hops of ``bytes_model.traverse_bytes`` over those
    entries and as many meet tests."""
    return (bytes_model.traverse_bytes(n_rows, entries, entries, seeds,
                                       hops=max_hops)
            + meet_bytes(n_rows, seeds, max_hops))
