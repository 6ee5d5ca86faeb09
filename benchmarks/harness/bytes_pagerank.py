"""A PageRank run's bytes, from shapes only (the rule of ``bytes_model.py``:
nothing here reads a plan, a layout or a counter of the program). The
iterations are the traffic's, never the program's count.

An iteration over ``entries`` target entries (each one incidence entry)
reads both relations once and the ranks — 4 bytes an atom — once, and
writes the ranks once.
"""

from __future__ import annotations

from harness import bytes_model


def pr_bytes(n_rows: int, entries: int, iterations: int) -> int:
    """``iterations`` PageRank iterations over ``n_rows`` atoms."""
    return iterations * (bytes_model.relation_bytes(n_rows, entries, entries)
                         + 2 * 4 * n_rows)
