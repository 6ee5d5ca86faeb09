"""A connected-components run's bytes, from shapes only (the rule of
``bytes_model.py``: nothing here reads a plan, a layout or a counter of the
program). The rounds come from the reference (``refs_wcc``), never from
the program.

A synchronous round over ``entries`` admitted target entries (each one
incidence entry) reads both relations once and the labels — 4 bytes an
atom — once, and writes the labels once.
"""

from __future__ import annotations

from harness import bytes_model


def wcc_bytes(n_rows: int, entries: int, rounds: int) -> int:
    """``rounds`` min-label rounds over ``n_rows`` atoms."""
    return rounds * (bytes_model.relation_bytes(n_rows, entries, entries)
                     + 2 * 4 * n_rows)
