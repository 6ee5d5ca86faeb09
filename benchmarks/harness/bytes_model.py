"""The algorithm's bytes, from shapes only.

A kernel's roofline share divides the least time the chip could take for
these bytes by the device time the trace shows. Nothing here may read a
plan, a layout or a counter of the program: the same work must read the
same whatever implements it. Both kernels are bound by bytes (a traversal
does a few bit operations per word moved), so the bound is HBM bandwidth.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def relation_bytes(n_rows: int, e_inc: int, e_tgt: int) -> int:
    """Both relations read once: int32 entries plus an int32 offset per
    row and relation."""
    return 4 * (e_inc + e_tgt) + 2 * 4 * (n_rows + 1)


def traverse_bytes(n_rows: int, e_inc: int, e_tgt: int, seeds: int,
                   hops: int) -> int:
    """A ``seeds``-wide, ``hops``-deep traversal over ``n_rows`` atoms: per
    hop, read and write the visited bitmap (one bit per seed and row) once
    and read both relations once."""
    return hops * (2 * n_rows * seeds // 8
                   + relation_bytes(n_rows, e_inc, e_tgt))


def served_bfs_bytes(n_rows: int, e_inc: int, e_tgt: int,
                     requests_by_hops: dict) -> int:
    """The BFS questions a window answered, ``{hops: how many}``: each needs
    its own bit per row, read and written once per hop; the questions of
    one hop count could at best share one pass over the relations."""
    return sum(traverse_bytes(n_rows, e_inc, e_tgt, k, int(h))
               for h, k in requests_by_hops.items() if k)


def peaks(device_kind: str) -> dict:
    """The published peaks of a chip; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def roofline_share_pct(n_bytes: int, device_s: float,
                       device_kind: str) -> float:
    """Least time for ``n_bytes`` at the chip's HBM peak, as a share of the
    device time measured."""
    least_s = n_bytes / (peaks(device_kind)["hbm_GBps"] * 1e9)
    return 100.0 * least_s / device_s
