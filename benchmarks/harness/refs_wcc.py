"""The plain reference of a connected-components run: numpy only, fed by
the generator's own arrays. Nothing here imports the program or reads
anything the program made: the entries are the generator's (``flat``,
``link_of``) and the links' types its ``type_of``
(``builders/columnar_snapshot.tables``, a pure function of the seed).

The semantics (``PERF.md`` section 4): two atoms are adjacent iff a link of
the family holds both among its targets; ``label[a]`` is the least atom id
reachable from ``a``, ``a`` included. Synchronous min-label propagation
reaches it: a round takes each admitted link's least label over its
targets, then each atom's least over its admitted links and its own; after
r rounds an atom holds the least id within r hops, and the first round
that lowers nothing ends it.
"""

from __future__ import annotations

import numpy as np

from harness import refs, refs_typed


def min_label_rounds(n_ids: int, flat: np.ndarray, link_of: np.ndarray,
                     type_of: np.ndarray, family: np.ndarray,
                     max_rounds: int | None = None) -> tuple:
    """``(labels, rounds, lowered)``: the ``(n_ids,)`` int32 labels, the
    rounds run — the last of them the quiet one that lowered nothing — and
    the rows each round lowered. No admitted entry: no round, every atom
    its own label. ``max_rounds`` stops after that many rounds (the
    CONTROL, never a run's reference)."""
    keep = refs_typed.admitted_entries(type_of, link_of, family)
    flat, link_of = flat[keep], link_of[keep]
    labels = np.arange(n_ids, dtype=np.int32)
    lowered: list = []
    if not len(flat):
        return labels, 0, lowered
    _, link_s, grp, grp_ids, lst, lst_ids = refs.bfs_prepare(flat, link_of)
    link_min = np.empty(n_ids, dtype=np.int32)
    while max_rounds is None or len(lowered) < max_rounds:
        link_min[lst_ids] = np.minimum.reduceat(labels[flat], lst)
        pulled = np.minimum.reduceat(link_min[link_s], grp)
        fell = pulled < labels[grp_ids]
        labels[grp_ids[fell]] = pulled[fell]
        lowered.append(int(np.count_nonzero(fell)))
        if not lowered[-1]:
            break
    return labels, len(lowered), lowered


def components(labels: np.ndarray) -> int:
    """The atoms that are their own label: one a component."""
    return int(np.count_nonzero(labels == np.arange(len(labels))))
