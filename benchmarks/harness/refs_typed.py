"""The plain reference under a link predicate: numpy only, fed by the
generator's own arrays. Nothing here imports the program or reads anything
the program made: the links' types come from the generator's ``type_of``
(``builders/columnar_snapshot.tables``, a pure function of the seed).

A traversal that follows a link only if its type is in the family is the
untyped traversal over the entries of the admitted links, so this file only
filters the entry arrays and hands them to ``refs.host_bfs_bits``.
"""

from __future__ import annotations

import numpy as np

from harness import refs


def admitted_entries(type_of: np.ndarray, link_of: np.ndarray,
                     family: np.ndarray) -> np.ndarray:
    """For each incidence entry, whether its link's type atom is in
    ``family``."""
    return np.isin(type_of[link_of], family)


def host_bfs_bits(n_ids: int, flat: np.ndarray, link_of: np.ndarray,
                  type_of: np.ndarray, family: np.ndarray,
                  seeds: np.ndarray, hops: int) -> np.ndarray:
    """``refs.host_bfs_bits`` where a hop follows a link only if
    ``type_of[link]`` is in ``family``: bit k of ``out[v]`` says seed k
    reaches atom v within ``hops`` over admitted links (seed included)."""
    keep = admitted_entries(type_of, link_of, family)
    return refs.host_bfs_bits(n_ids, flat[keep], link_of[keep], n_ids,
                              seeds, hops)
