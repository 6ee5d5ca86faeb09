"""The plain reference of a path match: numpy only, fed by the generator's
own arrays. Nothing here imports the program or reads anything the program
made: the entries are the generator's (``flat``, ``link_of``) and the links'
types its ``type_of`` (``builders/columnar_snapshot.tables``, a pure
function of the seed).

The semantics (``PERF.md`` section 4): ``X_0[k] = {seed k}``; step h makes a
link live for seed k if its type atom is in ``steps[h]`` and one of its
targets is in ``X_{h-1}[k]``, and ``X_h[k]`` is every target of a live link.
No visited set: the state after a step is what the step reached, and
nothing else.
"""

from __future__ import annotations

import numpy as np


def host_match_bits(n_ids: int, flat: np.ndarray, link_of: np.ndarray,
                    type_of: np.ndarray, steps: list,
                    seeds: np.ndarray) -> np.ndarray:
    """Bit-parallel path match for up to 64 seeds: bit k of ``out[v]`` says
    atom v is an end point of the path ``steps[0] / steps[1] / …`` from
    seed k. ``flat[e]`` is the target atom of entry e and ``link_of[e]``
    (non-decreasing) its link; ``steps[h]`` is an array of link type atoms.
    A step is an OR by link over the admitted entries, then an OR by atom
    into an EMPTY state."""
    if len(seeds) > 64:
        raise ValueError("host_match_bits takes at most 64 seeds")
    state = np.zeros(n_ids, dtype=np.uint64)
    np.bitwise_or.at(state, seeds,
                     np.uint64(1) << np.arange(len(seeds), dtype=np.uint64))
    entry_type = type_of[link_of]
    for family in steps:
        keep = np.isin(entry_type, family)
        atoms, links = flat[keep], link_of[keep]
        nxt = np.zeros(n_ids, dtype=np.uint64)
        if len(atoms):
            first = np.flatnonzero(np.r_[True, links[1:] != links[:-1]])
            live = np.bitwise_or.reduceat(state[atoms], first)
            pulled = np.repeat(live, np.diff(np.r_[first, len(links)]))
            order = np.argsort(atoms, kind="stable")
            atoms_s = atoms[order]
            grp = np.flatnonzero(np.r_[True, atoms_s[1:] != atoms_s[:-1]])
            nxt[atoms_s[grp]] = np.bitwise_or.reduceat(pulled[order], grp)
        state = nxt
    return state
