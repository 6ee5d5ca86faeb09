"""The plain reference of a pair-distance batch: numpy only, fed by the
generator's own arrays. Nothing here imports the program or reads anything
the program made: the entries are the generator's (``flat``, ``link_of``)
and the links' types its ``type_of`` (``builders/columnar_snapshot.tables``,
a pure function of the seed).

The semantics (``PERF.md`` section 4): ``N_0(s) = {s}``; a hop makes a link
of the family live if one of its targets is in the ball and adds every
target of a live link; ``dist = min {h : t in N_h(s)}``. ONE ball, grown
forward from the sources alone — no search from two ends, so nothing of
the program's way of finding a length is repeated here.
"""

from __future__ import annotations

import numpy as np

from harness import refs, refs_typed


def host_pair_dist(n_ids: int, flat: np.ndarray, link_of: np.ndarray,
                   type_of: np.ndarray, family: np.ndarray,
                   sources: np.ndarray, targets: np.ndarray,
                   depth: int) -> np.ndarray:
    """Bit-parallel forward search for up to 64 pairs: ``out[k]`` is the
    hop at which the ball of ``sources[k]`` first holds ``targets[k]``
    (0 for an end that is both), -1 where it does not within ``depth``
    hops. ``flat[e]`` is the target atom of entry e and ``link_of[e]``
    (non-decreasing) its link; a hop follows a link only if
    ``type_of[link]`` is in ``family``."""
    n = len(sources)
    if n > 64 or len(targets) != n:
        raise ValueError("host_pair_dist takes at most 64 pairs, an end each")
    keep = refs_typed.admitted_entries(type_of, link_of, family)
    flat, link_of = flat[keep], link_of[keep]
    if not len(flat):  # no link to follow: an end that is both, or nothing
        return np.where(np.asarray(sources) == np.asarray(targets), 0, -1)
    _, link_s, grp, grp_ids, lst, lst_ids = refs.bfs_prepare(flat, link_of)
    bit = np.uint64(1) << np.arange(n, dtype=np.uint64)
    vis = np.zeros(n_ids, dtype=np.uint64)
    np.bitwise_or.at(vis, sources, bit)
    dist = np.full(n, -1, dtype=np.int64)
    for h in range(depth + 1):
        first = (dist < 0) & ((vis[targets] & bit) != 0)
        dist[first] = h
        if h == depth or (dist >= 0).all():
            break
        live = np.zeros(n_ids, dtype=np.uint64)
        live[lst_ids] = np.bitwise_or.reduceat(vis[flat], lst)
        vis[grp_ids] |= np.bitwise_or.reduceat(live[link_s], grp)
    return dist


def capped(dist: np.ndarray, max_hops: int) -> np.ndarray:
    """What a search capped at ``max_hops`` answers: a longer length is -1,
    as no path is."""
    return np.where(dist > max_hops, -1, dist)


def tested_on_even_depths_only(dist: np.ndarray) -> np.ndarray:
    """The CONTROL's broken guarantee (never used by a run): an odd length
    rounded up to the next even one — what a two-sided search that tested
    only after BOTH sides had expanded would answer."""
    return np.where(dist > 0, dist + dist % 2, dist)
