"""Plain host references: numpy only, fed by the generators' own arrays.

Copies of ``chip_smoke.py``'s ``host_bfs_bits`` / ``ServeGraph.reference``
(PR 22), kept here so that no later PR can change the yardstick. Nothing
in this file imports the program or reads anything the program made.
``ServeReference`` answers through an endpoint index built once (the
smoke scanned all links per question: ~220 s at 3M atoms).
"""

from __future__ import annotations

import numpy as np


def bfs_prepare(flat: np.ndarray, link_of: np.ndarray) -> tuple:
    """The seed-independent half of :func:`host_bfs_bits` (two sorts over
    the incidence entries), so several passes over one graph share it."""
    order = np.argsort(flat, kind="stable")
    flat_s, link_s = flat[order], link_of[order]
    grp = np.flatnonzero(np.r_[True, flat_s[1:] != flat_s[:-1]])
    lst = np.flatnonzero(np.r_[True, link_of[1:] != link_of[:-1]])
    return flat_s, link_s, grp, flat_s[grp], lst, link_of[lst]


def host_bfs_bits(n_ids: int, flat: np.ndarray, link_of: np.ndarray,
                  n_links: int, seeds: np.ndarray, hops: int,
                  prepared: tuple | None = None,
                  row_cap: int | None = None) -> np.ndarray:
    """Bit-parallel BFS for up to 64 seeds: bit k of ``out[v]`` says seed
    k reaches atom v within ``hops`` (seed included). ``flat[e]`` is the
    target atom of incidence entry e and ``link_of[e]`` (non-decreasing)
    its link: a hop is 'a link is live when any of its targets is
    visited; every target of a live link is reached'.

    ``row_cap`` is the CONTROL's broken guarantee (never set by a run):
    an atom looks at no more than its first ``row_cap`` incident links —
    the approximate answer a kernel that cut hub rows would give."""
    if len(seeds) > 64:
        raise ValueError("host_bfs_bits takes at most 64 seeds")
    _, link_s, grp, grp_ids, lst, lst_ids = (
        prepared if prepared is not None else bfs_prepare(flat, link_of))
    keep = None
    if row_cap is not None:
        rank = np.arange(len(link_s)) - np.repeat(
            grp, np.diff(np.r_[grp, len(link_s)]))
        keep = rank < row_cap
    vis = np.zeros(n_ids, dtype=np.uint64)
    np.bitwise_or.at(vis, seeds,
                     np.uint64(1) << np.arange(len(seeds), dtype=np.uint64))
    for _ in range(hops):
        live = np.zeros(n_links, dtype=np.uint64)
        live[lst_ids] = np.bitwise_or.reduceat(vis[flat], lst)
        pulled = live[link_s]
        if keep is not None:
            pulled = np.where(keep, pulled, np.uint64(0))
        vis[grp_ids] |= np.bitwise_or.reduceat(pulled, grp)
    return vis


def bits_columns(vis: np.ndarray, n: int) -> list:
    """For each of the first ``n`` bits, the sorted atom ids that have it
    set; looks only at the atoms that any seed reached."""
    reached = np.flatnonzero(vis)
    words = vis[reached]
    return [reached[(words >> np.uint64(k)) & np.uint64(1) != 0]
            for k in range(n)]


class ServeReference:
    """Host answers for the served lanes over binary valued links.

    ``link_h/link_a/link_b/link_val`` are the links' handles, endpoints and
    values as generated; ``stale_links`` is the CONTROL's broken guarantee
    (never set by a run): the newest that many acknowledged links are
    invisible, as to a reader of a stale snapshot."""

    def __init__(self, link_h, link_a, link_b, link_val, link_type: int,
                 stale_links: int = 0):
        n = len(link_h) - int(stale_links)
        self.link_h = np.asarray(link_h[:n], dtype=np.int64)
        self.link_a = np.asarray(link_a[:n], dtype=np.int64)
        self.link_b = np.asarray(link_b[:n], dtype=np.int64)
        self.link_val = np.asarray(link_val[:n], dtype=np.int64)
        self.link_type = int(link_type)
        self.n_ids = int(max(self.link_h.max(), self.link_a.max(),
                             self.link_b.max())) + 1
        ends = np.concatenate([self.link_a, self.link_b])
        self._order = np.argsort(ends, kind="stable") % n
        self._off = np.r_[0, np.cumsum(np.bincount(ends,
                                                   minlength=self.n_ids))]
        self._val_order = np.argsort(self.link_val, kind="stable")
        self._val_sorted = self.link_val[self._val_order]
        self._bfs_prepared = None

    def on(self, atom: int) -> np.ndarray:
        """Sorted indices of the links with ``atom`` at either end."""
        if not 0 <= atom < self.n_ids:
            return np.zeros(0, dtype=np.int64)
        return np.unique(self._order[self._off[atom]: self._off[atom + 1]])

    def neighbours(self, v: int) -> np.ndarray:
        on = self.on(v)
        both = np.concatenate([self.link_a[on], self.link_b[on]])
        return np.unique(both[both != v])

    def bfs_many(self, seeds: list, hops: int) -> list:
        """Visited sets (sorted ids) of ``seeds``, 64 per host pass."""
        n = len(self.link_h)
        flat = np.stack([self.link_a, self.link_b], axis=1).reshape(-1)
        link_of = np.repeat(np.arange(n, dtype=np.int64), 2)
        if self._bfs_prepared is None:
            self._bfs_prepared = bfs_prepare(flat, link_of)
        out = []
        for s0 in range(0, len(seeds), 64):
            part = np.asarray(seeds[s0: s0 + 64], dtype=np.int64)
            vis = host_bfs_bits(self.n_ids, flat, link_of, n, part, hops,
                                prepared=self._bfs_prepared)
            out.extend(bits_columns(vis, len(part)))
        return out

    def answer(self, q: dict):
        """One request's answer (not BFS: see :meth:`bfs_many`): a sorted
        id array (value order for a range), or — for the join — sorted
        (y, z) tuples."""
        k = q["kind"]
        if k == "pattern":
            if q["type"] is not None and q["type"] != self.link_type:
                return np.zeros(0, dtype=np.int64)
            hit = np.intersect1d(self.on(q["atoms"][0]),
                                 self.on(q["atoms"][1]))
            return np.sort(self.link_h[hit])
        if k == "range":
            lo = np.searchsorted(self._val_sorted, q["lo"], side="left")
            hi = np.searchsorted(self._val_sorted, q["hi"], side="right")
            sel = self._val_order[lo:hi]
            return self.link_h[sel[::-1] if q["desc"] else sel]
        if k == "planned":
            hit = self.on(q["atoms"][1])
            if q["window"] is None:
                hit = np.intersect1d(hit, self.on(q["atoms"][0]))
            else:
                lo, hi = q["window"]
                v = self.link_val[hit]
                hit = hit[(v >= lo) & (v <= hi)]
            return np.sort(self.link_h[hit])
        if k == "join":
            # a - y - z over co-incidence, y != a, z != y, z != a
            a = q["atoms"][0]
            return sorted((int(y), int(z)) for y in self.neighbours(a)
                          for z in self.neighbours(int(y)) if z != a)
        raise ValueError(f"no reference for kind {k!r}")


def answer_matches(q: dict, want, got_count: int, got_rows, truncated: bool,
                   top_r: int) -> bool:
    """Does one served answer say what the reference says: the exact
    count, the exact prefix of ``top_r`` rows (a planned answer comes
    whole), an honest truncation flag."""
    if q["kind"] == "join":
        got = [tuple(int(v) for v in row) for row in got_rows]
        full = min(len(want), top_r)
        same = got == want[: len(got)]
    else:
        got = np.asarray(got_rows, dtype=np.int64)
        full = len(want) if q["kind"] == "planned" else min(len(want), top_r)
        same = np.array_equal(got, want[: len(got)])
    return (got_count == len(want) and len(got) == full and bool(same)
            and truncated == (got_count > len(got)))
