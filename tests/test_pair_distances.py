"""``ops.pair_distances`` — batched single-pair shortest-path LENGTHS by a
two-sided search over two bitmaps — against the plain host reference
``algorithms/traversals.shortest_path_length`` (one ball, from the start
alone) and ``len(dijkstra(...)) - 1``, lengths compared exactly, on the CPU
at small sizes. The chain it runs is ``bfs_pull``'s (``ellbfs._expand``);
the tests of that chain are ``tests/test_ellbfs.py``'s, untouched."""

import jax.numpy as jnp
import numpy as np
import pytest

from hypergraphdb_tpu import obs
from hypergraphdb_tpu.algorithms.traversals import (
    DefaultALGenerator,
    dijkstra,
    shortest_path_length,
)
from hypergraphdb_tpu.ops import PairDistResult, bfs_pull, pair_distances
from hypergraphdb_tpu.ops import ellbfs as eb
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot
from tests.test_ellbfs import (  # noqa: F401  (typed_graph: a fixture)
    FAMILIES,
    _Sides,
    _SnapshotGraph,
    random_snapshot,
    typed_graph,
)

PAIR_COUNTERS = ("bfs.pairs.batches", "bfs.pairs.expansions.sparse",
                 "bfs.pairs.expansions.dense", "bfs.pairs.meet_tests",
                 "bfs.pairs.early_exits")


class _Counted:
    """What the five ``bfs.pairs.*`` counters grew by inside the block."""

    @staticmethod
    def _read():
        got = [obs.default_registry().get(n) for n in PAIR_COUNTERS]
        return [0 if c is None else int(c.value) for c in got]

    def __enter__(self):
        self._t0 = self._read()
        return self

    def __exit__(self, *exc):
        (self.batches, self.sparse, self.dense, self.tests,
         self.early_exits) = (a - b for a, b in zip(self._read(), self._t0))


def _generator(graph, family):
    """``DefaultALGenerator`` under the link predicate "type in family"."""
    if family is None:
        return DefaultALGenerator(graph)
    return DefaultALGenerator(
        graph, link_predicate=lambda g, link:
        int(g.get_type_handle_of(link)) in family)


def _assert_matches_reference(graph, n_atoms, sources, targets, cap, family,
                              res):
    assert isinstance(res, PairDistResult)
    assert isinstance(res.dist, np.ndarray) and res.dist.dtype == np.int32
    assert res.dist.shape == (len(sources),)
    gen = _generator(graph, family)
    for k, (s, t) in enumerate(zip(np.asarray(sources).tolist(),
                                   np.asarray(targets).tolist())):
        # an end that is no atom (the pad seed) is no end of a path
        want = -1 if n_atoms in (s, t) else shortest_path_length(
            graph, s, t, gen, max_distance=cap)
        assert res.dist[k] == want, f"pair {k}: ({s}, {t}) under cap {cap}"
        if want >= 0:
            assert len(dijkstra(graph, s, t, gen)) - 1 == want


def linked_snapshot(n_nodes, n_links, seed, n_types):
    """A random typed hypergraph in which a link's targets are nodes AND
    earlier links (links that target links), arity 2-4."""
    r = np.random.default_rng(seed)
    n = n_nodes + n_links
    type_of = np.zeros(n, dtype=np.int32)
    type_of[n_nodes:] = 1 + r.integers(0, n_types, size=n_links)
    is_link = np.zeros(n, dtype=bool)
    is_link[n_nodes:] = True
    arities = r.integers(2, 5, size=n_links)
    offsets = np.zeros(n + 1, dtype=np.int64)
    offsets[n_nodes + 1:] = np.cumsum(arities)
    below = np.repeat(n_nodes + np.arange(n_links), arities)
    # a third of the entries point below their own link, at a link if one
    # is there
    flat = r.integers(0, n_nodes, size=len(below))
    at_link = (r.random(len(below)) < 0.33) & (below > n_nodes)
    flat[at_link] = r.integers(n_nodes, below[at_link])
    return CSRSnapshot.from_tables(type_of, is_link, offsets, flat)


# ------------------------------------------- against the plain reference


@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
@pytest.mark.parametrize("cap", [1, 2, 3, 6])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_pairs_on_a_real_hypergraph_match_the_reference(
        typed_graph, family, cap, first_hop, monkeypatch):
    """A real ``HyperGraph``: links of four types, links that target links,
    a hub, an atom only one family touches; every seed paired with every
    other through two rotations (a link among the ends, ``s == t`` too)."""
    g, snap, handle, seeds = typed_graph
    fam = {handle[n] for n in FAMILIES[family]}
    sources = np.concatenate([seeds, seeds, seeds])
    targets = np.concatenate([np.roll(seeds, 1), np.roll(seeds, 4), seeds])
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    res = pair_distances(snap, sources, targets, cap, link_types=fam)
    _assert_matches_reference(g, snap.num_atoms, sources, targets, cap, fam,
                              res)
    if family == "empty":  # nothing to follow: s == t or nothing
        assert res.expansions == 0
        assert res.dist.tolist() == [-1] * 18 + [0] * 9


@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("cap", [2, 5, 9])
@pytest.mark.parametrize("seed", [3, 4])
def test_pairs_on_random_graphs_with_links_that_target_links(
        seed, cap, typed, monkeypatch):
    """70 pairs in blocks of 32, 32 and 6 (+ 26 pad columns) over a sparse
    random hypergraph whose links target links: by the rule as it stands,
    then both first hops dense — the same lengths."""
    snap = linked_snapshot(700, 800, seed, n_types=4)
    family = (1, 3, 4) if typed else None
    r = np.random.default_rng(seed)
    sources = r.integers(0, snap.num_atoms, size=70).astype(np.int32)
    targets = r.integers(0, snap.num_atoms, size=70).astype(np.int32)
    targets[:3] = sources[:3]                       # s == t
    sources[3], targets[4] = snap.num_atoms, snap.num_atoms  # no atom
    with _Sides() as ran:
        res = pair_distances(snap, sources, targets, cap, link_types=family,
                             k_block=32)
    assert ran.sparse == 6 and res.expansions <= 3 * cap
    _assert_matches_reference(_SnapshotGraph(snap), snap.num_atoms, sources,
                              targets, cap, family, res)
    if cap > 2:  # several lengths, and pairs with no path
        assert len(set(res.dist.tolist())) >= 4
    monkeypatch.setattr(eb, "SPARSE_SHARE", 1 << 62)  # no input is sparse
    with _Sides() as ran:
        dense = pair_distances(snap, sources, targets, cap,
                               link_types=family, k_block=32)
    assert ran.sparse == 0 and ran.dense == dense.expansions
    assert np.array_equal(res.dist, dense.dist)
    assert dense.expansions == res.expansions


# ------------------------------------------------- a path graph: 0 ... 7


def path_snapshot(n=9):
    """Atoms 0 … n-1 in a row, atom i and i+1 joined by a binary link."""
    total = n + (n - 1)
    is_link = np.zeros(total, dtype=bool)
    is_link[n:] = True
    offsets = np.zeros(total + 1, dtype=np.int64)
    offsets[n + 1:] = 2 * np.arange(1, n)
    flat = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1).reshape(-1)
    return CSRSnapshot.from_tables(np.zeros(total, np.int32), is_link,
                                   offsets, flat)


@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
@pytest.mark.parametrize("cap", [0, 1, 2, 3, 4, 7, 8])
def test_every_length_and_meet_position_on_a_path(cap, first_hop,
                                                  monkeypatch):
    """From atom 0 to atom d, d = 0 … 7, and back: a length d is first seen
    by the test after expansion d, at the balls' radii (⌈d/2⌉, ⌊d/2⌋) — the
    forward side has the extra hop —, so the lengths 1 … 7 pass through
    every meet position up to (4, 3); past the cap it is -1."""
    snap = path_snapshot()
    sources = np.r_[np.zeros(8, int), np.arange(8)].astype(np.int32)
    targets = np.r_[np.arange(8), np.zeros(8, int)].astype(np.int32)
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    with _Counted() as ran:
        res = pair_distances(snap, sources, targets, cap)
    lengths = [d if d <= cap else -1 for d in range(8)]
    assert res.dist.tolist() == lengths * 2
    # every pair up to 7 apart: the batch runs to the cap, or to depth 7
    assert res.expansions == min(cap, 7)
    assert ran.tests == res.expansions and ran.batches == 1
    assert ran.sparse + ran.dense == res.expansions
    assert ran.sparse == (min(cap, 2) if first_hop == "sparse" else 0)
    assert ran.early_exits == (1 if cap > 7 else 0)


def test_the_lengths_are_the_first_hop_count_at_which_bfs_pull_holds_the_target():
    snap = random_snapshot(300, 500, 3, seed=9, n_types=3)
    r = np.random.default_rng(9)
    sources = r.integers(0, 300, size=32).astype(np.int32)
    targets = r.integers(0, 300, size=32).astype(np.int32)
    family, cap = (1, 2), 6
    dist = pair_distances(snap, sources, targets, cap,
                          link_types=family).dist
    first = np.full(32, -1)
    for h in range(cap, -1, -1):
        vt = np.asarray(bfs_pull(snap, sources, h, link_types=family,
                                 count_edges=False).visited_t)
        k = np.arange(32)
        holds = (vt[targets, k >> 5] >> (k & 31).astype(np.uint32)) & 1
        first[holds.astype(bool)] = h
    assert dist.tolist() == first.tolist()
    assert {-1, 2, 3} <= set(dist.tolist())


# ------------------------------------------- the block's end follows the data


def star_snapshot(n_leaves=40, lone=3):
    """One centre joined to every leaf by a binary link, and ``lone`` atoms
    no link touches: two leaves are 2 apart."""
    n = 1 + n_leaves + lone
    total = n + n_leaves
    is_link = np.zeros(total, dtype=bool)
    is_link[n:] = True
    offsets = np.zeros(total + 1, dtype=np.int64)
    offsets[n + 1:] = 2 * np.arange(1, n_leaves + 1)
    flat = np.stack([np.zeros(n_leaves, int), 1 + np.arange(n_leaves)],
                    axis=1).reshape(-1)
    return CSRSnapshot.from_tables(np.zeros(total, np.int32), is_link,
                                   offsets, flat), n_leaves


def test_all_pairs_two_apart_run_no_dense_expansion(monkeypatch):
    """Early exit: leaves of a star are met by the test after each side's
    sparse first hop — two expansions under a cap of 6, none dense."""
    snap, n_leaves = star_snapshot()
    monkeypatch.setattr(eb, "SPARSE_SHARE", 1)
    sources = (1 + np.arange(33)).astype(np.int32)
    targets = (1 + (np.arange(33) + 5) % n_leaves).astype(np.int32)
    with _Counted() as ran, _Sides() as sides:
        res = pair_distances(snap, sources, targets, 6, k_block=32)
    assert res.dist.tolist() == [2] * 33
    assert res.expansions == 4  # two blocks of two
    assert (ran.sparse, ran.dense, ran.tests) == (4, 0, 4)
    assert (sides.sparse, sides.dense) == (4, 0)
    assert (ran.batches, ran.early_exits) == (1, 2)


@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
def test_exhaustion_ends_a_block_before_the_cap(first_hop, monkeypatch):
    """An end in no link (its ball never grows), both ends in no link, and
    pairs of leaves: the block ends when the last pair is met, at depth 2
    of 9, and the ends in no link read -1 from the first test on."""
    snap, n_leaves = star_snapshot()
    lone = 1 + n_leaves
    sources = np.asarray([1, lone, 2, lone, 3, 0], dtype=np.int32)
    targets = np.asarray([lone, 4, 5, lone + 1, 3, 6], dtype=np.int32)
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    with _Counted() as ran:
        res = pair_distances(snap, sources, targets, 9)
    assert res.dist.tolist() == [-1, -1, 2, -1, 0, 1]
    assert res.expansions == 2 and ran.early_exits == 1
    # the unanswerable pairs alone: one expansion shows the forward ball of
    # pair 1 and 3 closed; pair 0's needs the backward side's
    with _Counted() as ran:
        res = pair_distances(snap, sources[[1, 3]], targets[[1, 3]], 9)
    assert res.dist.tolist() == [-1, -1] and res.expansions == 1
    res = pair_distances(snap, sources[:1], targets[:1], 9)
    assert res.dist.tolist() == [-1] and res.expansions == 2


def test_a_small_component_is_exhausted_by_a_dense_expansion(monkeypatch):
    """Two paths of three atoms: a pair across them is -1 once a ball has
    stopped growing — the third expansion of a cap of 8, the forward ball's
    second, dense, whose update says no column gained a row."""
    total = 6 + 4
    is_link = np.zeros(total, dtype=bool)
    is_link[6:] = True
    offsets = np.zeros(total + 1, dtype=np.int64)
    offsets[7:] = 2 * np.arange(1, 5)
    flat = np.asarray([0, 1, 1, 2, 3, 4, 4, 5])
    snap = CSRSnapshot.from_tables(np.zeros(total, np.int32), is_link,
                                   offsets, flat)
    monkeypatch.setattr(eb, "SPARSE_SHARE", 1)
    with _Counted() as ran:
        res = pair_distances(snap, [1, 0], [4, 2], 8)
    assert res.dist.tolist() == [-1, 2]
    assert res.expansions == 3 and (ran.sparse, ran.dense) == (2, 1)
    assert ran.early_exits == 1


def test_pad_columns_are_never_met_and_no_pair_is_no_work():
    """K = 5: 27 pad columns, both ends on the dummy row. They are neither
    met nor waited for, and what is handed back is K long."""
    snap, _ = star_snapshot()
    res = pair_distances(snap, [1, 2, 3, 4, 5], [2, 3, 4, 5, 1], 4)
    assert res.dist.tolist() == [2] * 5 and res.expansions == 2
    # nothing to search for: every pair answered at depth 0
    with _Sides() as sides:
        res = pair_distances(snap, [1, 2], [1, 2], 4)
    assert res.dist.tolist() == [0, 0] and res.expansions == 0
    assert (sides.sparse, sides.dense) == (0, 0)
    res = pair_distances(snap, np.zeros(0, np.int32), np.zeros(0, np.int32),
                         4)
    assert res.dist.shape == (0,) and res.expansions == 0


@pytest.mark.parametrize("bad", ["k_block", "max_hops", "lengths"])
def test_pair_distances_validates_its_arguments(bad):
    snap, _ = star_snapshot()
    with pytest.raises(ValueError):
        if bad == "k_block":
            pair_distances(snap, [1], [2], 3, k_block=48)
        elif bad == "max_hops":
            pair_distances(snap, [1], [2], -1)
        else:
            pair_distances(snap, [1, 2], [2], 3)


# ------------------------------------------------------ the two programs


def _meet_definition(fwd, bwd):
    return np.bitwise_or.reduce(fwd & bwd, axis=0)


@pytest.mark.parametrize("rows", ["short", "multiple", "ragged"])
@pytest.mark.parametrize("kw", [1, 4, 128])
def test_meet_is_the_or_of_the_anded_rows(kw, rows):
    """``_meet`` row block by row block (64 rows a block here) against the
    definition, on sparse bitmaps so that most columns do NOT meet."""
    n = {"short": 40, "multiple": 192, "ragged": 201}[rows]
    r = np.random.default_rng(n + kw)

    def sparse():
        words = r.integers(0, 1 << 32, size=(n, kw), dtype=np.uint64)
        return (words & r.integers(0, 1 << 32, size=(n, kw), dtype=np.uint64)
                & (r.random((n, kw)) < 0.2)
                * np.uint64(0xFFFFFFFF)).astype(np.uint32)

    fwd, bwd = sparse(), sparse()
    want = _meet_definition(fwd, bwd)
    assert 0 < np.count_nonzero(want) and (want != 0xFFFFFFFF).any()
    got = np.asarray(eb._meet_words(jnp.asarray(fwd), jnp.asarray(bwd),
                                    block_rows=64))
    assert got.dtype == np.uint32 and np.array_equal(got, want)
    assert np.array_equal(np.asarray(eb._meet(jnp.asarray(fwd),
                                              jnp.asarray(bwd))), want)
    cols = eb._columns(want)
    assert cols.shape == (32 * kw,) and cols.dtype == bool
    assert all(cols[k] == bool((want[k >> 5] >> (k & 31)) & 1)
               for k in range(32 * kw))


@pytest.mark.parametrize("case", ["no_fresh_bit", "some_columns_grow"])
def test_ball_update_is_the_visited_update_and_says_which_columns_grew(case):
    r = np.random.default_rng(17)
    n_pad, kw, n_reach = 72, 2, 20
    visited = r.integers(0, 1 << 32, size=(n_pad, kw), dtype=np.uint64
                         ).astype(np.uint32)
    reach = r.integers(0, 1 << 32, size=(n_reach + 1, kw), dtype=np.uint64
                       ).astype(np.uint32)
    reach[n_reach] = 0  # the zero row
    out_map = r.integers(0, n_reach + 1, size=n_pad).astype(np.int32)
    n_atoms = 70
    out_map[n_atoms:] = n_reach
    visited[n_atoms:] = 0
    if case == "no_fresh_bit":
        reach &= np.bitwise_and.reduce(visited[:n_atoms], axis=0)
    else:  # column 3 and word 1 can only hold what the ball already does
        keep = np.bitwise_and.reduce(visited[:n_atoms], axis=0)
        reach[:, 1] &= keep[1]
        reach[:, 0] &= keep[0] | ~np.uint32(1 << 3)
    rows = eb._listed(jnp.asarray(out_map), np.ones(1, dtype=bool))
    want = visited | reach[out_map]
    fresh = np.bitwise_or.reduce(reach[out_map] & ~visited, axis=0)
    grown = eb._visited_update(jnp.asarray(visited), jnp.asarray(reach),
                               rows, jnp.int32(n_atoms))
    ball, grew = eb._ball_update(jnp.asarray(visited), jnp.asarray(reach),
                                 rows, jnp.int32(n_atoms))
    assert np.array_equal(np.asarray(ball), np.asarray(grown))
    assert np.array_equal(np.asarray(ball), want)
    assert np.array_equal(np.asarray(grew), fresh)
    if case == "no_fresh_bit":
        assert not fresh.any()
    else:
        assert fresh[1] == 0 and fresh[0] and not (fresh[0] >> 3) & 1
