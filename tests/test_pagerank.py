"""``ops.pagerank`` — float32 sums through the pull chain's own plan — against
the plain host reference ``algorithms/traversals.pagerank`` (numpy float64
from the snapshot's target relation), every atom's rank compared, on the CPU
at small sizes; the host reference against a dense-matrix Graphalytics PR on
a hypergraph of two-target links; the sum pyramid and the replacing fold
against numpy; and planted faults that the comparison must catch.

The tolerance, ``RTOL``, is relative per atom: float32 sums in a tree over
rows of a few hundred entries, ten iterations deep, stay within a few 1e-7
of float64 at these sizes; every planted fault moves a rank by far more.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypergraphdb_tpu import obs
from hypergraphdb_tpu.algorithms import traversals
from hypergraphdb_tpu.ops import PageRankResult, pagerank
from hypergraphdb_tpu.ops import ellbfs as eb
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot
from tests.test_components import _wide
from tests.test_ellbfs import (  # noqa: F401  (typed_graph: a fixture)
    FAMILIES,
    scalar_kernel_route,
    typed_graph,
)
from tests.test_pair_distances import linked_snapshot

RTOL = 1e-5
PR_COUNTERS = ("pr.runs", "pr.iterations", "pr.rows_folded")


def _tables(n_nodes, arities, flat, types=None):
    """A snapshot of ``n_nodes`` nodes, then a link per arity."""
    n = n_nodes + len(arities)
    type_of = np.zeros(n, dtype=np.int32)
    if types is not None:
        type_of[n_nodes:] = types
    is_link = np.zeros(n, dtype=bool)
    is_link[n_nodes:] = True
    offsets = np.zeros(n + 1, dtype=np.int64)
    offsets[n_nodes + 1:] = np.cumsum(arities)
    return CSRSnapshot.from_tables(type_of, is_link, offsets, flat)


def _ranks(res, snap):
    return np.asarray(res.ranks)[: snap.num_atoms].astype(np.float64)


def _assert_is_the_reference(snap, family, res, iterations=10):
    assert isinstance(res, PageRankResult)
    n = snap.num_atoms
    ranks = np.asarray(res.ranks)
    assert ranks.dtype == np.float32 and ranks.shape == (eb._n_pad(n),)
    assert (ranks[n:] == 0).all()  # the dummy row and the pad rows
    want = traversals.pagerank(snap, family, iterations=iterations)
    np.testing.assert_allclose(_ranks(res, snap), want, rtol=RTOL, atol=0)
    assert res.iterations == iterations
    assert abs(res.mass - 1.0) < 1e-5 and abs(want.sum() - 1.0) < 1e-12


def _counted():
    got = [obs.default_registry().get(n) for n in PR_COUNTERS]
    return np.asarray([0 if c is None else int(c.value) for c in got])


# ------------------------------------------- against the plain reference


@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_ranks_on_random_hypergraphs_with_links_that_target_links(seed,
                                                                  typed):
    """A third of a link's entries point at an earlier link; a tenth of the
    nodes lie in no link and are dangling, as every link nothing targets
    is."""
    snap = linked_snapshot(700, 800, seed, n_types=4)
    family = (1, 3) if typed else None
    res = pagerank(snap, family, chunk=8)
    _assert_is_the_reference(snap, family, res)
    alone = np.diff(snap.inc_offsets[: snap.num_atoms + 1]) == 0
    assert alone[:700].sum() > 10 and alone[700:].sum() > 10


def test_duplicate_targets_and_one_target_links_are_the_walk_they_define():
    """Links of arity one (no step), links that hold one atom twice (a slot
    each, a step to the other DISTINCT atoms, or none where that is all the
    link holds), and a node held only by such links (dangling)."""
    r = np.random.default_rng(21)
    n_nodes = 300
    arities = r.integers(1, 6, size=500)
    arities[0] = 2
    flat = r.integers(0, 25, size=int(arities.sum()))  # a small pool: repeats
    flat[:2] = 299                                      # (299, 299): no step
    snap = _tables(n_nodes, arities, flat)
    lens = np.diff(snap.tgt_offsets[: snap.num_atoms + 1])
    assert (lens == 1).sum() > 50
    rows = [snap.targets_row(a) for a in range(n_nodes, snap.num_atoms)]
    assert sum(len(set(t.tolist())) < len(t) for t in rows) > 40
    res = pagerank(snap, chunk=4)
    _assert_is_the_reference(snap, None, res)
    # node 299 holds slots in a link with no other atom: it is dangling
    pw = eb._pr_weights(snap, eb.plans_for(snap))
    assert float(pw.inv_d[299]) == 0.0 and float(pw.c[299]) == 0.0


@pytest.mark.parametrize("kind", ["hub", "wide_link"])
def test_a_row_above_w_max_makes_the_upper_levels_sum(kind):
    snap = _wide(kind)
    plans = eb.plans_for(snap)
    if kind == "hub":
        assert len(plans.stage2_levels) > plans.stage2_n_lvl0
    else:
        assert len(plans.stage1.levels) > plans.stage1.n_lvl0
    _assert_is_the_reference(snap, None, pagerank(snap, chunk=4))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_ranks_on_a_real_hypergraph_under_each_family(typed_graph, family):
    """A real ``HyperGraph``: four link types, links that target links, a
    hub, an atom only one family touches, the type atoms among the
    vertices."""
    _, snap, handle, _ = typed_graph
    fam = {handle[n] for n in FAMILIES[family]}
    _assert_is_the_reference(snap, fam, pagerank(snap, fam))


@pytest.mark.parametrize("chunk", [1, 3, 8, 1 << 16])
def test_a_small_chunk_takes_the_scan_and_its_ragged_tail(chunk):
    snap = linked_snapshot(500, 600, 9, n_types=3)
    _assert_is_the_reference(snap, (1, 2), pagerank(snap, (1, 2),
                                                    chunk=chunk))


@pytest.mark.parametrize("iterations", [0, 1, 2, 5])
def test_the_iteration_count_is_run_exactly(iterations):
    """``iterations`` 0 is the uniform ``1/N``; every count is the
    reference's after as many."""
    snap = linked_snapshot(400, 500, 8, n_types=2)
    before = _counted()
    res = pagerank(snap, iterations=iterations, chunk=8)
    _assert_is_the_reference(snap, None, res, iterations=iterations)
    if not iterations:
        n = snap.num_atoms
        np.testing.assert_array_equal(np.asarray(res.ranks)[:n],
                                      np.float32(1.0) / np.float32(n))
    got = _counted() - before
    listed = int(eb._active_blocks(eb.plans_for(snap)).sum()) \
        * eb._block_rows(eb.plans_for(snap).n_pad)
    assert got.tolist() == [1, iterations, iterations * listed]


def test_the_mass_is_one_after_every_iteration():
    snap = linked_snapshot(600, 700, 6, n_types=4)
    for k in range(11):
        res = pagerank(snap, iterations=k, chunk=8)
        ranks = _ranks(res, snap)
        assert abs(res.mass - 1.0) < 1e-5 and abs(ranks.sum() - 1.0) < 1e-5
        want = traversals.pagerank(snap, iterations=k)
        assert abs(want.sum() - 1.0) < 1e-12


def test_an_empty_family_leaves_every_atom_dangling():
    snap = linked_snapshot(300, 300, 2, n_types=3)
    res = pagerank(snap, ())
    n = snap.num_atoms
    np.testing.assert_allclose(_ranks(res, snap), 1.0 / n, rtol=RTOL)
    _assert_is_the_reference(snap, (), res)


def _two_ranges(seed):
    """More rows than a row block: nodes through both blocks, the links
    last, so the ragged last block — folded from ``n_pad - UPDATE_ROWS`` —
    shares thousands of active rows with the block before it."""
    ub = eb.UPDATE_ROWS
    r = np.random.default_rng(seed)
    n_nodes, n_links = ub + 2000, 3000
    arities = r.integers(2, 4, size=n_links)
    flat = r.integers(0, n_nodes, size=int(arities.sum()))
    snap = _tables(n_nodes, arities, flat)
    plans = eb.plans_for(snap)
    blocks = eb._active_blocks(plans)
    start = plans.n_pad - ub
    assert blocks.tolist() == [True, True] and 0 < start < n_nodes
    return snap


def test_the_clamped_last_block_overlaps_active_rows_and_counts_once():
    snap = _two_ranges(31)
    _assert_is_the_reference(snap, None, pagerank(snap, iterations=4),
                             iterations=4)


# ------------------------------------------- planted faults must fail


@pytest.fixture
def retraced(monkeypatch):
    """``_pr_iter`` traced anew under a patched helper: JAX keeps a trace by
    the function it traced, so its caches are cleared before the patch and
    again after it, when no trace of the patched program may be left."""
    jax.clear_caches()
    yield monkeypatch
    monkeypatch.undo()
    jax.clear_caches()


def test_a_fold_that_accumulates_double_counts_the_overlap(retraced):
    """An accumulating add in place of the replacing fold adds the rows the
    ragged last block shares with the one before twice."""
    snap = _two_ranges(32)
    real = eb._fold_rows

    def accumulating(state, reach, rows, n_atoms, combine, **kw):
        return real(state, reach, rows, n_atoms,
                    lambda cur, reached: cur + reached, **kw)

    retraced.setattr(eb, "_fold_rows", accumulating)
    res = pagerank(snap, iterations=2)
    want = traversals.pagerank(snap, iterations=2)
    rel = np.abs(_ranks(res, snap) - want) / want
    assert (rel > 0.1).sum() > 1000


def test_a_dropped_dangling_term_fails(retraced):
    snap = linked_snapshot(700, 800, 3, n_types=4)
    retraced.setattr(eb, "_dangling", lambda ranks, inv_d: jnp.float32(0.0))
    res = pagerank(snap, chunk=8)
    want = traversals.pagerank(snap)
    assert not np.allclose(_ranks(res, snap), want, rtol=RTOL, atol=0)
    assert res.mass < 0.9


# ------------------------------------------- the host reference


def test_the_reference_is_graphalytics_pr_on_two_target_links():
    """On a hypergraph whose links all hold two distinct atoms the walk is
    Graphalytics' undirected PR over the multigraph whose edges are the
    links (parallel links counted, links that target links included, every
    atom a vertex): a dense matrix, power-iterated."""
    r = np.random.default_rng(41)
    n_nodes, n_links = 60, 150
    n = n_nodes + n_links
    a = r.integers(0, n_nodes, size=n_links)
    b = (a + 1 + r.integers(0, n_nodes - 1, size=n_links)) % n_nodes
    # a fifth of the links hold an earlier link as their second atom
    later = np.arange(n_links) > 10
    to_link = later & (r.random(n_links) < 0.2)
    b[to_link] = n_nodes + r.integers(0, 10, size=int(to_link.sum()))
    a[:3] = a[3]  # parallel links
    b[:3] = b[3]
    flat = np.stack([a, b], axis=1).reshape(-1)
    snap = _tables(n_nodes, np.full(n_links, 2), flat)
    adj = np.zeros((n, n))
    np.add.at(adj, (a, b), 1.0)
    np.add.at(adj, (b, a), 1.0)
    deg = adj.sum(axis=1)
    d = 0.85
    pr = np.full(n, 1.0 / n)
    for k in range(1, 11):
        share = np.divide(pr, deg, out=np.zeros(n), where=deg > 0)
        pr = (1 - d) / n + d * adj.T @ share + d * pr[deg == 0].sum() / n
        np.testing.assert_allclose(traversals.pagerank(snap, iterations=k),
                                   pr, rtol=1e-12)
    assert (deg == 0).sum() > 10  # isolated nodes and links: dangling


# ------------------------------------------- the pyramid and the fold


@pytest.mark.parametrize("width", [*eb.CLASS_WIDTHS, "above"])
def test_the_sum_pyramid_is_numpy_at_every_width_class(width):
    """Each row's chunk holds the sum of its values, an empty row the zero
    row's 0.0: every entry counted once, every padded index the
    identity."""
    r = np.random.default_rng(eb.CLASS_WIDTHS.index(width)
                              if width != "above" else 99)
    if width == "above":
        lo, hi = eb.W_MAX + 1, 9 * eb.W_MAX
    else:
        i = eb.CLASS_WIDTHS.index(width)
        lo, hi = (eb.CLASS_WIDTHS[i - 1] + 1 if i else 1), width
    n_rows, n_values = 97, 500
    degrees = r.integers(lo, hi + 1, size=n_rows)
    degrees[::9] = 0
    offsets = np.zeros(n_rows + 1, dtype=np.int64)
    offsets[1:] = np.cumsum(degrees)
    flat = r.integers(0, n_values, size=int(offsets[-1]))
    plan = eb.build_reduce_plan(offsets, flat, n_rows, zero_row=n_values)
    values = r.integers(1, 64, size=n_values + 1).astype(np.float32)
    values[n_values] = 0.0  # the zero row a padded index reads
    route = eb._stage_route(tuple(map(len, plan.levels)), plan.widths,
                            plan.n_lvl0, values, 4, kernel=False)
    buf = np.asarray(eb._apply_plan(
        jnp.asarray(values), tuple(map(jnp.asarray, plan.levels)), route,
        scopes=("hg.t", "hg.t")))
    assert buf.shape == (plan.concat_size + 1,) and buf[-1] == 0.0
    # small whole numbers: float32 sums are exact
    want = np.asarray([values[flat[offsets[i]:offsets[i + 1]]].sum()
                       for i in range(n_rows)])
    np.testing.assert_array_equal(buf[plan.out_map], want)


@pytest.mark.parametrize("typed", [False, True])
def test_pr_iter_on_the_kernel_gives_the_xla_routes_ranks(typed, monkeypatch):
    """``pagerank`` with its level-0 gathers on the kernel's scalar form
    (the Pallas interpreter standing in for the chip) against the same
    iterations on the XLA gather: every rank within ``RTOL`` (a sum in
    another order), the mass, both the reference, and most of the plan's
    level-0 indices on the kernel."""
    snap = linked_snapshot(700, 800, 12, n_types=4)
    family = (1, 3) if typed else None
    want = pagerank(snap, family, iterations=3, chunk=1 << 16)
    calls = scalar_kernel_route(monkeypatch)
    got = pagerank(snap, family, iterations=3, chunk=1 << 16)
    np.testing.assert_allclose(np.asarray(got.ranks), np.asarray(want.ranks),
                               rtol=RTOL, atol=0)
    assert abs(got.mass - want.mass) < 1e-6
    _assert_is_the_reference(snap, family, got, iterations=3)
    assert calls and {op for _, op in calls} == {"sum"}
    share = obs.default_registry().get("scalar.gather.indices_kernel").value
    assert share > 0


@pytest.mark.parametrize("route", ["xla", "kernel", "short_blocks"])
def test_the_fold_counters_add_up_the_listed_rows(route, retraced):
    """``scalar.fold.rows`` grows by the plan's listed rows an iteration —
    what ``pr.rows_folded`` counts — and ``.rows_kernel`` by as many where
    the replacing fold's fetch takes the kernel (the interpreter standing
    in), by none on the XLA route and where a block is under
    ``MIN_INDICES``; the ranks are the reference's on every route."""
    names = ("scalar.fold.rows", "scalar.fold.rows_kernel")

    def counted():
        got = [obs.default_registry().get(n) for n in names]
        return np.asarray([0 if c is None else int(c.value) for c in got])

    snap = linked_snapshot(700, 800, 12, n_types=4)
    plans = eb.plans_for(snap)
    listed = (int(eb._active_blocks(plans).sum())
              * eb._block_rows(plans.n_pad))
    calls = []
    if route != "xla":
        calls = scalar_kernel_route(
            retraced, 64 if route == "kernel" else plans.n_pad + 1)
    before, folded = counted(), _counted()[2]
    res = pagerank(snap, iterations=3)
    rows, kernel = counted() - before
    assert rows == 3 * listed == _counted()[2] - folded > 0
    assert kernel == (rows if route == "kernel" else 0)
    assert ((1, "sum") in calls) == (route == "kernel")
    _assert_is_the_reference(snap, None, res, iterations=3)


@pytest.mark.parametrize("blocks", ["all", "some", "ragged_last"])
def test_the_replacing_fold_is_numpy_and_an_add_is_not(blocks):
    """``_fold_rows`` replacing a zero state over listed blocks of 16 rows of
    a state whose last block is ragged: ``buf[out_map]`` where listed, 0
    elsewhere, the dummy row 0.0 — the rows the ragged block shares with
    the block before written twice with the same value. The accumulating
    add gives those rows twice their value where both blocks are listed."""
    r = np.random.default_rng(len(blocks))
    n_pad, n_atoms, ub = 72, 69, 16
    buf = r.integers(1, 100, size=30).astype(np.float32)
    buf[-1] = 0.0
    out_map = r.integers(0, 30, size=n_pad).astype(np.int32)
    out_map[n_atoms] = 29
    listed = {"all": [0, 1, 2, 3, 4], "some": [1, 3],
              "ragged_last": [3, 4]}[blocks]
    rows = eb._listed(jnp.asarray(out_map), np.isin(np.arange(5), listed),
                      block_rows=ub)
    fold = partial(eb._fold_rows, jnp.zeros(n_pad, jnp.float32),
                   jnp.asarray(buf), rows, jnp.int32(n_atoms), block_rows=ub)
    got = np.asarray(fold(lambda cur, reached: reached))
    folded = np.zeros(n_pad, dtype=int)
    for b in listed:
        start = min(b * ub, n_pad - ub)
        folded[start:start + ub] += 1
    want = np.where(folded > 0, buf[out_map], 0.0)
    want[n_atoms] = 0.0
    np.testing.assert_array_equal(got, want)
    added = np.asarray(fold(lambda cur, reached: cur + reached))
    twice = np.flatnonzero(folded == 2)
    assert (len(twice) > 0) == (blocks != "some")
    np.testing.assert_array_equal(added[twice], 2 * want[twice])


def test_the_weights_are_built_once_a_plan_and_kept_apart():
    """``_pr_weights`` hangs its arrays on the snapshot beside
    ``_device_plans``' dict, never inside it: the other operators upload
    nothing new."""
    snap = linked_snapshot(300, 400, 12, n_types=2)
    pagerank(snap, iterations=1)
    dev = snap._pull_device
    pw = snap._pull_pagerank
    assert set(dev) == {"levels1", "levels2", "out_map", "inc_deg", "blocks",
                        "rows"}
    pagerank(snap, iterations=1)
    assert snap._pull_pagerank is pw
    s1 = eb.plans_for(snap).stage1
    assert pw.w.shape == (s1.concat_size + 1,) and float(pw.w[-1]) == 0.0
