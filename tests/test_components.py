"""``ops.connected_components`` — min-label rounds over the pull chain's own
plan — against the plain host reference
``algorithms/traversals.connected_components`` (a ``HGBreadthFirstTraversal``
to exhaustion from each atom not labelled yet, in id order), labels compared
exactly for every atom, on the CPU at small sizes; and the min pyramid and
the counting fold it runs against numpy. The bitmap operators' tests are
``tests/test_ellbfs.py``'s and ``tests/test_pair_distances.py``'s,
untouched."""

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypergraphdb_tpu import obs
from hypergraphdb_tpu.algorithms import traversals
from hypergraphdb_tpu.algorithms.traversals import DefaultALGenerator
from hypergraphdb_tpu.ops import ComponentsResult, connected_components
from hypergraphdb_tpu.ops import ellbfs as eb
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot
from tests.test_ellbfs import (  # noqa: F401  (typed_graph: a fixture)
    FAMILIES,
    _SnapshotGraph,
    scalar_kernel_route,
    typed_graph,
)
from tests.test_pair_distances import linked_snapshot

WCC_COUNTERS = ("wcc.runs", "wcc.rounds", "wcc.rows_lowered",
                "wcc.rows_folded")
MAX = eb.INT32_MAX


class _Graph(_SnapshotGraph):
    """What the host reference asks of a graph, answered from a snapshot:
    every atom id of it, in order."""

    def atoms(self):
        return range(self.snap.num_atoms)


def _generator(graph, family):
    if family is None:
        return None
    return DefaultALGenerator(
        graph, link_predicate=lambda g, link:
        int(g.get_type_handle_of(link)) in family)


def _reference(snap, family):
    g = _Graph(snap)
    got = traversals.connected_components(g, _generator(g, family))
    return np.asarray([got[a] for a in range(snap.num_atoms)])


def _rounds(snap, family, labels):
    """The rounds synchronous propagation takes: the farthest any atom
    lies from its component's least id, plus the quiet round."""
    g = _Graph(snap)
    gen = _generator(g, family) or traversals.SimpleALGenerator(g)
    far = 0
    for m in np.unique(labels).tolist():
        dist, q = {m: 0}, deque([m])
        while q:
            a = q.popleft()
            for _, b in gen.generate(a):
                if b not in dist:
                    dist[b] = dist[a] + 1
                    q.append(b)
        far = max(far, max(dist.values()))
    return far + 1


def _counted():
    got = [obs.default_registry().get(n) for n in WCC_COUNTERS]
    return np.asarray([0 if c is None else int(c.value) for c in got])


def _assert_is_the_reference(snap, family, res):
    assert isinstance(res, ComponentsResult)
    n = snap.num_atoms
    labels = np.asarray(res.labels)
    assert labels.dtype == np.int32 and labels.shape == (eb._n_pad(n),)
    assert (labels[n:] == MAX).all()      # the dummy row and the pad rows
    want = _reference(snap, family)
    np.testing.assert_array_equal(labels[:n], want)
    assert res.n_components == int(np.count_nonzero(want == np.arange(n)))
    assert res.rounds == _rounds(snap, family, want)


# ------------------------------------------- against the plain reference


@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_labels_on_random_hypergraphs_with_links_that_target_links(
        seed, typed):
    """A third of a link's entries point at an earlier link; a tenth of the
    nodes lie in no link and keep their own label."""
    snap = linked_snapshot(700, 800, seed, n_types=4)
    family = (1, 3) if typed else None
    res = connected_components(snap, family, chunk=8)
    _assert_is_the_reference(snap, family, res)
    alone = np.diff(snap.inc_offsets[: snap.num_atoms + 1]) == 0
    assert alone[:700].sum() > 10  # isolated nodes are there
    labels = np.asarray(res.labels)[: snap.num_atoms]
    assert (labels[np.flatnonzero(alone)] == np.flatnonzero(alone)).all()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_labels_on_a_real_hypergraph_match_the_reference(typed_graph,
                                                         family):
    """A real ``HyperGraph``: four link types, links that target links, a
    hub, an atom only one family touches; the reference runs over the
    graph itself, its type atoms among its atoms."""
    g, snap, handle, _ = typed_graph
    fam = {handle[n] for n in FAMILIES[family]}
    res = connected_components(snap, fam)
    want = traversals.connected_components(g, _generator(g, fam))
    labels = np.asarray(res.labels)
    for atom, label in want.items():
        assert labels[atom] == label, atom
    if family == "empty":
        assert res.rounds == 0


def _path(n_links):
    """Atoms 0..L, link i holding atoms i and i+1: the least id walks one
    atom a round."""
    n = 2 * n_links + 1
    is_link = np.zeros(n, dtype=bool)
    is_link[n_links + 1:] = True
    offsets = np.zeros(n + 1, dtype=np.int64)
    offsets[n_links + 2:] = 2 * np.arange(1, n_links + 1)
    flat = np.stack([np.arange(n_links), np.arange(1, n_links + 1)], 1)
    return CSRSnapshot.from_tables(np.zeros(n, np.int32), is_link, offsets,
                                   flat.reshape(-1))


@pytest.mark.parametrize("n_links", [1, 2, 5, 9])
def test_a_path_of_l_links_takes_l_plus_one_rounds(n_links):
    snap = _path(n_links)
    res = connected_components(snap)
    assert res.rounds == n_links + 1
    labels = np.asarray(res.labels)[: snap.num_atoms]
    assert (labels[: n_links + 1] == 0).all()
    # a link lies in no link: its own component
    assert (labels[n_links + 1:] == np.arange(n_links + 1,
                                              2 * n_links + 1)).all()
    assert res.n_components == 1 + n_links
    _assert_is_the_reference(snap, None, res)


def _wide(kind):
    """A hub in more links than ``W_MAX`` (stage 2 climbs), or a link of
    more targets than ``W_MAX`` (stage 1 climbs), in a random graph."""
    r = np.random.default_rng(11)
    n_nodes, n_links = 400, 300
    n = n_nodes + n_links
    arities = r.integers(2, 4, size=n_links)
    if kind == "wide_link":
        arities[7] = 3 * eb.W_MAX + 5
    offsets = np.zeros(n + 1, dtype=np.int64)
    offsets[n_nodes + 1:] = np.cumsum(arities)
    flat = r.integers(1, n_nodes, size=int(arities.sum()))
    if kind == "hub":
        flat[offsets[n_nodes:-1][: 2 * eb.W_MAX + 9]] = 0
    is_link = np.zeros(n, dtype=bool)
    is_link[n_nodes:] = True
    return CSRSnapshot.from_tables(np.zeros(n, np.int32), is_link, offsets,
                                   flat)


@pytest.mark.parametrize("kind", ["hub", "wide_link"])
def test_a_row_above_w_max_makes_the_upper_levels_run(kind):
    snap = _wide(kind)
    plans = eb.plans_for(snap)
    if kind == "hub":
        assert len(plans.stage2_levels) > plans.stage2_n_lvl0
    else:
        assert len(plans.stage1.levels) > plans.stage1.n_lvl0
    _assert_is_the_reference(snap, None, connected_components(snap, chunk=4))


@pytest.mark.parametrize("chunk", [1, 3, 8, 1 << 16])
def test_a_small_chunk_takes_the_scan_and_its_ragged_tail(chunk):
    """``chunk * STEP_WIDTH`` indices a scan step: 8 to 64 run every class
    through the scan and its tail, the default through one step."""
    snap = linked_snapshot(500, 600, 9, n_types=3)
    _assert_is_the_reference(snap, (1, 2),
                             connected_components(snap, (1, 2), chunk=chunk))


def test_an_empty_family_leaves_every_atom_its_own_label():
    snap = linked_snapshot(300, 300, 2, n_types=3)
    before = _counted()
    res = connected_components(snap, ())
    n = snap.num_atoms
    assert res.rounds == 0 and res.n_components == n
    np.testing.assert_array_equal(np.asarray(res.labels)[:n], np.arange(n))
    assert (_counted() - before).tolist() == [1, 0, 0, 0]


def test_two_calls_both_run_every_round():
    """Nothing is kept between calls: the second runs every round again,
    lowers the same rows and folds the plan's listed rows each round."""
    snap = linked_snapshot(600, 700, 6, n_types=4)
    first = _counted()
    a = connected_components(snap, (2, 4))
    second = _counted()
    b = connected_components(snap, (2, 4))
    third = _counted()
    assert a.rounds == b.rounds >= 2
    one, two = second - first, third - second
    assert one.tolist() == two.tolist()
    assert one[:2].tolist() == [1, a.rounds]
    plans = eb.plans_for(eb.restricted_for(snap, (2, 4)))
    n_pad = plans.n_pad
    listed = int(eb._active_blocks(plans).sum()) * eb._block_rows(n_pad)
    assert one[3] == a.rounds * listed
    # each lowered row is a label above its final one: at least one round
    # lowers it, and no row is lowered past where it ends
    final = np.asarray(a.labels)[: snap.num_atoms]
    assert 0 < one[2] and (final <= np.arange(snap.num_atoms)).all()
    np.testing.assert_array_equal(np.asarray(b.labels), np.asarray(a.labels))


#: the fold's two counters, once a dispatch (``_scalar_fold_rows``)
FOLD_COUNTERS = ("scalar.fold.rows", "scalar.fold.rows_kernel")


def _fold_counted():
    got = [obs.default_registry().get(n) for n in FOLD_COUNTERS]
    return np.asarray([0 if c is None else int(c.value) for c in got])


@pytest.fixture
def retraced(monkeypatch):
    """``_wcc_round`` traced anew under a patched helper (JAX keeps a trace
    by the function it traced): its caches cleared before the patch and
    again after it, when no trace of the patched program may be left."""
    jax.clear_caches()
    yield monkeypatch
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("route", ["xla", "kernel", "short_blocks"])
def test_the_fold_counters_add_up_the_listed_rows(route, retraced):
    """``scalar.fold.rows`` grows by the plan's listed rows a round — what
    ``wcc.rows_folded`` counts — and ``.rows_kernel`` by as many where the
    fold's fetch takes the kernel (the interpreter standing in), by none
    on the XLA route and where a block is under ``MIN_INDICES``; the
    labels are the reference's on every route."""
    snap = linked_snapshot(700, 800, 11, n_types=4)
    plans = eb.plans_for(eb.restricted_for(snap, (1, 3)))
    listed = (int(eb._active_blocks(plans).sum())
              * eb._block_rows(plans.n_pad))
    calls = []
    if route != "xla":
        calls = scalar_kernel_route(
            retraced, 64 if route == "kernel" else plans.n_pad + 1)
    before, folded = _fold_counted(), _counted()[3]
    res = connected_components(snap, (1, 3))
    rows, kernel = _fold_counted() - before
    assert rows == res.rounds * listed == _counted()[3] - folded > 0
    assert kernel == (rows if route == "kernel" else 0)
    assert ((1, "min") in calls) == (route == "kernel")
    _assert_is_the_reference(snap, (1, 3), res)


# ------------------------------------------- the pyramid and the fold


def _csr(degrees, n_values, seed):
    r = np.random.default_rng(seed)
    offsets = np.zeros(len(degrees) + 1, dtype=np.int64)
    offsets[1:] = np.cumsum(degrees)
    return offsets, r.integers(0, n_values, size=int(offsets[-1]))


@pytest.mark.parametrize("width", [*eb.CLASS_WIDTHS, "above"])
def test_the_min_pyramid_is_numpy_at_every_width_class(width):
    """Rows whose degrees fill one class (past the previous width, up to
    this one), or lie above ``W_MAX`` and climb, and empty rows: each row's
    chunk holds the min of its values, an empty row the zero row's
    ``INT32_MAX``; the zero row is ``INT32_MAX``."""
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
    offsets, flat = _csr(degrees, n_values, 5)
    plan = eb.build_reduce_plan(offsets, flat, n_rows, zero_row=n_values)
    values = r.integers(-50, 1 << 30, size=n_values + 1).astype(np.int32)
    values[n_values] = MAX  # the zero row a padded index reads
    buf = np.asarray(eb._apply_plan(
        jnp.asarray(values), tuple(map(jnp.asarray, plan.levels)),
        plan.widths, plan.n_lvl0, 4, False, scopes=("hg.t", "hg.t")))
    assert buf.shape == (plan.concat_size + 1,) and buf[-1] == MAX
    want = np.asarray([values[flat[offsets[i]:offsets[i + 1]]].min()
                       if degrees[i] else MAX for i in range(n_rows)])
    np.testing.assert_array_equal(buf[plan.out_map], want)


@pytest.mark.parametrize("blocks", ["all", "some", "ragged_last"])
def test_the_counting_fold_lowers_and_counts_like_numpy(blocks):
    """``_fold_rows`` with ``_LOWERED`` over listed blocks of 16 rows of a
    state whose last block is ragged: the min where listed, the state
    elsewhere, the dummy row ``INT32_MAX``, and the rows whose label fell,
    each once though the ragged block shares rows with the one before."""
    r = np.random.default_rng(len(blocks))
    n_pad, n_atoms, ub = 72, 69, 16
    state = r.integers(0, 100, size=n_pad).astype(np.int32)
    state[n_atoms:] = MAX
    buf = r.integers(0, 100, size=30).astype(np.int32)
    buf[-1] = MAX
    out_map = r.integers(0, 30, size=n_pad).astype(np.int32)
    out_map[n_atoms] = 29
    listed = {"all": [0, 1, 2, 3, 4], "some": [1, 3],
              "ragged_last": [3, 4]}[blocks]
    mark = np.isin(np.arange(5), listed)
    rows = eb._listed(jnp.asarray(out_map), mark, block_rows=ub)
    got, lowered = eb._fold_rows(
        jnp.asarray(state), jnp.asarray(buf), rows, jnp.int32(n_atoms),
        jnp.minimum, block_rows=ub, gain=eb._LOWERED)
    folded = np.zeros(n_pad, dtype=bool)
    for b in listed:
        start = min(b * ub, n_pad - ub)
        folded[start:start + ub] = True
    want = np.where(folded, np.minimum(state, buf[out_map]), state)
    want[n_atoms] = MAX
    np.testing.assert_array_equal(np.asarray(got), want)
    assert int(lowered) == int(np.count_nonzero(
        folded & (buf[out_map] < state)))


@pytest.mark.parametrize("state, want", [
    (np.zeros((5, 2), np.uint32), "or"),
    (np.zeros((5,), np.int32), "min"),
    (np.zeros((5, 2), np.int32), None),    # an int32 bitmap
    (np.zeros((5,), np.uint32), None),     # a flat vector of words
    (np.zeros((5, 1), np.int32), None),    # a lane-padded label
    (np.zeros((5, 2), np.uint8), None),
    (np.zeros((5,), np.float32), "sum"),   # PageRank's shares
    (np.zeros((5,), np.float16), None),
    (np.zeros((5, 2), np.float32), None),  # a float bitmap
    (np.zeros((5, 1), np.float32), None),  # a lane-padded rank
    (np.zeros((), np.float32), None),      # a scalar
])
def test_the_reduction_is_the_states_and_no_other_state_has_one(state,
                                                                want):
    """``_reduction`` is strict: the OR for ``(S, Kw)`` uint32 words, the
    min for ``(S,)`` int32 labels, the sum (identity 0.0) for ``(S,)``
    float32 shares, an error for anything else — so neither pyramid nor
    fold reduces a state by a reduction picked by default."""
    if want is None:
        with pytest.raises(TypeError, match="no reduction"):
            eb._reduction(jnp.asarray(state))
        with pytest.raises(TypeError, match="no reduction"):
            eb._apply_plan(jnp.asarray(state), (jnp.zeros(4, jnp.int32),),
                           (2,), 1, 4, False, scopes=("hg.t", "hg.t"))
    else:
        assert eb._reduction(jnp.asarray(state)) is {
            "or": eb._OR_WORDS, "min": eb._MIN_LABELS,
            "sum": eb._SUM_FLOATS}[want]
    assert (eb._SUM_FLOATS.combine, eb._SUM_FLOATS.identity) == (jnp.add,
                                                                 0.0)
