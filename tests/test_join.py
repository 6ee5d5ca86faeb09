"""hgjoin differential suite: device joins == host ``find_all`` truth.

The worst-case-optimal executor (``ops/join``) and the GHD-lite planner
(``join/planner``) are held to the exact host enumerator
(``join/host.host_join`` — find_all + satisfies, a deliberately separate
implementation path) on seeded random graphs across every supported
shape: triangles, paths, stars, typed variants, link-variable patterns,
empty results, duplicate-target links, pad-lane garbage, truncation
prefixes, and mid-ingest memtable visibility through the serving lane.
"""

from __future__ import annotations

import numpy as np
import pytest

from hypergraphdb_tpu import join
from hypergraphdb_tpu.join.ir import (
    ConjunctivePattern,
    JoinAtom,
    JoinUnsupported,
)
from hypergraphdb_tpu.ops.join import execute_join, neighbor_csr
from hypergraphdb_tpu.query import conditions as c
from hypergraphdb_tpu.query import dsl as q
from hypergraphdb_tpu.query.variables import var
from tests.conftest import make_random_hypergraph


def _build(g, seed=0, n_nodes=80, n_links=160):
    nodes, links = make_random_hypergraph(
        g, n_nodes=n_nodes, n_links=n_links, max_arity=4, seed=seed
    )
    return [int(n) for n in nodes], [int(x) for x in links]


def _device_rows(g, pattern, **kw):
    """Full device binding rows in the REQUEST's variable order.
    Exact-count shape policy by default — the truncation contract has
    its own test (:func:`test_truncation_honest_prefix`)."""
    kw.setdefault("var_pad_max", True)
    snap = g.snapshot()
    sig, consts = join.split_constants(pattern)
    plan = join.plan_join(snap, pattern, sig, consts)
    out = execute_join(snap, plan, np.asarray([consts], dtype=np.int32),
                       top_r=0, full=True, **kw)
    rows = out.full_bindings(0)
    perm = [plan.order.index(v) for v in pattern.vars]
    dev = sorted(tuple(int(x) for x in row[perm]) for row in rows)
    trunc = bool(np.asarray(out.trunc)[0])
    count = int(np.asarray(out.counts)[0])
    return dev, count, trunc


def _check(g, spec, distinct=True, **kw):
    p = join.extract_pattern(g, spec, distinct=distinct)
    truth = join.host_join(g, p)
    dev, count, trunc = _device_rows(g, p, **kw)
    assert not trunc
    assert dev == truth
    assert count == len(truth)
    return truth


# ---------------------------------------------------------------- shapes


SHAPES = {
    "triangle": lambda a: {
        "y": c.And(c.CoIncident(a), c.CoIncident(var("z"))),
        "z": c.CoIncident(a),
    },
    "path2": lambda a: {
        "y": c.CoIncident(a),
        "z": c.CoIncident(var("y")),
    },
    "star3": lambda a: {
        "y": c.CoIncident(a),
        "z": c.CoIncident(a),
        "w": c.CoIncident(a),
    },
    "link_var": lambda a: {
        "l": c.Incident(a),
        "y": c.Target(var("l")),
    },
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_join_matches_host_truth(graph, shape, seed):
    nodes, _ = _build(graph, seed=seed)
    _check(graph, SHAPES[shape](nodes[3 + seed]))


def test_host_join_reorders_spec_declaration_order(graph):
    """The spec declares y BEFORE its generator z is bound — the host
    enumerator must find a feasible binding order (the device planner
    reorders freely; the exact fallback has to keep up), and tuples
    still read in spec-declared variable order."""
    nodes, _ = _build(graph, seed=3)
    a = nodes[6]
    fwd = {"z": c.CoIncident(a), "y": c.CoIncident(var("z"))}
    rev = {"y": c.CoIncident(var("z")), "z": c.CoIncident(a)}
    t_fwd = join.host_join(graph, join.extract_pattern(graph, fwd))
    t_rev = join.host_join(graph, join.extract_pattern(graph, rev))
    assert t_fwd and {(y, z) for z, y in t_fwd} == set(t_rev)
    _check(graph, rev)  # device agrees on the awkward declaration too


def test_typed_variant_matches(graph):
    nodes, _ = _build(graph, seed=4)
    a = nodes[2]
    th = int(graph.get_type_handle_of(
        graph.add_link([a, nodes[9]], value="typed-probe")
    ))
    _check(graph, {"y": c.And(c.CoIncident(a), c.AtomType(th))})
    # typed on the non-anchor variable of a 2-path
    _check(graph, {
        "y": c.CoIncident(a),
        "z": c.And(c.CoIncident(var("y")), c.AtomType(th)),
    })


def test_empty_result_and_out_of_pattern_anchor(graph):
    _build(graph, seed=5)
    lone = int(graph.add_node("lonely"))
    truth = _check(graph, {"y": c.CoIncident(lone)})
    assert truth == []
    truth = _check(graph, {
        "y": c.CoIncident(lone), "z": c.CoIncident(var("y"))
    })
    assert truth == []


def test_duplicate_targets_dedupe(graph):
    """A link whose target tuple repeats an atom must not mint duplicate
    binding rows through the tgt-expansion path."""
    a, b = int(graph.add_node("a")), int(graph.add_node("b"))
    dup = int(graph.add_link([a, b, a], value="dup"))
    _check(graph, {"y": c.Target(dup)})                      # tgt const
    _check(graph, {"l": c.Incident(a), "y": c.Target(var("l"))})


def test_distinctness_is_enforced(graph):
    """distinct=True: no variable repeats another variable's binding or
    a pattern constant anywhere in a result tuple."""
    nodes, _ = _build(graph, seed=6)
    a = nodes[4]
    truth = _check(graph, SHAPES["star3"](a))
    for t in truth:
        assert len(set(t)) == len(t)
        assert a not in t


def test_pad_lane_garbage_is_inert(graph):
    """Bucket-padded lanes (n_real < K) must contribute nothing: zero
    counts, no truncation, and real lanes unchanged."""
    nodes, _ = _build(graph, seed=7)
    p = join.extract_pattern(graph, SHAPES["triangle"](nodes[5]))
    sig, consts = join.split_constants(p)
    snap = graph.snapshot()
    plan = join.plan_join(snap, p, sig, consts)
    K = 8
    cv = np.zeros((K, sig.n_consts), dtype=np.int32)
    cv[0] = consts
    # pad lanes deliberately carry garbage constants (stale anchors)
    cv[1:] = snap.num_atoms - 1
    out = execute_join(snap, plan, cv, top_r=16, n_real=1)
    counts = np.asarray(out.counts)
    trunc = np.asarray(out.trunc)
    truth = join.host_join(graph, p)
    assert int(counts[0]) == len(truth)
    assert (counts[1:] == 0).all()
    assert not trunc.any()


def test_truncation_honest_prefix(graph):
    """Caps small enough to overflow flag ``trunc`` and leave counts a
    LOWER bound whose downloaded rows are a subset of the truth — never
    fabricated rows, never a silent drop."""
    nodes, _ = _build(graph, seed=8)
    p = join.extract_pattern(graph, SHAPES["star3"](nodes[2]))
    truth = set(join.host_join(graph, p))
    assert truth  # the shape must actually overflow to test anything
    sig, consts = join.split_constants(p)
    snap = graph.snapshot()
    plan = join.plan_join(snap, p, sig, consts)
    out = execute_join(snap, plan, np.asarray([consts], dtype=np.int32),
                       top_r=0, full=True, row_cap=16, pad_cap=8)
    assert bool(np.asarray(out.trunc)[0])
    count = int(np.asarray(out.counts)[0])
    assert count <= len(truth)
    perm = [plan.order.index(v) for v in p.vars]
    rows = {tuple(int(x) for x in r[perm]) for r in out.full_bindings(0)}
    assert rows <= truth


def test_seeds_mode_global_count(graph):
    """Unanchored (whole-graph) triangle counting via seeds mode equals
    the numpy enumeration over the co-incidence CSR."""
    _build(graph, seed=9, n_nodes=50, n_links=110)
    p = join.extract_pattern(graph, {
        "x": c.CoIncident(var("y")),
        "y": c.And(c.CoIncident(var("x")), c.CoIncident(var("z"))),
        "z": c.CoIncident(var("x")),
    })
    snap = graph.snapshot()
    plan = join.plan_join(snap, p, seed_var="x")
    out = execute_join(
        snap, plan, np.zeros((1, 0), dtype=np.int32), top_r=0,
        count_only=True, seeds=np.arange(snap.num_atoms, dtype=np.int32),
        row_cap=1 << 18, var_pad_max=True,
    )
    assert not bool(np.asarray(out.trunc)[0])
    off, flat = neighbor_csr(snap)
    tri = sum(
        len(np.intersect1d(flat[off[int(y)]: off[int(y) + 1]],
                           flat[off[x]: off[x + 1]]))
        for x in range(snap.num_atoms)
        for y in flat[off[x]: off[x + 1]]
    )
    assert int(np.asarray(out.counts)[0]) == tri
    assert tri % 6 == 0  # every triangle appears once per ordering


def test_neighbor_csr_matches_satisfies(graph):
    """The materialized co-incidence CSR agrees with the CoIncident
    condition's own satisfies() on every pair of a small graph."""
    nodes, _ = _build(graph, seed=10, n_nodes=30, n_links=60)
    snap = graph.snapshot()
    off, flat = neighbor_csr(snap)
    for u in nodes[:12]:
        row = set(int(x) for x in flat[off[u]: off[u + 1]])
        assert u not in row  # irreflexive
        for v in nodes[:12]:
            expect = c.CoIncident(v).satisfies(graph, u)
            assert (v in row) == expect, (u, v)


# ---------------------------------------------------------------- planner


def test_planner_rejects_unanchored_and_disconnected(graph):
    _build(graph, seed=11)
    snap = graph.snapshot()
    floating = ConjunctivePattern(
        vars=("x", "y"), atoms=(JoinAtom("co", "x", "y"),)
    )
    with pytest.raises(JoinUnsupported):
        join.plan_join(snap, floating)  # no constant anchor
    disconnected = ConjunctivePattern(
        vars=("x", "y"), atoms=(JoinAtom("co", "x", 3),)
    )
    with pytest.raises(JoinUnsupported):
        join.plan_join(snap, disconnected)  # y unreachable


def test_extraction_rejects_out_of_vocabulary(graph):
    _build(graph, seed=12)
    with pytest.raises(JoinUnsupported):
        join.extract_pattern(graph, {"x": c.Or(c.CoIncident(3),
                                               c.CoIncident(4))})
    with pytest.raises(JoinUnsupported):
        join.extract_pattern(graph, {"x": c.BFS(3, max_distance=2)})


def test_extraction_dedupes_mirrored_atoms(graph):
    _build(graph, seed=13)
    p = join.extract_pattern(graph, {
        "x": c.CoIncident(var("y")),
        "y": c.And(c.CoIncident(var("x")), c.CoIncident(7)),
    })
    # co(x,y) and co(y,x) are ONE constraint
    assert len([a for a in p.atoms if a.key_is_var]) == 1


# ---------------------------------------------------------------- compiler


def test_single_var_pushdown_equals_host(graph, monkeypatch):
    """find_all(And(CoIncident, CoIncident)) — common neighbours — must
    answer identically with the join pushdown forced onto the device arm
    (at toy scale the cost model rightly prefers host, so both gates are
    pinned open) and with it off."""
    from hypergraphdb_tpu.join import planner as jp

    nodes, _ = _build(graph, seed=14)
    a, b = nodes[3], nodes[8]
    cond = q.and_(q.co_incident(a), q.co_incident(b))
    host = sorted(int(h) for h in graph.find_all(cond))
    monkeypatch.setattr(graph.config.query, "device_min_batch", 0)
    monkeypatch.setattr(jp, "host_cost_bytes",
                        lambda *_: float("inf"))
    dev = sorted(int(h) for h in graph.find_all(cond))
    assert dev == host
    assert graph.metrics.counters.get("query.join.device", 0) >= 1


def test_pushdown_with_memtable_falls_back_exact(graph):
    nodes, _ = _build(graph, seed=15)
    a, b = nodes[2], nodes[6]
    graph.snapshot()  # pin a base, then mutate past it
    fresh = int(graph.add_link([a, b], value="fresh"))
    cond = q.and_(q.co_incident(a), q.co_incident(b))
    old = graph.config.query.device_min_batch
    try:
        graph.config.query.device_min_batch = 0
        got = sorted(int(h) for h in graph.find_all(cond))
    finally:
        graph.config.query.device_min_batch = old
    # ground truth by direct satisfies() over every atom — the device
    # base predates the fresh link, so agreement here proves the
    # memtable correction (or exact fallback) engaged
    expect = sorted(
        int(h) for h in graph.atoms()
        if c.CoIncident(a).satisfies(graph, h)
        and c.CoIncident(b).satisfies(graph, h)
    )
    assert got == expect
    assert fresh not in got  # the link shares no LINK with a (it IS one)


# ---------------------------------------------------------------- serving


def _serve(g, **kw):
    from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

    kw.setdefault("buckets", (4, 16))
    kw.setdefault("max_linger_s", 0.001)
    kw.setdefault("top_r", 128)
    return ServeRuntime(g, ServeConfig(**kw))


def test_serve_join_batch_differential(graph):
    """A same-signature batch of anchored triangles through the serving
    lane: every lane equals its host truth, device-served."""
    nodes, _ = _build(graph, seed=16)
    rt = _serve(graph)
    try:
        futs = [(x, rt.submit_join(SHAPES["triangle"](x)))
                for x in nodes[:8]]
        saw_device = False
        for x, f in futs:
            res = f.result(timeout=60)
            truth = join.host_join(
                graph, join.extract_pattern(graph, SHAPES["triangle"](x))
            )
            assert res.count == len(truth)
            got = sorted(tuple(int(v) for v in row) for row in res.tuples)
            assert got == (truth[:128] if res.truncated else truth)
            saw_device = saw_device or res.served_by == "device"
        assert saw_device
    finally:
        rt.close()


def test_serve_join_mid_ingest_partial_correction(graph):
    """A link added after the base pack must be visible. Join engine v2
    (ROADMAP 2d): a SMALL pure-add dirty set no longer re-routes the
    batch to host — the lane stays device-served and collect merges the
    host-enumerated tuples touching the dirty atoms, counted in
    ``serve.join.partial_corrections``."""
    nodes, _ = _build(graph, seed=17)
    a = nodes[5]
    rt = _serve(graph)
    try:
        rt.submit_join(SHAPES["path2"](a)).result(timeout=60)  # pin base
        far = int(graph.add_node("far"))
        graph.add_link([a, far], value="mid-ingest")
        res = rt.submit_join({"y": c.CoIncident(a)}).result(timeout=60)
        assert res.served_by == "device"
        got = {int(r[0]) for r in res.tuples}
        assert far in got
        truth = join.host_join(
            graph, join.extract_pattern(graph, {"y": c.CoIncident(a)})
        )
        assert res.count == len(truth)
        assert rt.stats.join_partial_corrections >= 1
    finally:
        rt.close()


def test_serve_join_mid_ingest_big_dirty_set_serves_host(graph):
    """Past ``join_dirty_max`` touched atoms (here: 0 — the partial
    path disabled) the lane keeps PR 10's exact-at-collect rule: the
    whole batch re-routes to host while the memtable is dirty."""
    nodes, _ = _build(graph, seed=17)
    a = nodes[5]
    rt = _serve(graph, join_dirty_max=0)
    try:
        rt.submit_join(SHAPES["path2"](a)).result(timeout=60)  # pin base
        far = int(graph.add_node("far"))
        graph.add_link([a, far], value="mid-ingest")
        res = rt.submit_join({"y": c.CoIncident(a)}).result(timeout=60)
        assert res.served_by == "host"
        assert far in {int(r[0]) for r in res.tuples}
        assert rt.stats.join_partial_corrections == 0
    finally:
        rt.close()


def test_serve_join_mid_ingest_tombstone_serves_host(graph):
    """Tombstones are never partially correctable (a vanished link may
    have been a result's only witness): the batch takes the exact host
    path even under a tiny dirty set."""
    nodes, links = _build(graph, seed=22)
    a = nodes[4]
    rt = _serve(graph)
    try:
        rt.submit_join(SHAPES["path2"](a)).result(timeout=60)  # pin base
        graph.remove(links[0])
        res = rt.submit_join({"y": c.CoIncident(a)}).result(timeout=60)
        assert res.served_by == "host"
        truth = join.host_join(
            graph, join.extract_pattern(graph, {"y": c.CoIncident(a)})
        )
        assert res.count == len(truth)
    finally:
        rt.close()


def test_serve_join_result_window_truncation(graph):
    """count exact + ascending prefix when the binding set outgrows
    top_r — the compact-window contract, join edition."""
    nodes, _ = _build(graph, seed=18)
    a = nodes[1]
    truth = join.host_join(
        graph, join.extract_pattern(graph, SHAPES["star3"](a))
    )
    assert len(truth) > 4
    rt = _serve(graph, top_r=4)
    try:
        res = rt.submit_join(SHAPES["star3"](a)).result(timeout=60)
        assert res.truncated and res.count == len(truth)
        got = [tuple(int(v) for v in row) for row in res.tuples]
        assert got == truth[:4]
    finally:
        rt.close()


def test_serve_join_stale_anchor_exact(graph):
    """An anchor newer than the pinned base must still answer exactly.
    v2: within the base's padded id space the anchor's BASE rows are
    empty and the per-lane correction supplies every memtable tuple —
    device-served, exact; with the partial path disabled it keeps PR
    10's exact host route."""
    nodes, _ = _build(graph, seed=19)
    for dirty_max, path in ((16, "device"), (0, "host")):
        rt = _serve(graph, join_dirty_max=dirty_max)
        try:
            rt.submit_join(SHAPES["path2"](nodes[0])).result(timeout=60)
            fresh_n = int(graph.add_node(f"fresh-anchor-{dirty_max}"))
            graph.add_link([fresh_n, nodes[2]], value="fresh-link")
            res = rt.submit_join({"y": c.CoIncident(fresh_n)}).result(
                timeout=60
            )
            truth = join.host_join(
                graph,
                join.extract_pattern(graph, {"y": c.CoIncident(fresh_n)}),
            )
            assert res.count == len(truth) > 0
            got = sorted(int(r[0]) for r in res.tuples)
            assert got == [t[0] for t in truth]
            if rt.executor.mgr.compactions == 1:
                # no compaction raced the submit: the routing verdict is
                # deterministic and pinned per config
                assert res.served_by == path
        finally:
            rt.close()


def test_factorize_failure_never_poisons_plan_cache(graph, monkeypatch):
    """An over-budget co relation makes the factorized build raise —
    that must NOT demote a co-FREE signature (which the pair-budget
    guard rightly let through) to the host path: the plan survives and
    the lane serves device over the flat CSRs (review regression)."""
    from hypergraphdb_tpu.ops import join as oj

    nodes, _ = _build(graph, seed=40)
    a = nodes[2]
    monkeypatch.setattr(oj, "NBR_MAX_PAIRS", 1)
    spec = {"l": c.Incident(a), "y": c.Target(var("l"))}  # no co atoms
    truth = join.host_join(graph, join.extract_pattern(graph, spec))
    assert truth
    rt = _serve(graph)   # join_factorized defaults on
    try:
        res = rt.submit_join(spec).result(timeout=60)
        assert res.served_by == "device"
        assert res.count == len(truth)
    finally:
        rt.close()


def test_nbr_pair_budget_declines_to_host(graph, monkeypatch):
    """A snapshot whose co-incidence relation would blow the pair
    budget never builds it: the serve lane declines BEFORE launch and
    the one-shot pushdown falls back — both still exact via host."""
    from hypergraphdb_tpu.join import planner as jp
    from hypergraphdb_tpu.ops import join as oj

    nodes, _ = _build(graph, seed=21)
    monkeypatch.setattr(oj, "NBR_MAX_PAIRS", 1)
    a = nodes[3]
    spec = {"y": c.CoIncident(a)}
    truth = join.host_join(graph, join.extract_pattern(graph, spec))
    rt = _serve(graph)
    try:
        res = rt.submit_join(spec).result(timeout=60)
        assert res.served_by == "host"
        assert res.count == len(truth)
    finally:
        rt.close()
    # one-shot: the executor raises JoinUnsupported inside run(), the
    # classic host plan answers (And pushdown — a bare CoIncident is a
    # NeighborsPlan leaf and never reaches the device arm)
    monkeypatch.setattr(graph.config.query, "device_min_batch", 0)
    monkeypatch.setattr(jp, "host_cost_bytes", lambda *_: float("inf"))
    b = nodes[8]
    cond = q.and_(q.co_incident(a), q.co_incident(b))
    got = sorted(int(h) for h in graph.find_all(cond))
    expect = sorted(
        int(h) for h in graph.atoms()
        if c.CoIncident(a).satisfies(graph, h)
        and c.CoIncident(b).satisfies(graph, h)
    )
    assert got == expect
    assert graph.metrics.counters.get("query.join.host", 0) >= 1


# ------------------------------------------------- join engine v2 suites


def _build_hub(g, seed=0, hub_links=70):
    """A random graph plus one deliberate HUB: a node sharing a link
    with most of the population, so its co row (~70 distinct
    neighbours) dwarfs every tail row (base-graph co rows stay ≤ ~30)."""
    nodes, links = _build(g, seed=seed)
    hub = nodes[0]
    for i in range(hub_links):
        g.add_link([hub, nodes[1 + i % (len(nodes) - 1)]],
                   value=f"hub-{i}")
    return hub, nodes


@pytest.mark.parametrize("shape", ["path2", "triangle"])
def test_degree_split_hub_anchor_matches_host(graph, shape):
    """Hub-anchored patterns through the degree-split executor: the
    dense-frontier chain serves the hub exactly (no width truncation)
    where the PR-10 padded path would truncate under the same pad cap."""
    hub, _ = _build_hub(graph, seed=30)
    p = join.extract_pattern(graph, SHAPES[shape](hub))
    truth = join.host_join(graph, p)
    assert truth
    snap = graph.snapshot()
    sig, consts = join.split_constants(p)
    plan = join.plan_join(snap, p, sig, consts)
    # pad_cap sits BETWEEN the tail row widths (base-graph co rows stay
    # under it) and the hub row width (well over it): the flat executor
    # must truncate the hub expansion, the split must not
    kw = dict(top_r=0, full=True, pad_cap=40, row_cap=1 << 16)
    out = execute_join(snap, plan, np.asarray([consts], dtype=np.int32),
                       hub_threshold=40, **kw)
    assert out.hub_lanes == 1
    assert not bool(np.asarray(out.trunc)[0])
    perm = [plan.order.index(v) for v in p.vars]
    dev = sorted(tuple(int(x) for x in r[perm])
                 for r in out.full_bindings(0))
    assert dev == truth
    assert int(np.asarray(out.counts)[0]) == len(truth)
    # the PR-10 executor under the same caps: the hub row overflows the
    # pad and the lane truncates (host re-route in production)
    old = execute_join(snap, plan, np.asarray([consts], dtype=np.int32),
                       hub_split=False, **kw)
    assert old.hub_lanes == 0
    assert bool(np.asarray(old.trunc)[0])


def test_degree_split_mixed_batch(graph):
    """One batch mixing hub and tail anchors: tail lanes keep the
    padded fast path (pads priced from tail widths only), the hub lane
    rides the dense-frontier chain, and every lane equals host truth."""
    from hypergraphdb_tpu.ops.join import neighbor_csr

    hub, nodes = _build_hub(graph, seed=31)
    snap = graph.snapshot()
    off, _ = neighbor_csr(snap)
    w = np.diff(off.astype(np.int64))[: snap.num_atoms]
    tails = [n for n in nodes[1:] if 2 <= w[n] <= 8][:7]
    assert tails
    anchors = [hub] + tails
    p0 = join.extract_pattern(graph, SHAPES["path2"](anchors[0]))
    sig, _ = join.split_constants(p0)
    plan = join.plan_join(snap, p0, sig,
                          join.split_constants(p0)[1])
    consts = np.asarray([[a] for a in anchors], dtype=np.int32)
    mask = join.hub_lane_mask(snap, plan.steps, consts, threshold=8)
    assert mask[0] and not mask[1:].any()
    out = execute_join(snap, plan, consts, top_r=0, count_only=True,
                       hub_threshold=8, var_pad_max=True,
                       row_cap=1 << 16)
    assert out.hub_lanes == 1
    counts = np.asarray(out.counts)
    assert not np.asarray(out.trunc).any()
    for i, a in enumerate(anchors):
        truth = join.host_join(
            graph, join.extract_pattern(graph, SHAPES["path2"](a))
        )
        assert int(counts[i]) == len(truth), (i, a)


def test_bushy_star_of_stars_matches_host(graph):
    """Star-of-stars (two independently-anchored 2-var components):
    auto planning goes bushy, and bushy == forced-left-deep == host
    truth, including cross-component distinctness."""
    from hypergraphdb_tpu.join.planner import BushyJoinPlan

    nodes, _ = _build(graph, seed=32)
    a, b = nodes[3], nodes[8]
    spec = {
        "y": c.CoIncident(a), "z": c.CoIncident(var("y")),
        "u": c.CoIncident(b), "w": c.CoIncident(var("u")),
    }
    p = join.extract_pattern(graph, spec)
    truth = join.host_join(graph, p)
    snap = graph.snapshot()
    sig, consts = join.split_constants(p)
    plan = join.plan_join(snap, p, sig, consts)        # auto
    assert isinstance(plan, BushyJoinPlan)
    assert "bushy[" in plan.describe()
    cv = np.asarray([consts], dtype=np.int32)
    out = execute_join(snap, plan, cv, top_r=0, full=True,
                       var_pad_max=True, row_cap=1 << 18)
    assert not bool(np.asarray(out.trunc)[0])
    perm = [plan.order.index(v) for v in p.vars]
    dev = sorted(tuple(int(x) for x in r[perm])
                 for r in out.full_bindings(0))
    assert dev == truth
    assert int(np.asarray(out.counts)[0]) == len(truth)
    # forced left-deep agrees
    flat = join.plan_join(snap, p, sig, consts, bushy=False)
    assert not isinstance(flat, BushyJoinPlan)
    out2 = execute_join(snap, flat, cv, top_r=0, count_only=True,
                        var_pad_max=True, row_cap=1 << 18)
    assert not bool(np.asarray(out2.trunc)[0])
    assert int(np.asarray(out2.counts)[0]) == len(truth)
    for t in truth:
        assert len(set(t)) == len(t)  # cross-bag distinctness held


def test_bushy_auto_policy(graph):
    """Auto stays left-deep when every component is a singleton (plain
    star3 — a bag would buy nothing) and for single-component shapes;
    ``bushy=True`` forces the split."""
    from hypergraphdb_tpu.join.planner import BushyJoinPlan

    nodes, _ = _build(graph, seed=33)
    a = nodes[2]
    snap = graph.snapshot()
    star = join.extract_pattern(graph, SHAPES["star3"](a))
    assert not isinstance(join.plan_join(snap, star), BushyJoinPlan)
    assert isinstance(join.plan_join(snap, star, bushy=True),
                      BushyJoinPlan)
    tri = join.extract_pattern(graph, SHAPES["triangle"](a))
    assert not isinstance(join.plan_join(snap, tri, bushy=True),
                          BushyJoinPlan)  # one component: nothing to bag


def test_bushy_forced_star3_matches_host(graph):
    """Bushy with singleton bags (forced on star3) still answers
    exactly — the fold enforces the pairwise distinctness the left-deep
    chain got from its step masks."""
    nodes, _ = _build(graph, seed=34)
    a = nodes[5]
    p = join.extract_pattern(graph, SHAPES["star3"](a))
    truth = join.host_join(graph, p)
    snap = graph.snapshot()
    sig, consts = join.split_constants(p)
    plan = join.plan_join(snap, p, sig, consts, bushy=True)
    out = execute_join(snap, plan, np.asarray([consts], dtype=np.int32),
                       top_r=0, full=True, var_pad_max=True,
                       row_cap=1 << 18)
    assert not bool(np.asarray(out.trunc)[0])
    perm = [plan.order.index(v) for v in p.vars]
    dev = sorted(tuple(int(x) for x in r[perm])
                 for r in out.full_bindings(0))
    assert dev == truth


def test_bushy_truncation_honest(graph):
    """Bushy chains and folds under tiny caps flag ``trunc`` with a
    count that stays a lower bound and rows a subset of truth — the
    PR-10 honesty contract, bag edition."""
    nodes, _ = _build(graph, seed=35)
    a, b = nodes[1], nodes[6]
    spec = {
        "y": c.CoIncident(a), "z": c.CoIncident(var("y")),
        "u": c.CoIncident(b), "w": c.CoIncident(var("u")),
    }
    p = join.extract_pattern(graph, spec)
    truth = set(join.host_join(graph, p))
    assert truth
    snap = graph.snapshot()
    sig, consts = join.split_constants(p)
    plan = join.plan_join(snap, p, sig, consts, bushy=True)
    out = execute_join(snap, plan, np.asarray([consts], dtype=np.int32),
                       top_r=0, full=True, row_cap=32, pad_cap=8)
    assert bool(np.asarray(out.trunc)[0])
    assert int(np.asarray(out.counts)[0]) <= len(truth)
    perm = [plan.order.index(v) for v in p.vars]
    rows = {tuple(int(x) for x in r[perm])
            for r in out.full_bindings(0)}
    assert rows <= truth


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_factorized_matches_flat(graph, shape):
    """The prefix-grouped (trie) relation encoding answers every shape
    identically to the flat CSRs — closed co rows re-irreflexed, tgt
    tuples grouped exactly."""
    nodes, _ = _build(graph, seed=36)
    a = nodes[4]
    p = join.extract_pattern(graph, SHAPES[shape](a))
    truth = join.host_join(graph, p)
    dev, count, trunc = _device_rows(graph, p, factorized=True)
    assert not trunc
    assert dev == truth
    assert count == len(truth)


def test_factorized_grouping_shares_link_rows(graph):
    """Members of a single shared link carry IDENTICAL closed co rows —
    one stored group; the encoding's saving is measurable and the
    grouped payload is never larger than the flat one."""
    from hypergraphdb_tpu.ops.join import factorized_relations

    a = int(graph.add_node("a"))
    b = int(graph.add_node("b"))
    d = int(graph.add_node("d"))
    graph.add_link([a, b, d], value="triple")
    fr = factorized_relations(graph.snapshot())["co"]
    ga, gb, gd = fr.group_of[a], fr.group_of[b], fr.group_of[d]
    assert ga == gb == gd != 0
    row = fr.flat[fr.offsets[ga]: fr.offsets[ga + 1]]
    assert sorted(int(x) for x in row) == sorted([a, b, d])
    assert fr.entries <= fr.entries_flat
    assert fr.closed


def test_host_join_touching_equivalence(graph):
    """``host_join_touching`` with the full atom set reproduces
    ``host_join`` exactly, and with a restricted set returns precisely
    the truth tuples intersecting it — the per-lane correction's
    soundness contract."""
    nodes, _ = _build(graph, seed=37)
    a, b = nodes[2], nodes[9]
    spec = {
        "y": c.CoIncident(a), "z": c.CoIncident(var("y")),
        "u": c.CoIncident(b), "w": c.CoIncident(var("u")),
    }
    p = join.extract_pattern(graph, spec)
    truth = join.host_join(graph, p)
    everything = [int(h) for h in graph.atoms()]
    assert join.host_join_touching(graph, p, everything) == truth
    if truth:
        probe = set(truth[0][:1])
        got = join.host_join_touching(graph, p, probe)
        expect = sorted(t for t in truth if probe & set(t))
        assert got == expect


def test_serve_join_hub_dispatch_counter(graph):
    """A hub-anchored join through the serving lane dispatches the hub
    lane on DEVICE (``serve.join.hub_dispatches`` moves) and equals the
    host truth — the lane PR 10 re-routed to host."""
    hub, _ = _build_hub(graph, seed=38)
    rt = _serve(graph, join_hub_threshold=8)
    try:
        res = rt.submit_join(SHAPES["path2"](hub)).result(timeout=60)
        truth = join.host_join(
            graph, join.extract_pattern(graph, SHAPES["path2"](hub))
        )
        assert res.served_by == "device"
        assert res.count == len(truth)
        got = sorted(tuple(int(v) for v in row) for row in res.tuples)
        assert got == (truth[:128] if res.truncated else truth)
        assert rt.stats.join_hub_dispatches > 0
    finally:
        rt.close()


def test_serve_join_bushy_signature_batch(graph):
    """A same-signature batch of star-of-stars requests through the
    serving lane (bushy plans under the hood): every lane equals its
    host truth."""
    nodes, _ = _build(graph, seed=39)
    rt = _serve(graph)
    try:
        spec_of = lambda x, y: {             # noqa: E731 - test-local
            "p": c.CoIncident(x), "q": c.CoIncident(var("p")),
            "r": c.CoIncident(y), "s": c.CoIncident(var("r")),
        }
        pairs = [(nodes[i], nodes[i + 4]) for i in range(4)]
        futs = [(x, y, rt.submit_join(spec_of(x, y)))
                for x, y in pairs]
        for x, y, f in futs:
            # (a hang guard, not a bound: the bushy plan's compiles take
            # ~10 s alone and have passed 60 s beside five busy workers)
            res = f.result(timeout=300)
            truth = join.host_join(
                graph, join.extract_pattern(graph, spec_of(x, y))
            )
            assert res.count == len(truth), (x, y)
            got = sorted(tuple(int(v) for v in row)
                         for row in res.tuples)
            assert got == (truth[:128] if res.truncated else truth)
    finally:
        rt.close()


def test_bridge_routes_coincident_conditions_to_join(graph):
    from hypergraphdb_tpu.query.bridge import to_join_request, to_request
    from hypergraphdb_tpu.serve.types import JoinRequest, Unservable

    nodes, _ = _build(graph, seed=20)
    a, b = nodes[0], nodes[1]
    req = to_request(graph, q.and_(q.co_incident(a), q.co_incident(b)))
    assert isinstance(req, JoinRequest)
    assert req.consts == (a, b)
    # single-variable CONDITIONS carry find_all semantics: no implicit
    # distinct-from-anchors (Incident(a) admits a self-targeting a)
    assert req.sig.distinct is False
    req2 = to_request(graph, q.co_incident(a))
    assert isinstance(req2, JoinRequest)
    # same shape, different anchors → same signature (one batch key)
    assert to_request(graph, q.co_incident(b)).batch_key == req2.batch_key
    with pytest.raises(Unservable):
        to_join_request(graph, {
            "x": c.CoIncident(var("y")), "y": c.CoIncident(var("x")),
        })  # no constant anchor
