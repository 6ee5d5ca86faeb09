"""Device value-predicate pushdown (VERDICT r2 item 3): conjunctions of
``Incident + AtomValue[range] (+ AtomType)`` must run on the device value
ranks, never through per-handle host ``satisfies`` for fixed-width kinds
(the reference's value-indexed conjunctions, ``cond2qry/AndToQuery.java:
102-306``)."""

import numpy as np
import pytest

from hypergraphdb_tpu import HyperGraph
from hypergraphdb_tpu.query import dsl as hg
from hypergraphdb_tpu.query import conditions as c
from hypergraphdb_tpu.query.compiler import (
    DeviceValueConjPlan,
    compile_query,
)


@pytest.fixture()
def valued_db():
    g = HyperGraph()
    g.config.query.device_min_batch = 0  # force the device path at test scale
    nodes = [g.add(f"n{i}") for i in range(24)]
    rels = []
    rng = np.random.default_rng(5)
    for i in range(200):
        a, b = rng.choice(24, size=2, replace=False)
        rels.append(
            g.add_link((nodes[a], nodes[b]), value=int(rng.integers(0, 50)))
        )
    yield g, nodes, rels
    g.close()


def _brute(g, rels, anchor, pred):
    out = []
    for l in rels:
        atom = g.get(l)
        if int(anchor) in [int(t) for t in atom.targets] and pred(atom.value):
            out.append(int(l))
    return sorted(out)


OPS = {
    "eq": lambda v, k: v == k,
    "lt": lambda v, k: v < k,
    "lte": lambda v, k: v <= k,
    "gt": lambda v, k: v > k,
    "gte": lambda v, k: v >= k,
}


@pytest.mark.parametrize("op", list(OPS))
def test_int_value_pushdown_differential(valued_db, op):
    g, nodes, rels = valued_db
    for anchor in nodes[:6]:
        cond = hg.and_(
            hg.type_("int"), hg.value(25, op), hg.incident(anchor)
        )
        q = compile_query(g, cond)
        assert isinstance(q.plan, DeviceValueConjPlan), q.analyze()
        got = sorted(g.find_all(cond))
        want = _brute(g, rels, anchor, lambda v: OPS[op](v, 25))
        assert got == want, (op, int(anchor))


def test_int_pushdown_never_calls_satisfies(valued_db, monkeypatch):
    """Fixed-width kinds are tie-free on device: zero host satisfies()."""
    g, nodes, rels = valued_db
    calls = []
    orig = c.AtomValue.satisfies
    monkeypatch.setattr(
        c.AtomValue, "satisfies",
        lambda self, graph, h: calls.append(h) or orig(self, graph, h),
    )
    cond = hg.and_(hg.value(25, "lt"), hg.incident(nodes[0]))
    got = sorted(g.find_all(cond))
    assert calls == []
    want = _brute(g, rels, nodes[0], lambda v: v < 25)
    assert got == want


def test_string_value_ties_verified_host_side():
    """Variable-width kinds: rank ties (shared 8-byte prefix) must be
    resolved exactly by host verification."""
    g = HyperGraph()
    g.config.query.device_min_batch = 0
    n = g.add("anchor")
    # all values share an 8-byte prefix → every rank comparison ties
    vals = ["prefix__a", "prefix__b", "prefix__c", "prefix__"]
    links = {v: g.add_link((n,), value=v) for v in vals}
    got = sorted(g.find_all(hg.and_(hg.value("prefix__b", "lte"), hg.incident(n))))
    want = sorted(int(links[v]) for v in vals if v <= "prefix__b")
    assert got == want
    got_eq = sorted(g.find_all(hg.and_(hg.value("prefix__b", "eq"), hg.incident(n))))
    assert got_eq == [int(links["prefix__b"])]
    g.close()


def test_pushdown_shape_rejected_with_extra_clauses(valued_db):
    """A conjunction with clauses outside the pushdown shape must take the
    generic planner (correctness first)."""
    g, nodes, rels = valued_db
    cond = hg.and_(
        hg.value(25, "lt"), hg.incident(nodes[0]), c.Arity(2, "eq")
    )
    q = compile_query(g, cond)
    assert not isinstance(q.plan, DeviceValueConjPlan)
    got = sorted(g.find_all(cond))
    want = _brute(g, rels, nodes[0], lambda v: v < 25)  # all rels arity 2
    assert got == want


def test_typed_value_expands_into_pushdown(valued_db):
    g, nodes, rels = valued_db
    cond = hg.and_(
        c.TypedValue(25, "int", "gte"), hg.incident(nodes[1])
    )
    q = compile_query(g, cond)
    assert isinstance(q.plan, DeviceValueConjPlan), q.analyze()
    got = sorted(g.find_all(cond))
    want = _brute(g, rels, nodes[1], lambda v: v >= 25)
    assert got == want


# --------------------------------------------------------------------------
# fused range windows — VERDICT r4 item 4
# --------------------------------------------------------------------------


def test_range_window_fuses_to_one_plan(valued_db):
    """And(incident, gte lo, lt hi) compiles to ONE DeviceValueConjPlan with
    both bounds (a single fused launch), not a generic intersection."""
    g, nodes, rels = valued_db
    cond = hg.and_(
        hg.value(10, "gte"), hg.value(30, "lt"), hg.incident(nodes[0])
    )
    q = compile_query(g, cond)
    assert isinstance(q.plan, DeviceValueConjPlan), q.analyze()
    assert q.plan.op2 is not None
    assert ".." in q.plan.describe()


@pytest.mark.parametrize("lo_op,hi_op", [
    ("gte", "lt"), ("gt", "lte"), ("gte", "lte"), ("gt", "lt"),
])
def test_range_window_differential(valued_db, lo_op, hi_op):
    g, nodes, rels = valued_db
    lo, hi = 10, 30
    for anchor in nodes[:6]:
        cond = hg.and_(
            hg.value(lo, lo_op), hg.value(hi, hi_op), hg.incident(anchor)
        )
        got = sorted(g.find_all(cond))
        want = _brute(
            g, rels, anchor,
            lambda v: OPS[lo_op](v, lo) and OPS[hi_op](v, hi),
        )
        assert got == want, (lo_op, hi_op, int(anchor))


def test_range_kernel_matches_two_single_probes(valued_db):
    """incident_value_range must agree bit-for-bit with the AND of two
    incident_value_pattern launches over the same window."""
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops.setops import (
        _bucket,
        ell_targets,
        incident_value_pattern,
        incident_value_range,
    )
    from hypergraphdb_tpu.utils.ordered_bytes import rank64

    g, nodes, rels = valued_db
    snap = g.snapshot()
    ell = ell_targets(snap)
    vt = g.typesystem.infer(11)
    key_lo, key_hi = vt.to_key(11), vt.to_key(37)
    r_lo, r_hi = rank64(key_lo[1:]), rank64(key_hi[1:])
    kind = key_lo[0]

    anchors = np.asarray([[int(nodes[0])], [int(nodes[3])]], dtype=np.int32)
    lens = snap.inc_offsets[anchors[:, 0] + 1] - snap.inc_offsets[anchors[:, 0]]
    pad = _bucket(int(lens.max()))
    args = (snap.device, ell, jnp.asarray(anchors), pad, jnp.uint8(kind))

    _, keep_lo, _ = incident_value_pattern(
        *args, jnp.uint32(r_lo >> 32), jnp.uint32(r_lo & 0xFFFFFFFF),
        "gte", True, None,
    )
    _, keep_hi, _ = incident_value_pattern(
        *args, jnp.uint32(r_hi >> 32), jnp.uint32(r_hi & 0xFFFFFFFF),
        "lt", True, None,
    )
    rows, keep, tie, counts = incident_value_range(
        *args,
        jnp.uint32(r_lo >> 32), jnp.uint32(r_lo & 0xFFFFFFFF),
        jnp.uint32(r_hi >> 32), jnp.uint32(r_hi & 0xFFFFFFFF),
        "gte", "lt", True, None,
    )
    np.testing.assert_array_equal(
        np.asarray(keep), np.asarray(keep_lo & keep_hi)
    )
    np.testing.assert_array_equal(
        np.asarray(counts), np.asarray((keep_lo & keep_hi).sum(axis=1))
    )
    assert not np.asarray(tie).any()


def test_string_range_ties_verified_host_side():
    """Variable-width kinds: survivors strictly inside the window are
    definite; bound ties go through host verification — results must still
    be exact."""
    g = HyperGraph()
    g.config.query.device_min_batch = 0
    a = g.add("anchor")
    words = ["apple", "banana", "cherry", "damson", "elder", "fig"]
    links = {w: g.add_link((a,), value=w) for w in words}
    cond = hg.and_(
        hg.value("banana", "gte"), hg.value("elder", "lt"), hg.incident(a)
    )
    q = compile_query(g, cond)
    assert isinstance(q.plan, DeviceValueConjPlan) and q.plan.op2 is not None
    got = sorted(g.find_all(cond))
    want = sorted(
        int(links[w]) for w in words if "banana" <= w < "elder"
    )
    assert got == want
    g.close()


def test_value_columns_row_pack_matches_default(valued_db):
    """The optional (N+1, 4) row-packed rank layout (ROADMAP queue 3 item 7)
    must agree bit-for-bit with the default column gathers."""
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops.setops import (
        _bucket,
        ell_targets,
        incident_value_range,
        value_columns,
    )
    from hypergraphdb_tpu.utils.ordered_bytes import rank64

    g, nodes, rels = valued_db
    snap = g.snapshot()
    ell = ell_targets(snap)
    vcols = value_columns(snap)
    vt = g.typesystem.infer(11)
    r_lo = rank64(vt.to_key(11)[1:])
    r_hi = rank64(vt.to_key(37)[1:])
    kind = vt.to_key(11)[0]
    anchors = np.asarray([[int(nodes[0])], [int(nodes[4])]], dtype=np.int32)
    lens = snap.inc_offsets[anchors[:, 0] + 1] - snap.inc_offsets[anchors[:, 0]]
    pad = _bucket(int(lens.max()))
    args = (
        snap.device, ell, jnp.asarray(anchors), pad, jnp.uint8(kind),
        jnp.uint32(r_lo >> 32), jnp.uint32(r_lo & 0xFFFFFFFF),
        jnp.uint32(r_hi >> 32), jnp.uint32(r_hi & 0xFFFFFFFF),
        "gte", "lt", True, None,
    )
    _, keep0, _, counts0 = incident_value_range(*args)
    _, keep1, _, counts1 = incident_value_range(*args, vcols)
    np.testing.assert_array_equal(np.asarray(keep0), np.asarray(keep1))
    np.testing.assert_array_equal(np.asarray(counts0), np.asarray(counts1))
