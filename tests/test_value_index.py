"""hgindex differential tests: the device value-index lanes == host truth.

The range serve lane's contract is the serving contract everywhere else:
coalescing, padding, and the sorted-column machinery are INVISIBLE — a
batched range/ordered/top-k request returns exactly what an exact host
scan of the by-value index returns, across pad-adjacent lanes, duplicate
bounds, empty windows, mid-ingest delta/tombstone visibility, and
truncation prefixes. Runs the REAL DeviceExecutor under
``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import numpy as np
import pytest

from hypergraphdb_tpu.query import conditions as c
from hypergraphdb_tpu.query import dsl
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime
from hypergraphdb_tpu.serve.types import RangeRequest, Unservable


def _runtime(g, bucket=64, **kw):
    kw.setdefault("top_r", 256)
    cfg = ServeConfig(buckets=(bucket,), manual=True, max_linger_s=0.0,
                      **kw)
    return ServeRuntime(g, cfg)


def _drain(rt):
    while rt.step(drain=True):
        pass


def _int_graph(g, n=40, dup_every=0):
    """Nodes with int values 0..n-1 (``dup_every`` > 0 repeats every
    k-th value — duplicate ranks) plus typed links carrying int values
    100..; returns (node_handles, link_handles, link_type_handle)."""
    nodes = []
    for i in range(n):
        v = i - (i % dup_every) if dup_every else i
        nodes.append(int(g.add(v)))
    links = [int(g.add_link([nodes[i], nodes[(i + 1) % n]], value=100 + i))
             for i in range(n // 2)]
    return nodes, links, int(g.get_type_handle_of(links[0]))


def _host_truth(g, lo=None, hi=None, lo_op="gte", hi_op="lte",
                type_handle=None, anchor=None, desc=False):
    """The oracle: every live atom satisfying the predicate, in value
    order (ascending key; ``desc`` flips the key order, gid-ascending
    within ties either way — the kernel's complemented-rank order)."""
    from hypergraphdb_tpu.storage.value_index import value_key_of

    clauses = []
    if lo is not None:
        clauses.append(c.AtomValue(lo, lo_op))
    if hi is not None:
        clauses.append(c.AtomValue(hi, hi_op))
    if type_handle is not None:
        clauses.append(c.AtomType(int(type_handle)))
    if anchor is not None:
        clauses.append(c.Incident(int(anchor)))
    cond = clauses[0] if len(clauses) == 1 else c.And(*clauses)
    hs = [int(h) for h in g.find_all(cond)]
    keyed = sorted(
        ((value_key_of(g, h)[1:], h) for h in hs),
        key=lambda kv: (kv[0], kv[1]),
    )
    if desc:
        keyed.sort(key=lambda kv: kv[1])
        keyed.sort(key=lambda kv: kv[0], reverse=True)
    return [h for _, h in keyed]


def test_range_batched_equals_host_scan_pad_adjacent(graph):
    """A bucket-minus-one batch (the last lane sits against padding):
    every lane == the exact host scan, including duplicate requests,
    duplicate BOUNDS (eq windows over repeated values), and empty
    windows."""
    nodes, links, lt = _int_graph(graph, n=40, dup_every=4)
    probes = [
        dict(lo=5, hi=17),                      # plain window
        dict(lo=8, hi=8),                       # eq over DUPLICATED value
        dict(lo=0, hi=39),                      # whole dimension
        dict(lo=500, hi=900),                   # provably empty
        dict(lo=12, hi=12, lo_op="gt", hi_op="lt"),  # empty by ops
        dict(lo=10, hi=None),                   # open upper
        dict(lo=None, hi=6, hi_op="lt"),        # open lower
        dict(lo=5, hi=17),                      # duplicate request
    ]
    bucket = 64
    reqs = [probes[i % len(probes)] for i in range(bucket - 1)]
    rt = _runtime(graph, bucket)
    futs = [rt.submit_range(**p) for p in reqs]
    _drain(rt)
    assert rt.stats.batches == 1          # ONE coalesced dispatch
    assert rt.stats.range_dispatches == 1
    rt.close()
    for p, f in zip(reqs, futs):
        res = f.result(timeout=0)
        truth = _host_truth(graph, **p)
        assert res.count == len(truth)
        assert res.matches.tolist() == truth[: len(res.matches)]
        assert res.truncated == (res.count > len(res.matches))
        assert res.served_by == "device"


def test_ordered_and_topk_shapes(graph):
    nodes, links, lt = _int_graph(graph, n=30)
    rt = _runtime(graph, 64)
    fa = rt.submit_range(lo=3, hi=25)                      # ascending
    fd = rt.submit_range(lo=3, hi=25, desc=True)           # descending
    fk = rt.submit_range(lo=3, hi=25, limit=4)             # top-4 smallest
    fkd = rt.submit_range(lo=3, hi=25, desc=True, limit=4)  # top-4 largest
    _drain(rt)
    rt.close()
    truth = _host_truth(graph, lo=3, hi=25)
    truth_d = _host_truth(graph, lo=3, hi=25, desc=True)
    assert fa.result(timeout=0).matches.tolist() == truth
    assert fd.result(timeout=0).matches.tolist() == truth_d
    rk = fk.result(timeout=0)
    assert rk.matches.tolist() == truth[:4]
    assert rk.count == len(truth) and rk.truncated is True
    assert fkd.result(timeout=0).matches.tolist() == truth_d[:4]


def test_truncation_prefix_is_honest(graph):
    """count stays exact past the compact window; matches is the
    value-ordered prefix — and a truncated window under a dirty
    memtable re-serves exactly on host (prefixes cannot absorb
    corrections)."""
    nodes, links, lt = _int_graph(graph, n=40)
    rt = _runtime(graph, 64, top_r=5)
    fut = rt.submit_range(lo=0, hi=39)
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = _host_truth(graph, lo=0, hi=39)
    assert res.truncated is True
    assert res.count == len(truth) > 5
    assert res.matches.tolist() == truth[:5]
    assert res.served_by == "device"

    graph.enable_incremental(background=False, compact_ratio=100.0)
    graph.remove(nodes[2])  # memtable tombstone → prefix not correctable
    rt = _runtime(graph, 64, top_r=5)
    fut = rt.submit_range(lo=0, hi=39)
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = _host_truth(graph, lo=0, hi=39)
    assert res.served_by == "host"
    assert res.count == len(truth)
    assert res.matches.tolist() == truth[:5]


def test_mid_ingest_delta_tombstone_revalue_visibility(graph):
    """Post-pack mutations stay exact: fresh atoms arrive through the
    delta column, tombstones drop, revalues move atoms to their new
    window — all against one pinned view."""
    nodes, links, lt = _int_graph(graph, n=30)
    mgr = graph.enable_incremental(background=False, compact_ratio=100.0)
    fresh = [int(graph.add(1000 + i)) for i in range(4)]
    graph.remove(nodes[12])
    graph.replace(nodes[13], 9999)
    assert mgr.correction()[1]  # really still memtable
    rt = _runtime(graph, 64)
    f_win = rt.submit_range(lo=10, hi=20)       # straddles both mutations
    f_new = rt.submit_range(lo=999, hi=1002)    # delta-column only
    f_rev = rt.submit_range(lo=9000, hi=10000)  # revalued's new home
    _drain(rt)
    rt.close()
    for fut, kw in ((f_win, dict(lo=10, hi=20)),
                    (f_new, dict(lo=999, hi=1002)),
                    (f_rev, dict(lo=9000, hi=10000))):
        res = fut.result(timeout=0)
        truth = _host_truth(graph, **kw)
        assert res.matches.tolist() == truth
        assert res.count == len(truth)
    assert fresh[0] in f_new.result(timeout=0).matches.tolist()
    assert nodes[12] not in f_win.result(timeout=0).matches.tolist()
    assert nodes[13] in f_rev.result(timeout=0).matches.tolist()


def test_value_delta_column_reuse_under_lag(graph):
    """The delta column refreshes under the max_lag_edges drift
    discipline: within the bound the cached column is reused and the
    residual is host-corrected — results stay exact either way."""
    nodes, links, lt = _int_graph(graph, n=20)
    graph.enable_incremental(background=False, compact_ratio=100.0)
    int(graph.add(500))
    rt = _runtime(graph, 64, max_lag_edges=1_000_000)
    f1 = rt.submit_range(lo=400, hi=600)
    _drain(rt)
    # a second fresh atom INSIDE the lag bound: the cached column may
    # skip it — the host residual correction must not
    h2 = int(graph.add(501))
    f2 = rt.submit_range(lo=400, hi=600)
    _drain(rt)
    rt.close()
    assert f1.result(timeout=0).count == 1
    r2 = f2.result(timeout=0)
    assert h2 in r2.matches.tolist() and r2.count == 2


def test_type_filter_and_anchor_filter(graph):
    nodes, links, lt = _int_graph(graph, n=30)
    rt = _runtime(graph, 64)
    f_typed = rt.submit_range(lo=100, hi=110, type_handle=lt)
    anchor = nodes[3]
    f_anch = rt.submit_range(lo=100, hi=130, anchor=anchor)
    _drain(rt)
    rt.close()
    rt_res = f_typed.result(timeout=0)
    truth = _host_truth(graph, lo=100, hi=110, type_handle=lt)
    assert rt_res.matches.tolist() == truth
    ra = f_anch.result(timeout=0)
    truth_a = _host_truth(graph, lo=100, hi=130, anchor=anchor)
    assert ra.matches.tolist() == truth_a
    assert ra.served_by == "device"


def test_typed_lane_sees_fresh_memtable_atoms(graph):
    """A type-filtered range must not lose covered memtable atoms: the
    kernel's type filter reads the BASE type_of column (a delta gid is
    -1 there — masked out on device), so the collect merge re-offers
    the FULL memtable candidate set for typed lanes."""
    nodes, links, lt = _int_graph(graph, n=20)
    graph.enable_incremental(background=False, compact_ratio=100.0)
    a, b = nodes[2], nodes[5]
    fresh = int(graph.add_link([a, b], value=777))  # type lt, memtable
    rt = _runtime(graph, 64)
    f_typed = rt.submit_range(lo=100, hi=800, type_handle=lt)
    f_plain = rt.submit_range(lo=100, hi=800)
    _drain(rt)
    rt.close()
    res = f_typed.result(timeout=0)
    truth = _host_truth(graph, lo=100, hi=800, type_handle=lt)
    assert fresh in truth
    assert res.matches.tolist() == truth
    assert res.count == len(truth)
    assert f_plain.result(timeout=0).count == len(
        _host_truth(graph, lo=100, hi=800))


def test_anchored_lane_under_fresh_ingest_stays_on_device(graph):
    """A memtable link incident to the anchor is invisible to the BASE
    incidence rows the device filter probes — but the probe only masks
    candidates OUT, so the lane stays on device and the collect's
    delta-incidence re-offer (the live-graph ``get_targets`` check)
    merges the fresh link back in exactly."""
    nodes, links, lt = _int_graph(graph, n=20)
    graph.enable_incremental(background=False, compact_ratio=100.0)
    anchor = nodes[3]
    fresh = int(graph.add_link([anchor, nodes[7]], value=777))
    rt = _runtime(graph, 64)
    fut = rt.submit_range(lo=100, hi=800, anchor=anchor)
    _drain(rt)
    res = fut.result(timeout=0)
    truth = _host_truth(graph, lo=100, hi=800, anchor=anchor)
    assert fresh in truth
    assert res.served_by == "device"
    assert rt.stats.range_dispatches == 1
    assert res.matches.tolist() == truth
    rt.close()


def test_anchored_lane_under_churn_equals_host_oracle(graph):
    """Anchored lanes ride the device through the full memtable menu —
    a fresh incident link in-window, a fresh incident link out-of-window,
    a fresh NON-incident link in-window, a removed incident link, and a
    revalued one — and still equal the exact host oracle."""
    nodes, links, lt = _int_graph(graph, n=20)
    graph.enable_incremental(background=False, compact_ratio=100.0)
    anchor = nodes[3]
    inwin = int(graph.add_link([anchor, nodes[9]], value=350))
    outwin = int(graph.add_link([anchor, nodes[11]], value=9000))
    other = int(graph.add_link([nodes[5], nodes[6]], value=360))
    graph.remove(links[2])          # base link incident to anchor dies
    graph.replace(links[3], 370)    # base link revalued into the window
    rt = _runtime(graph, 64)
    fut = rt.submit_range(lo=100, hi=800, anchor=anchor)
    f_free = rt.submit_range(lo=100, hi=800)  # anchor-free control lane
    _drain(rt)
    res = fut.result(timeout=0)
    truth = _host_truth(graph, lo=100, hi=800, anchor=anchor)
    assert inwin in truth and outwin not in truth and other not in truth
    assert links[2] not in truth
    assert res.served_by == "device"
    assert res.matches.tolist() == truth
    assert res.count == len(truth)
    free = f_free.result(timeout=0)
    assert free.matches.tolist() == _host_truth(graph, lo=100, hi=800)
    rt.close()


def test_clean_variable_width_windows_serve_on_device(graph):
    """str values with CLEAN keys (≤16 payload bytes, NUL-free) ride the
    device lane through the 128-bit rank pair — including rank ties in
    the first word ('alphabetical' vs 'alphabetic': identical first 8
    payload bytes) — and return exactly the host scan."""
    words = ("apple", "alphabetic", "alphabetical", "banana", "blueberry",
             "cherry", "cherrystone", "date")
    for s in words:
        graph.add(s)
    rt = _runtime(graph, 64)
    fut = rt.submit_range(lo="alphabetical", hi="cherry")
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = _host_truth(graph, lo="alphabetical", hi="cherry")
    assert "alphabetic" not in [graph.get(h) for h in res.matches.tolist()]
    assert res.served_by == "device"
    assert res.matches.tolist() == truth
    assert rt.stats.range_dispatches == 1


def test_ambiguous_variable_width_kinds_serve_host_exactly(graph):
    """Ambiguity past the rank pair falls back to the exact host lane:
    an AMBIGUOUS BOUND (>16 payload bytes) makes the request inexact,
    and an ambiguous COLUMN ENTRY clears device_exact so even clean
    bounds host-serve. Both answered exactly, never approximated."""
    for s in ("apple", "banana", "cherry", "date"):
        graph.add(s)
    rt = _runtime(graph, 64)
    fut = rt.submit_range(lo="b", hi="an unambiguously long upper bound")
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = _host_truth(graph, lo="b", hi="an unambiguously long upper bound")
    assert res.served_by == "host"
    assert res.matches.tolist() == truth
    assert rt.stats.range_dispatches == 0  # nothing device-dispatched

    g2 = type(graph)()
    g2.add("a long string past the sixteen-byte rank pair")
    g2.add("brief")
    rt2 = _runtime(g2, 64)
    fut2 = rt2.submit_range(lo="a", hi="z")  # clean bounds, dirty column
    _drain(rt2)
    rt2.close()
    res2 = fut2.result(timeout=0)
    assert res2.served_by == "host"
    assert res2.matches.tolist() == _host_truth(g2, lo="a", hi="z")
    assert rt2.stats.range_dispatches == 0


def test_batch_key_separates_dimensions(graph):
    """int and float requests probe different sorted columns — they must
    never share a batch (the statics key is ("range", dim))."""
    graph.add(5)
    graph.add(5.0)
    rt = _runtime(graph, 64)
    fi = rt.submit_range(lo=0, hi=10)
    ff = rt.submit_range(lo=0.0, hi=10.0)
    _drain(rt)
    rt.close()
    assert rt.stats.batches == 2
    assert fi.result(timeout=0).count == 1
    assert ff.result(timeout=0).count == 1


def test_bridge_value_conditions(graph):
    """The condition front door: AtomValue / TypedValue / range-And
    conjunctions ride the range lane through submit_query."""
    nodes, links, lt = _int_graph(graph, n=20)
    rt = _runtime(graph, 64)
    f1 = rt.submit_query(dsl.value(7, "lte"))
    f2 = rt.submit_query(c.And(c.AtomValue(3, "gte"), c.AtomValue(9, "lt")))
    f3 = rt.submit_query(c.And(c.AtomValue(100, "gte"),
                               c.AtomValue(130, "lte"), c.AtomType(lt)))
    f4 = rt.submit_query(c.And(c.AtomValue(100, "gte"),
                               c.AtomValue(130, "lte"),
                               c.Incident(nodes[3])))
    with pytest.raises(Unservable):
        rt.submit_query(c.And(c.AtomValue(3, "gte"), c.AtomValue("z", "lt")))
    _drain(rt)
    rt.close()
    assert f1.result(timeout=0).matches.tolist() == _host_truth(
        graph, hi=7, hi_op="lte")
    assert f2.result(timeout=0).matches.tolist() == _host_truth(
        graph, lo=3, hi=9, hi_op="lt")
    assert f3.result(timeout=0).matches.tolist() == _host_truth(
        graph, lo=100, hi=130, type_handle=lt)
    assert f4.result(timeout=0).matches.tolist() == _host_truth(
        graph, lo=100, hi=130, anchor=nodes[3])


def test_range_prewarm_hits_aot_cache(graph, tmp_path):
    """``prewarm_range_dims``: a fresh runtime over a populated AOT
    cache reaches its first range dispatch without compiling (and the
    sorted column is built at startup, off the dispatch thread)."""
    _int_graph(graph, n=30)
    cfg = dict(buckets=(4,), max_linger_s=0.001, top_r=8,
               aot_cache_dir=str(tmp_path),
               prewarm_range_dims=(ord("i"),))
    rt1 = ServeRuntime(graph, ServeConfig(**cfg))
    r1 = rt1.submit_range(lo=3, hi=9).result(timeout=60)
    cold = rt1.stats_snapshot()["aot"]
    rt1.close()
    assert cold["puts"] >= 1, cold

    rt2 = ServeRuntime(graph, ServeConfig(**cfg))
    assert getattr(graph.incremental.base, "_value_index_cols", None)
    r2 = rt2.submit_range(lo=3, hi=9).result(timeout=60)
    warm = rt2.stats_snapshot()["aot"]
    rt2.close()
    assert warm["misses"] == 0, warm
    assert warm["disk_hits"] >= 1 or warm["hits"] >= 1, warm
    assert r1.count == r2.count
    np.testing.assert_array_equal(r1.matches, r2.matches)


def test_range_request_validation():
    with pytest.raises(Unservable):
        RangeRequest(dim=ord("i"), lo_rank=0, hi_rank=1, lo_op="lt")
    with pytest.raises(Unservable):
        RangeRequest(dim=ord("i"), lo_rank=0, hi_rank=1, limit=0)


def test_range_probe_batch_matches_numpy_searchsorted():
    """Kernel-level differential: the 4-word branchless binary search ==
    np.searchsorted over the recombined 128-bit rank pairs, both sides,
    at duplicate values, first-word ties, and both column ends."""
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops.value_index import range_probe_batch

    r = np.random.default_rng(9)
    ranks = np.sort(r.integers(0, 1 << 40, size=100).astype(np.uint64))
    ranks[10:15] = ranks[10]  # duplicates
    ranks2 = r.integers(0, 1 << 40, size=100).astype(np.uint64)
    ranks2[10:15] = np.sort(ranks2[10:15])  # tie band stays sorted
    ranks2[12] = ranks2[11]  # a full 128-bit duplicate inside the band
    order = np.lexsort((ranks2, ranks))
    ranks, ranks2 = ranks[order], ranks2[order]
    hi = (ranks >> np.uint64(32)).astype(np.uint32)
    lo = (ranks & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi2 = (ranks2 >> np.uint64(32)).astype(np.uint32)
    lo2 = (ranks2 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    pad = np.full(28, 0xFFFFFFFF, dtype=np.uint32)
    col_hi = np.concatenate([hi, pad])
    col_lo = np.concatenate([lo, pad])
    col_hi2 = np.concatenate([hi2, pad])
    col_lo2 = np.concatenate([lo2, pad])
    qi = [0, 10, 12, 50, 99]
    q = np.concatenate([ranks[qi], np.asarray([0, 1 << 63], np.uint64)])
    q2 = np.concatenate([ranks2[qi], np.asarray([0, 0], np.uint64)])
    # the reference search runs over the pair as python ints (numpy has
    # no native 128-bit ordering)
    pairs = [(int(a), int(b)) for a, b in zip(ranks, ranks2)]
    for right in (False, True):
        lo_idx, hi_idx = range_probe_batch(
            jnp.asarray(col_hi), jnp.asarray(col_lo),
            jnp.asarray(col_hi2), jnp.asarray(col_lo2), jnp.int32(100),
            jnp.asarray((q >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((q & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
            jnp.asarray((q2 >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((q2 & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
            jnp.asarray(np.full(len(q), right)),
            jnp.asarray((q >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((q & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
            jnp.asarray((q2 >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((q2 & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
            jnp.asarray(np.full(len(q), right)),
        )
        import bisect

        probe = list(zip((int(v) for v in q), (int(v) for v in q2)))
        fn = bisect.bisect_right if right else bisect.bisect_left
        want = np.asarray([fn(pairs, p) for p in probe], dtype=np.int32)
        np.testing.assert_array_equal(np.asarray(lo_idx), want)
        np.testing.assert_array_equal(np.asarray(hi_idx), want)


def test_join_value_window_filters_candidates(graph):
    """The executor hook: a rank window passed through
    ``execute_join(value_windows=...)`` filters the intersection
    candidates ON DEVICE — counts and bindings match the host plan's
    answer for the same conjunction."""
    from hypergraphdb_tpu.join.ir import split_constants
    from hypergraphdb_tpu.join.planner import plan_join, try_single_var_join
    from hypergraphdb_tpu.ops.join import execute_join
    from hypergraphdb_tpu.utils.ordered_bytes import encode_int, rank64

    vn = [int(graph.add(100 + i)) for i in range(12)]
    anchor = vn[0]
    for i in range(1, 12):
        graph.add_link([anchor, vn[i]], value=f"l{i}")
    cond = c.And(c.CoIncident(anchor), c.AtomValue(103, "gte"),
                 c.AtomValue(108, "lt"))
    truth = sorted(int(h) for h in graph.find_all(cond))
    assert len(truth) == 5

    plan_obj = try_single_var_join(
        graph, [c.CoIncident(anchor)], fallback=None,
        value_conds=[c.AtomValue(103, "gte"), c.AtomValue(108, "lt")],
    )
    snap = graph.snapshot()
    jp = plan_join(snap, plan_obj.pattern, plan_obj.sig, plan_obj.consts)
    win = {jp.order[0]: (ord("i"), rank64(encode_int(103)), "gte",
                         rank64(encode_int(108)), "lt")}
    consts = np.asarray([plan_obj.consts], dtype=np.int32)
    out = execute_join(snap, jp, consts, top_r=16, value_windows=win)
    assert not bool(np.asarray(out.trunc)[0])
    assert int(np.asarray(out.counts)[0]) == len(truth)
    rows = np.asarray(out.tuples)[0]
    got = sorted(int(x) for x in rows[rows[:, 0] >= 0][:, 0])
    assert got == truth
    # and WITHOUT the window the same plan binds the unfiltered set —
    # the filter really ran inside the step, not in this test
    out_nf = execute_join(snap, jp, consts, top_r=16)
    assert int(np.asarray(out_nf.counts)[0]) == 11


def test_join_pushdown_plan_carries_value_conds(graph):
    """Through find_all: the value-constrained co-incidence conjunction
    translates to a DeviceJoinPlan carrying the value conds (cost-based
    at run time, exact on either arm), and memtable candidates respect
    the window."""
    from hypergraphdb_tpu.join.planner import DeviceJoinPlan
    from hypergraphdb_tpu.query.compiler import compile_query

    vn = [int(graph.add(100 + i)) for i in range(12)]
    anchor = vn[0]
    for i in range(1, 12):
        graph.add_link([anchor, vn[i]], value=f"l{i}")
    cond = c.And(c.CoIncident(anchor), c.AtomValue(103, "gte"),
                 c.AtomValue(108, "lt"))
    cq = compile_query(graph, cond)
    assert isinstance(cq.plan, DeviceJoinPlan)
    assert len(cq.plan.value_conds) == 2
    truth = sorted(int(h) for h in graph.find_all(cond))
    assert len(truth) == 5
    # memtable candidates respect the value window too
    graph.enable_incremental(background=False, compact_ratio=100.0)
    inwin = int(graph.add(105))
    outwin = int(graph.add(150))
    graph.add_link([anchor, inwin], value="f1")
    graph.add_link([anchor, outwin], value="f2")
    got2 = sorted(int(h) for h in graph.find_all(cond))
    assert inwin in got2 and outwin not in got2
