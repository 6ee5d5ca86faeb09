"""Differential serving tests: batched == unbatched, per shape bucket.

The serving contract is that coalescing + padding is INVISIBLE: a padded
micro-batch of mixed requests must return results identical to unbatched
per-request execution (and to the host query engine's ground truth) —
including seeds adjacent to padding lanes, duplicate seeds, and empty
result sets. Runs the REAL DeviceExecutor over small graphs under
``JAX_PLATFORMS=cpu``; the concurrent-ingest soak is marked ``slow``.
"""

from __future__ import annotations

import numpy as np
import pytest

from hypergraphdb_tpu.query import dsl
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime
from tests.conftest import make_random_hypergraph

BUCKETS = (64, 256, 1024)


def _build(g, seed=3):
    nodes, links = make_random_hypergraph(
        g, n_nodes=100, n_links=200, max_arity=4, seed=seed
    )
    iso = [int(g.add(f"iso{i}")) for i in range(3)]
    return [int(n) for n in nodes], [int(x) for x in links], iso


def _runtime(g, bucket, **kw):
    kw.setdefault("top_r", 512)
    cfg = ServeConfig(buckets=(bucket,), manual=True, max_linger_s=0.0,
                      **kw)
    return ServeRuntime(g, cfg)


def _drain(rt):
    while rt.step(drain=True):
        pass


def _bfs_truth(g, seed, hops):
    return sorted(int(h) for h in g.find_all(
        dsl.bfs(seed, max_distance=hops)
    ))


@pytest.mark.parametrize("bucket", BUCKETS)
def test_bfs_batched_equals_unbatched(graph, bucket):
    nodes, links, iso = _build(graph)
    # unique probes: first/last packed atoms, isolated (empty result),
    # a link as seed — then CYCLED to fill the bucket minus one (so the
    # final lane sits right against the padding lanes)
    probes = [nodes[0], nodes[1], nodes[-1], iso[0], iso[1], links[0],
              nodes[7], nodes[7]]  # duplicate seed in the same batch
    n_req = bucket - 1
    reqs = [probes[i % len(probes)] for i in range(n_req)]

    rt = _runtime(graph, bucket)
    futs = [rt.submit_bfs(s, max_hops=2, include_seed=False) for s in reqs]
    _drain(rt)
    batched = [f.result(timeout=0) for f in futs]
    assert rt.stats.batches == 1  # everything coalesced into ONE dispatch
    rt.close()

    # unbatched: the same requests one per dispatch (K=1 bucket)
    rt1 = _runtime(graph, 1)
    singles = {}
    for s in set(reqs):
        fut = rt1.submit_bfs(s, max_hops=2, include_seed=False)
        _drain(rt1)
        singles[s] = fut.result(timeout=0)
    rt1.close()

    for s, res in zip(reqs, batched):
        one = singles[s]
        assert res.count == one.count
        assert res.truncated == one.truncated is False
        np.testing.assert_array_equal(res.matches, one.matches)
        assert res.matches.tolist() == _bfs_truth(graph, s, 2)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_pattern_batched_equals_unbatched(graph, bucket):
    nodes, links, iso = _build(graph)
    pairs = []
    for lk in links[:6]:
        ts = [int(t) for t in graph.get_targets(lk)]
        if len(ts) >= 2 and ts[0] != ts[1]:
            pairs.append((ts[0], ts[1]))
    pairs.append((iso[0], iso[1]))       # provably empty result
    pairs.append((nodes[3], nodes[3]))   # duplicate anchor
    pairs.append(pairs[0])               # duplicate request
    n_req = min(bucket, 2 * len(pairs))
    reqs = [pairs[i % len(pairs)] for i in range(n_req)]

    rt = _runtime(graph, bucket)
    futs = [rt.submit_pattern(p) for p in reqs]
    _drain(rt)
    batched = [f.result(timeout=0) for f in futs]
    rt.close()

    rt1 = _runtime(graph, 1)
    singles = {}
    for p in set(reqs):
        fut = rt1.submit_pattern(p)
        _drain(rt1)
        singles[p] = fut.result(timeout=0)
    rt1.close()

    for p, res in zip(reqs, batched):
        one = singles[p]
        assert res.count == one.count
        np.testing.assert_array_equal(res.matches, one.matches)
        truth = sorted(int(h) for h in graph.find_all(
            dsl.and_(dsl.incident(p[0]), dsl.incident(p[1]))
        ))
        assert res.matches.tolist() == truth


def test_mixed_kind_batches_match_ground_truth(graph):
    nodes, links, iso = _build(graph)
    th = int(graph.get_type_handle_of(links[0]))  # links carry int values
    rt = _runtime(graph, 64)
    fb = rt.submit_bfs(nodes[0], max_hops=2, include_seed=False)
    ts = [int(t) for t in graph.get_targets(links[0])][:2]
    fp = rt.submit_pattern(ts)
    ftp = rt.submit_pattern(ts, type_handle=th)
    fq = rt.submit_query(dsl.bfs(nodes[5], max_distance=2))
    f1 = rt.submit_query(dsl.incident(nodes[2]))
    _drain(rt)
    rt.close()
    assert fb.result(timeout=0).matches.tolist() == _bfs_truth(
        graph, nodes[0], 2
    )
    truth_p = sorted(int(h) for h in graph.find_all(
        dsl.and_(*[dsl.incident(t) for t in ts])
    ))
    assert fp.result(timeout=0).matches.tolist() == truth_p
    truth_tp = sorted(int(h) for h in graph.find_all(dsl.and_(
        dsl.type_(th), *[dsl.incident(t) for t in ts]
    )))
    assert ftp.result(timeout=0).matches.tolist() == truth_tp
    assert fq.result(timeout=0).matches.tolist() == _bfs_truth(
        graph, nodes[5], 2
    )
    assert f1.result(timeout=0).matches.tolist() == sorted(
        int(h) for h in graph.find_all(dsl.incident(nodes[2]))
    )


def test_include_seed_variants(graph):
    nodes, links, iso = _build(graph)
    rt = _runtime(graph, 64)
    fin = rt.submit_bfs(nodes[0], max_hops=2, include_seed=True)
    fout = rt.submit_bfs(nodes[0], max_hops=2, include_seed=False)
    fiso = rt.submit_bfs(iso[0], max_hops=2, include_seed=False)
    _drain(rt)
    rt.close()
    rin, rout, riso = (f.result(timeout=0) for f in (fin, fout, fiso))
    assert rin.count == rout.count + 1
    assert sorted(set(rout.matches.tolist()) | {nodes[0]}) \
        == rin.matches.tolist()
    assert riso.count == 0 and len(riso.matches) == 0  # empty result set


def test_serve_sees_delta_and_tombstones(graph):
    """Requests under pending (uncompacted) ingest stay EXACT: BFS flows
    through the device delta overlay, patterns through the host memtable
    merge, removals through tombstones — all pinned to one view."""
    nodes, links, iso = _build(graph)
    mgr = graph.enable_incremental(background=False, compact_ratio=100.0)
    # post-pack mutations living purely in the delta/memtable
    a, b = nodes[2], nodes[9]
    fresh_link = int(graph.add_link([a, b], value="fresh"))
    removed = links[0]
    rm_ts = [int(t) for t in graph.get_targets(removed)][:2]
    graph.remove(removed)
    assert mgr.delta_edges > 0  # the new edges are really still delta

    rt = _runtime(graph, 64)
    f_bfs = rt.submit_bfs(a, max_hops=1, include_seed=False)
    f_pat = rt.submit_pattern((a, b))
    f_rm = rt.submit_pattern(tuple(rm_ts)) if rm_ts[0] != rm_ts[1] else None
    _drain(rt)
    rt.close()

    r = f_bfs.result(timeout=0)
    assert b in r.matches.tolist()  # reached THROUGH the delta edge
    assert r.matches.tolist() == _bfs_truth(graph, a, 1)
    p = f_pat.result(timeout=0)
    assert fresh_link in p.matches.tolist()  # memtable merge found it
    assert p.matches.tolist() == sorted(int(h) for h in graph.find_all(
        dsl.and_(dsl.incident(a), dsl.incident(b))
    ))
    if f_rm is not None:
        assert removed not in f_rm.result(timeout=0).matches.tolist()


def test_truncation_flag_and_prefix(graph):
    nodes, links, iso = _build(graph)
    rt = _runtime(graph, 64, top_r=2)
    fut = rt.submit_bfs(nodes[0], max_hops=2, include_seed=False)
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = _bfs_truth(graph, nodes[0], 2)
    assert len(truth) > 2
    assert res.truncated is True
    assert res.count == len(truth)          # count stays exact
    assert res.matches.tolist() == truth[:2]  # ascending prefix


def test_host_fallback_is_exact(graph):
    """Anchors whose base incidence row exceeds pattern_pad leave the
    batched path but stay exact (served_by='host')."""
    nodes, links, iso = _build(graph)
    hub = int(graph.add("hub"))
    for i in range(9):
        graph.add_link([hub, nodes[i]], value=f"h{i}")
    rt = _runtime(graph, 64, pattern_pad=4)
    fut = rt.submit_pattern((hub, nodes[0]))
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    assert res.served_by == "host"
    assert rt.stats.host_fallbacks == 1
    assert res.matches.tolist() == sorted(int(h) for h in graph.find_all(
        dsl.and_(dsl.incident(hub), dsl.incident(nodes[0]))
    ))


def test_unservable_conditions_raise(graph):
    from hypergraphdb_tpu.serve.types import Unservable

    rt = _runtime(graph, 64)
    with pytest.raises(Unservable):
        rt.submit_query(dsl.bfs(1))  # unbounded hops
    with pytest.raises(Unservable):
        rt.submit_query(dsl.value_regex("x.*"))  # predicates stay host
    with pytest.raises(Unservable):
        rt.submit_query(dsl.or_(dsl.incident(1), dsl.incident(2)))
    # value predicates are SERVABLE since hgindex (the range lane) —
    # the old "value predicates raise Unservable" scoping is retired
    fut = rt.submit_query(dsl.value(3, op="lte"))
    _drain(rt)
    rt.close()
    assert fut.result(timeout=0).count >= 0


@pytest.mark.slow
def test_soak_threaded_under_concurrent_ingest(graph):
    """The real thing: threaded runtime, background-compacting manager,
    concurrent writer — every future resolves (result or a typed
    deadline), the drain completes, stats add up."""
    import threading

    from hypergraphdb_tpu.serve import DeadlineExceeded

    nodes, links, iso = _build(graph)
    graph.enable_incremental(background=True, compact_ratio=0.05)
    cfg = ServeConfig(buckets=(16, 64), max_linger_s=0.002,
                      max_queue=512, top_r=512)
    rt = ServeRuntime(graph, cfg)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            graph.bulk_import(
                values=[f"w{i}_{j}" for j in range(20)],
                target_lists=[
                    [nodes[(i + j) % len(nodes)],
                     nodes[(i * 7 + j) % len(nodes)]]
                    for j in range(20)
                ],
            )
            i += 1

    wt = threading.Thread(target=writer, daemon=True)
    wt.start()
    futs = []
    r = np.random.default_rng(5)
    for i in range(400):
        if i % 3 == 0:
            ts = [int(t) for t in graph.get_targets(
                links[int(r.integers(0, len(links)))]
            )][:2]
            if len(ts) == 2 and ts[0] != ts[1]:
                futs.append(rt.submit_pattern(ts, deadline_s=5.0))
                continue
        futs.append(rt.submit_bfs(
            nodes[int(r.integers(0, len(nodes)))], max_hops=2,
            deadline_s=5.0,
        ))
    stop.set()
    wt.join(30)
    rt.close(drain=True, timeout=60)
    resolved = 0
    for f in futs:
        try:
            res = f.result(timeout=10)
            assert res.count >= 0
            resolved += 1
        except DeadlineExceeded:
            pass
    assert resolved > 0
    s = rt.stats_snapshot()
    assert s["submitted"] == len(futs)
    assert s["completed"] + s["shed_deadline"] == len(futs)
    mgr = graph.incremental
    assert mgr.wait_compacted(30.0)


def test_truncated_pattern_under_memtable_serves_exactly(graph):
    """A truncated device window cannot absorb memtable corrections (a
    tombstone beyond the prefix would overcount; a fresh link would punch
    a hole) — such requests must come back exact via the host path."""
    nodes, links, iso = _build(graph)
    a, b = nodes[2], nodes[9]
    base_links = [int(graph.add_link([a, b], value=f"m{i}"))
                  for i in range(8)]
    graph.enable_incremental(background=False, compact_ratio=100.0)
    # post-pack memtable activity touching the SAME pattern
    graph.remove(base_links[-1])                      # beyond any 3-prefix
    fresh = int(graph.add_link([a, b], value="fresh"))
    rt = _runtime(graph, 64, top_r=3)
    fut = rt.submit_pattern((a, b))
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = sorted(int(h) for h in graph.find_all(
        dsl.and_(dsl.incident(a), dsl.incident(b))
    ))
    assert fresh in truth and base_links[-1] not in truth
    assert res.served_by == "host"
    assert res.count == len(truth)            # no tombstone overcount
    assert res.matches.tolist() == truth[:3]  # gap-free ascending prefix


def test_pattern_correction_uses_pinned_state_not_live_graph(graph):
    """Memtable corrections evaluate records captured at launch: a
    mutation landing while the device executes must not leak into a batch
    pinned before it."""
    nodes, links, iso = _build(graph)
    a, b = nodes[2], nodes[9]
    graph.enable_incremental(background=False, compact_ratio=100.0)
    fresh = int(graph.add_link([a, b], value="fresh"))
    rt = _runtime(graph, 64)
    fut = rt.submit_pattern((a, b))
    assert rt.pump(drain=True) is True   # launched, NOT yet collected
    graph.remove(fresh)                  # post-launch mutation
    rt.close(drain=True)                 # collects the pending batch
    res = fut.result(timeout=0)
    assert res.served_by == "device"
    assert fresh in res.matches.tolist()  # the pinned view still had it


def test_memtable_merge_past_top_r_truncates(graph):
    """A non-truncated device window whose memtable merge overflows top_r
    must come back truncated with a top_r-wide prefix and an exact
    count — one shape contract for every path."""
    nodes, links, iso = _build(graph)
    a, b = nodes[2], nodes[9]
    base = [int(graph.add_link([a, b], value=f"m{i}")) for i in range(2)]
    graph.enable_incremental(background=False, compact_ratio=100.0)
    fresh = [int(graph.add_link([a, b], value=f"f{i}")) for i in range(2)]
    rt = _runtime(graph, 64, top_r=3)
    fut = rt.submit_pattern((a, b))
    _drain(rt)
    rt.close()
    res = fut.result(timeout=0)
    truth = sorted(base + fresh)
    assert res.count == 4 and res.truncated is True
    assert res.matches.tolist() == truth[:3]


def test_all_host_batch_counts_no_device_dispatch(graph):
    nodes, links, iso = _build(graph)
    hub = int(graph.add("hub"))
    for i in range(9):
        graph.add_link([hub, nodes[i]], value=f"h{i}")
    rt = _runtime(graph, 64, pattern_pad=2)  # every pair over budget
    f1 = rt.submit_pattern((hub, nodes[0]))
    f2 = rt.submit_pattern((hub, nodes[1]))
    _drain(rt)
    rt.close()
    assert f1.result(timeout=0).served_by == "host"
    assert f2.result(timeout=0).served_by == "host"
    s = rt.stats_snapshot()
    assert s["batches"] == 1              # the micro-batch formed and served
    assert s["device_dispatches"] == 0    # but no kernel ever launched


def test_pattern_launch_skips_device_delta_upload(graph):
    """Pattern batches consume base + HOST corrections only — pinning one
    must not pay a device-delta upload (that transfer is the BFS path's
    freshness cost, not the pattern path's)."""
    nodes, links, iso = _build(graph)
    a, b = nodes[2], nodes[9]
    mgr = graph.enable_incremental(background=False, compact_ratio=100.0)
    fresh = int(graph.add_link([a, b], value="fresh"))  # dirty memtable
    up0 = (mgr.full_uploads, mgr.tail_uploads)
    rt = _runtime(graph, 64)
    fut = rt.submit_pattern((a, b))
    _drain(rt)
    rt.close()
    assert (mgr.full_uploads, mgr.tail_uploads) == up0  # no upload paid
    assert fresh in fut.result(timeout=0).matches.tolist()  # still exact


def test_bfs_bucket_the_compiler_refuses_is_not_formed(graph, monkeypatch,
                                                       caplog, tmp_path):
    """On a device with bounded memory the executor compiles the BFS
    buckets' programs narrowest first and stops at the first the compiler
    refuses for memory (here: a stand-in that refuses 128 seeds, as the
    v5e's compiler refuses 1024 at 3M atoms): that bucket is logged once,
    not prewarmed, never formed; a burst wider than the cap rides more
    batches of the widest bucket that fits, every answer unchanged."""
    import logging

    from hypergraphdb_tpu.serve import runtime as srt

    nodes, links, iso = _build(graph)
    real = srt.DeviceExecutor._bfs_program

    def compiler(self, view, bucket):
        if bucket >= 128:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm.")
        return real(self, view, bucket)

    monkeypatch.setattr(srt.DeviceExecutor, "_bfs_program", compiler)
    cfg = ServeConfig(buckets=(8, 32, 128), manual=True, max_linger_s=0.0,
                      top_r=512, aot_cache_dir=str(tmp_path))
    rt = ServeRuntime(graph, cfg)
    assert rt.executor.bfs_bucket_cap() is None     # CPU: nothing is asked
    rt.close()
    monkeypatch.setattr(srt.DeviceExecutor, "_device_memory_is_bounded",
                        lambda self: True)
    with caplog.at_level(logging.WARNING, logger="hypergraphdb_tpu.serve"):
        rt = ServeRuntime(graph, cfg)
        assert rt.executor.bfs_bucket_cap() == 32
        assert rt.executor.max_batch(("pattern", 2)) is None
        seeds = [nodes[i % len(nodes)] for i in range(100)]
        futs = [rt.submit_bfs(s, max_hops=2) for s in seeds]
        _drain(rt)
    declines = [r.getMessage() for r in caplog.records
                if "BFS bucket" in r.getMessage()]
    assert len(declines) == 1 and "bucket 128 declined" in declines[0]
    snap = rt.stats_snapshot()
    # 100 requests: 32 + 32 + 32 + 4 (an 8-lane bucket), never 128
    assert snap["batches"] == 4 and snap["errors"] == 0
    assert snap["batch_occupancy"] == pytest.approx(100 / (3 * 32 + 8))
    rt.close()
    for s, f in zip(seeds, futs):
        got = f.result(timeout=0)
        assert got.matches.tolist() == sorted(
            set(_bfs_truth(graph, s, 2)) | {s})


@pytest.mark.parametrize("n,top_r", [(1000, 4), (1000, 17), (257, 100),
                                     (64, 5), (40, 64)])
def test_first_r_dense_blocked_sweep_equals_numpy(monkeypatch, n, top_r):
    """The blocked compaction sweep (many blocks, a ragged last block,
    top_r wider than a block, rows with fewer hits than top_r) returns
    exactly the smallest set ids, ascending, SENTINEL-padded."""
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import serving
    from hypergraphdb_tpu.ops.setops import SENTINEL

    monkeypatch.setattr(serving, "FIRST_R_BLOCK", 64)
    r = np.random.default_rng(n + top_r)
    mask = r.random((6, n)) < np.asarray([0, 0.001, 0.01, 0.1, 0.5, 1.0]
                                         )[:, None]
    got = np.asarray(serving.first_r_dense(jnp.asarray(mask), top_r,
                                           base=1000))
    for row, out in zip(mask, got):
        ids = 1000 + np.nonzero(row)[0][:top_r]
        want = np.full(top_r, int(SENTINEL), np.int64)
        want[: len(ids)] = ids
        assert out.tolist() == want.tolist()
