"""Multi-chip sharded serving: differential exactness + routing.

The acceptance contract: sharded BFS/pattern/join serve results ==
single-chip results == host ground truth for every bucket shape,
including delta/tombstone visibility mid-ingest and truncation prefixes
— on the virtual 8-device CPU mesh the conftest forces.
"""

import numpy as np
import pytest

from hypergraphdb_tpu import HyperGraph
from hypergraphdb_tpu.query import conditions as c
from hypergraphdb_tpu.query.variables import var
from hypergraphdb_tpu.serve import (
    DeviceExecutor,
    ServeConfig,
    ServeRuntime,
    ShardedExecutor,
)

from conftest import make_random_hypergraph

#: small buckets keep the per-test compile count bounded; 16 and 64 are
#: both divisible by the 8-device mesh (the join lane split needs that)
BUCKETS = (16, 64)


def _cfg(**kw):
    base = dict(buckets=BUCKETS, max_linger_s=0.001, top_r=16,
                prewarm_aot=False)
    base.update(kw)
    return ServeConfig(**base)


def _pair(graph_builder):
    """Two graphs with identical content; a sharded runtime on one, a
    single-chip runtime on the other."""
    g1, aux1 = graph_builder()
    g2, aux2 = graph_builder()
    rt_sh = ServeRuntime(g1, _cfg(sharded=True))
    rt_one = ServeRuntime(g2, _cfg(sharded=False))
    assert isinstance(rt_sh.executor, ShardedExecutor)
    assert type(rt_one.executor) is DeviceExecutor
    return (g1, aux1, rt_sh), (g2, aux2, rt_one)


def _build(seed=3, n_nodes=150, n_links=300):
    def build():
        g = HyperGraph()
        aux = make_random_hypergraph(g, n_nodes=n_nodes, n_links=n_links,
                                     seed=seed)
        return g, aux
    return build


def _assert_same(r1, r2):
    assert r1.count == r2.count
    assert r1.truncated == r2.truncated
    np.testing.assert_array_equal(np.asarray(r1.matches),
                                  np.asarray(r2.matches))


# ---------------------------------------------------------------- BFS


def test_sharded_bfs_matches_single_chip_and_host():
    (g1, (nodes1, _), rt1), (g2, (nodes2, _), rt2) = _pair(_build())
    try:
        futs1 = [rt1.submit_bfs(int(nodes1[i]), max_hops=3)
                 for i in range(24)]
        futs2 = [rt2.submit_bfs(int(nodes2[i]), max_hops=3)
                 for i in range(24)]
        for i, (f1, f2) in enumerate(zip(futs1, futs2)):
            r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
            assert r1.served_by == "device"
            _assert_same(r1, r2)
            truth = sorted(
                int(h) for h in g1.find_all(
                    c.BFS(int(nodes1[i]), max_distance=3))
            ) + [int(nodes1[i])]
            assert r1.count == len(set(truth))
        assert rt1.stats.sharded_dispatches > 0
    finally:
        rt1.close()
        rt2.close()
        g1.close()
        g2.close()


def test_sharded_bfs_sees_delta_and_tombstones_mid_ingest():
    """The pinned sharded (base ∪ delta) twins: post-compaction adds are
    visible through the sharded kernel, removals tombstone out — equal
    to the single-chip delta path lane for lane."""
    (g1, (nodes1, links1), rt1), (g2, (nodes2, links2), rt2) = \
        _pair(_build(seed=5))
    try:
        # mutate BOTH graphs identically AFTER the runtimes pinned once
        for g, nodes, links in ((g1, nodes1, links1),
                                (g2, nodes2, links2)):
            for i in range(6):
                g.add_link([nodes[i], nodes[i + 40]])
            g.remove(links[7])
            g.remove(links[9])
        for i in list(range(8)) + [40, 41]:
            r1 = rt1.submit_bfs(int(nodes1[i]), max_hops=2).result(
                timeout=120)
            r2 = rt2.submit_bfs(int(nodes2[i]), max_hops=2).result(
                timeout=120)
            _assert_same(r1, r2)
            truth = set(
                int(h) for h in g1.find_all(
                    c.BFS(int(nodes1[i]), max_distance=2))
            ) | {int(nodes1[i])}
            assert r1.count == len(truth)
    finally:
        rt1.close()
        rt2.close()
        g1.close()
        g2.close()


def test_sharded_bfs_truncation_prefix_exact():
    (g1, (nodes1, _), rt1), (g2, (nodes2, _), rt2) = _pair(_build(seed=9))
    rt1.config.top_r = rt2.config.top_r = 4  # shrink the compact window
    try:
        r1 = rt1.submit_bfs(int(nodes1[0]), max_hops=3).result(timeout=120)
        r2 = rt2.submit_bfs(int(nodes2[0]), max_hops=3).result(timeout=120)
        assert r1.truncated and r1.count > 4 and len(r1.matches) == 4
        _assert_same(r1, r2)
        truth = sorted(set(
            int(h) for h in g1.find_all(
                c.BFS(int(nodes1[0]), max_distance=3))
        ) | {int(nodes1[0])})
        assert list(r1.matches) == truth[:4]   # ascending prefix
    finally:
        rt1.close()
        rt2.close()
        g1.close()
        g2.close()


# ---------------------------------------------------------------- patterns


def test_sharded_pattern_matches_single_chip_and_host():
    (g1, (nodes1, links1), rt1), (g2, (nodes2, links2), rt2) = \
        _pair(_build(seed=7, n_links=400))
    try:
        lt = int(g1.get_type_handle_of(links1[0]))
        pairs = []
        for lk in links1[:24]:
            ts = [int(t) for t in g1.get_targets(lk)]
            if len(ts) >= 2 and ts[0] != ts[1]:
                pairs.append((ts[0], ts[1]))
        assert len(pairs) >= 4
        for th in (None, lt):
            for a, b in pairs[:6]:
                r1 = rt1.submit_pattern([a, b], type_handle=th).result(
                    timeout=120)
                r2 = rt2.submit_pattern([a, b], type_handle=th).result(
                    timeout=120)
                _assert_same(r1, r2)
                clauses = [c.Incident(a), c.Incident(b)]
                if th is not None:
                    clauses.append(c.AtomType(th))
                truth = sorted(int(h) for h in g1.find_all(c.And(*clauses)))
                assert r1.count == len(truth)
                if not r1.truncated:
                    assert sorted(int(m) for m in r1.matches) == truth
        assert rt1.stats.sharded_dispatches > 0
    finally:
        rt1.close()
        rt2.close()
        g1.close()
        g2.close()


def test_sharded_pattern_memtable_correction_mid_ingest():
    """Pattern lanes run on the BASE; the host memtable merge at collect
    must make fresh links visible and tombstoned ones invisible —
    exactly the single-chip LSM correction, through the sharded path."""
    (g1, (nodes1, links1), rt1), (g2, (nodes2, links2), rt2) = \
        _pair(_build(seed=11))
    try:
        a, b = int(nodes1[2]), int(nodes1[3])
        a2, b2 = int(nodes2[2]), int(nodes2[3])
        fresh1 = [int(g1.add_link([a, b])) for _ in range(3)]
        [int(g2.add_link([a2, b2])) for _ in range(3)]
        g1.remove(fresh1[0])
        g2.remove(int(fresh1[0]))  # same handle space by construction
        r1 = rt1.submit_pattern([a, b]).result(timeout=120)
        r2 = rt2.submit_pattern([a2, b2]).result(timeout=120)
        _assert_same(r1, r2)
        truth = sorted(int(h) for h in g1.find_all(
            c.And(c.Incident(a), c.Incident(b))))
        assert r1.count == len(truth)
        assert sorted(int(m) for m in r1.matches) == truth[:16]
    finally:
        rt1.close()
        rt2.close()
        g1.close()
        g2.close()


# ---------------------------------------------------------------- joins


def test_sharded_join_matches_single_chip_and_host():
    from hypergraphdb_tpu.join.host import host_join
    from hypergraphdb_tpu.join.ir import extract_pattern

    (g1, (nodes1, _), rt1), (g2, (nodes2, _), rt2) = \
        _pair(_build(seed=13, n_links=400))
    try:
        spec = lambda a: {"y": c.CoIncident(a), "z": c.CoIncident(var("y"))}
        for i in range(6):
            a1, a2 = int(nodes1[i]), int(nodes2[i])
            r1 = rt1.submit_join(spec(a1)).result(timeout=300)
            r2 = rt2.submit_join(spec(a2)).result(timeout=300)
            assert r1.count == r2.count
            assert r1.truncated == r2.truncated
            np.testing.assert_array_equal(r1.tuples, r2.tuples)
            truth = host_join(g1, extract_pattern(g1, spec(a1)))
            assert r1.count == len(truth)
            got = [tuple(int(v) for v in row) for row in r1.tuples]
            assert got == truth[:16]
        assert rt1.stats.sharded_dispatches > 0
    finally:
        rt1.close()
        rt2.close()
        g1.close()
        g2.close()


# ---------------------------------------------------------------- routing


def test_executor_pick_forced_and_auto():
    g = HyperGraph()
    make_random_hypergraph(g, n_nodes=40, n_links=60, seed=1)
    try:
        rt = ServeRuntime(g, _cfg(sharded=False))
        assert type(rt.executor) is DeviceExecutor
        rt.close()
        # AUTO: a 1-byte budget means any snapshot overflows one chip
        rt = ServeRuntime(g, _cfg(sharded=None, hbm_budget_bytes=1))
        assert isinstance(rt.executor, ShardedExecutor)
        rt.close()
        # AUTO with a huge budget stays single-chip
        rt = ServeRuntime(g, _cfg(sharded=None,
                                  hbm_budget_bytes=1 << 40))
        assert type(rt.executor) is DeviceExecutor
        rt.close()
    finally:
        g.close()


_PREWARM_TWICE = """
import json, sys
from hypergraphdb_tpu import HyperGraph
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime
from tests.conftest import make_random_hypergraph

out = []
for _ in range(2):          # a pod, then a fresh pod over the same cache
    g = HyperGraph()
    make_random_hypergraph(g, n_nodes=80, n_links=160, seed=2)
    rt = ServeRuntime(g, ServeConfig(
        sharded=True, buckets=(16,), max_linger_s=0.001, top_r=16,
        prewarm_aot=True, aot_cache_dir=sys.argv[1],
        prewarm_pattern_arities=(2,)))
    out.append(rt.stats_snapshot()["aot"])
    rt.close()
    g.close()
print(json.dumps(out))
"""


def test_sharded_prewarm_hits_aot_cache(tmp_path):
    """Satellite: a fresh pod over a populated cache reaches first
    sharded dispatch with ZERO compiles — every prewarmed sharded bucket
    program loads from disk.

    In a process of its own: XLA:CPU cannot serialize an executable once
    it has RUN (its sort comparator is resolved in place — "`LessThan`
    is not serializable"), and jit hands ``lower().compile()`` the
    process-cached executable of an identical program, so in-process the
    first pod's puts would depend on which tests this worker ran before
    (``AOTCache._store`` logs such a put and goes on: the cache
    accelerates, it never gates)."""
    import json
    import os
    import subprocess
    import sys

    # tests/conftest.py already put the 8-device CPU mesh in os.environ
    proc = subprocess.run(
        [sys.executable, "-c", _PREWARM_TWICE, str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    first, warm = json.loads(proc.stdout.strip().splitlines()[-1])
    assert first["puts"] >= 2          # bfs + pattern sharded programs
    assert warm["misses"] == 0, warm
    assert warm["disk_hits"] >= 2, warm


def test_healthz_advertises_mesh_and_partition_map():
    from hypergraphdb_tpu.obs.http import runtime_health

    g = HyperGraph()
    make_random_hypergraph(g, n_nodes=60, n_links=100, seed=4)
    rt = ServeRuntime(g, _cfg(sharded=True))
    try:
        rt.submit_bfs(3, max_hops=1).result(timeout=120)  # builds the shard
        healthy, payload = runtime_health(rt)()
        assert healthy
        mesh = payload["mesh"]
        assert mesh["devices"] == 8
        assert mesh["axis"] == "shard"
        pm = mesh["partition_map"]
        assert pm["n_parts"] == 8
        assert len(pm["ranges"]) == 8
        assert len(mesh["shards"]) == 8
        assert mesh["shards"][0]["gid_lo"] == 0
    finally:
        rt.close()
        g.close()


def test_front_door_places_by_shard_ownership():
    """A backend whose advertised partition map covers the request's ids
    wins placement over a fresher one that does not."""
    from hypergraphdb_tpu.replica.router import FrontDoor, RouterConfig

    class FakeBackend:
        def __init__(self, bid, capacity, lag):
            self.id = bid
            self.capacity = capacity
            self.lag = lag
            self.served = 0

        def submit(self, payload, timeout):
            self.served += 1
            return {"kind": payload["kind"], "count": 0, "matches": [],
                    "truncated": False, "epoch": 0, "served_by": "device"}

        def health(self):
            return True, {
                "replication_lag": self.lag, "queue_depth": 0,
                "breaker_worst": 0,
                "mesh": {"partition_map": {"capacity": self.capacity}},
            }

    small = FakeBackend("small-pod", capacity=100, lag=0)   # fresher
    big = FakeBackend("big-pod", capacity=10_000, lag=5)    # covers more
    primary = FakeBackend("primary", capacity=None, lag=0)
    door = FrontDoor(primary, [small, big],
                     RouterConfig(poll_interval_s=0))
    # seed beyond the small pod's coverage → the big pod owns it,
    # despite its worse lag
    res = door.submit({"kind": "bfs", "seed": 5000, "max_hops": 1})
    assert res["routed_to"] == "big-pod"
    # seed INSIDE both coverages → freshness wins again
    res = door.submit({"kind": "bfs", "seed": 7, "max_hops": 1})
    assert res["routed_to"] == "small-pod"
    # the router's own healthz surfaces the advertised coverage
    _, payload = door.health_probe()()
    assert payload["backends"]["small-pod"]["gid_capacity"] == 100
    assert payload["backends"]["big-pod"]["gid_capacity"] == 10_000
    door.stop()


def test_sharded_view_refreshes_across_compaction():
    """A compaction swap re-shards the base; the sharded pinned view
    must keep answering exactly (epoch re-check loop)."""
    g = HyperGraph()
    nodes, links = make_random_hypergraph(g, n_nodes=80, n_links=150,
                                          seed=6)
    rt = ServeRuntime(g, _cfg(sharded=True))
    try:
        r_before = rt.submit_bfs(int(nodes[1]), max_hops=2).result(
            timeout=120)
        epoch_before = r_before.epoch
        mgr = rt.executor.mgr
        # force a compaction by flooding the memtable past the ratio
        for i in range(40):
            g.add_link([nodes[i % 20], nodes[(i + 1) % 20]])
        mgr._request_compact()
        mgr.wait_compacted(timeout=30)
        r_after = rt.submit_bfs(int(nodes[1]), max_hops=2).result(
            timeout=120)
        assert r_after.epoch > epoch_before
        truth = set(int(h) for h in g.find_all(
            c.BFS(int(nodes[1]), max_distance=2))) | {int(nodes[1])}
        assert r_after.count == len(truth)
        assert r_after.served_by == "device"
    finally:
        rt.close()
        g.close()
