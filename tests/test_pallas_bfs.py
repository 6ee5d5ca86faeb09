"""Fused Pallas pull-BFS megakernel + AOT compile cache.

Differential contract: the fused kernel (``ops/pallas_bfs``, run through
the Pallas interpreter on CPU — same grid/DMA/semaphore program, real
Mosaic needs a TPU) must equal the unfused ``ellbfs.bfs_pull`` chain and
the dense ``bfs_serve_batch`` sweep bit for bit: visited sets, reach
counts, truncation prefixes, pad-lane garbage included. Plus the AOT
cache lifecycle: cold miss → persist → warm hit → fingerprint/version
mismatch → quiet rebuild, corrupt file → warning + rebuild.
"""

from __future__ import annotations

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypergraphdb_tpu.ops import pallas_bfs as pb
from hypergraphdb_tpu.ops.ellbfs import bfs_pull, visited_rows
from tests.conftest import make_random_hypergraph


def _fused_pull(snap, seeds, hops, count_edges=True):
    """bfs_pull_fused with bfs_pull's seed padding applied."""
    seeds = np.asarray(seeds, dtype=np.int32)
    K = len(seeds)
    K_pad = -(-max(K, 32) // 32) * 32
    if K_pad != K:
        seeds = np.concatenate(
            [seeds, np.full(K_pad - K, snap.num_atoms, np.int32)]
        )
    vt, s_ins, reach = pb.bfs_pull_fused(snap, seeds, hops,
                                         count_edges=count_edges,
                                         interpret=True)
    return vt, s_ins, np.asarray(reach)[:K], K


# ------------------------------------------------------- fused vs unfused


@pytest.mark.parametrize("hops", [1, 3])
@pytest.mark.parametrize("k", [40, 64])
def test_fused_matches_unfused_chain(graph, hops, k):
    make_random_hypergraph(graph, n_nodes=150, n_links=300, seed=7)
    snap = graph.snapshot()
    r = np.random.default_rng(3)
    seeds = r.integers(0, snap.num_atoms, size=k).astype(np.int32)

    ref = bfs_pull(snap, seeds, hops, k_block=64)
    vt, s_ins, reach, K = _fused_pull(snap, seeds, hops)

    rvt = np.asarray(ref.visited_t)
    assert np.array_equal(np.asarray(vt)[: rvt.shape[0], : rvt.shape[1]],
                          rvt)
    assert np.array_equal(
        np.asarray(s_ins[-1]).astype(np.int64)[:k], ref.edges_touched
    )
    assert np.array_equal(reach[:k], np.asarray(ref.reach_counts))
    # per-seed reachable sets decode identically
    for a, b in zip(visited_rows(ref, snap.num_atoms)[:8],
                    _rows_of(vt, snap.num_atoms)[:8]):
        assert np.array_equal(a, b)


def _rows_of(vt, n_atoms):
    from hypergraphdb_tpu.ops.ellbfs import PullBFSResult

    return visited_rows(
        PullBFSResult(vt, np.zeros(1, np.int64), None), n_atoms
    )


def test_fused_duplicate_and_pad_seeds(graph):
    """Duplicate seeds OR into the same lanes' bits independently; pad
    seeds (dummy row) reach nothing and count zero — bfs_pull contract."""
    make_random_hypergraph(graph, n_nodes=80, n_links=160, seed=1)
    snap = graph.snapshot()
    seeds = np.asarray([5, 5, 5, 17], dtype=np.int32)
    ref = bfs_pull(snap, seeds, 2, k_block=32)
    vt, s_ins, reach, _ = _fused_pull(snap, seeds, 2)
    assert np.array_equal(reach[:4], np.asarray(ref.reach_counts))
    assert reach[0] == reach[1] == reach[2]
    # the pad lanes past K are all-zero
    assert int(np.asarray(reach)[4:].sum()) == 0 if len(reach) > 4 else True


def test_fused_empty_frontier(graph):
    """Every seed = the dummy row: zero reach, zero edges, empty bitmap."""
    make_random_hypergraph(graph, n_nodes=60, n_links=120, seed=2)
    snap = graph.snapshot()
    seeds = np.full(32, snap.num_atoms, np.int32)
    ref = bfs_pull(snap, seeds, 2, k_block=32)
    vt, s_ins, reach, _ = _fused_pull(snap, seeds, 2)
    assert int(np.asarray(vt).sum()) == 0
    assert np.array_equal(reach, np.asarray(ref.reach_counts))
    assert int(np.asarray(s_ins[-1]).sum()) == 0


def test_fused_multi_segment_scan(graph, monkeypatch):
    """Shrink SEG_BLOCKS so the per-hop lax.scan over segment
    pallas_calls runs in-test (big graphs hit this path for real)."""
    monkeypatch.setattr(pb, "SEG_BLOCKS", 4)
    make_random_hypergraph(graph, n_nodes=120, n_links=240, seed=4)
    snap = graph.snapshot()
    plan = pb.fused_plans_for(snap)
    assert plan.geom.n_seg > 1
    r = np.random.default_rng(0)
    seeds = r.integers(0, snap.num_atoms, size=32).astype(np.int32)
    ref = bfs_pull(snap, seeds, 3, k_block=32)
    vt, _, reach, _ = _fused_pull(snap, seeds, 3)
    rvt = np.asarray(ref.visited_t)
    assert np.array_equal(np.asarray(vt)[: rvt.shape[0], : rvt.shape[1]],
                          rvt)
    assert np.array_equal(reach[:32], np.asarray(ref.reach_counts))


def test_fused_count_edges_off(graph):
    make_random_hypergraph(graph, n_nodes=50, n_links=100, seed=6)
    snap = graph.snapshot()
    seeds = np.arange(32, dtype=np.int32)
    vt, s_ins, reach, _ = _fused_pull(snap, seeds, 2, count_edges=False)
    assert s_ins == [] or len(s_ins) == 0
    ref = bfs_pull(snap, seeds, 2, k_block=32, count_edges=False)
    assert np.array_equal(reach[:32], np.asarray(ref.reach_counts))


# ------------------------------------------------------- serve differential


def _serve_fused(base, delta, seeds_d, hops, top_r, bucket):
    from hypergraphdb_tpu.ops.serving import bfs_serve_batch_fused

    kw = pb.serve_fused_kwargs(base, delta, bucket)
    assert kw is not None
    return bfs_serve_batch_fused(
        kw["fused"], seeds_d, kw["n_atoms"], geom=kw["geom"],
        kwp=kw["kwp"], max_hops=hops, top_r=top_r,
        overlay=kw["overlay"], widths1=kw["widths1"],
        widths2=kw["widths2"], interpret=True,
    )


@pytest.mark.parametrize("bucket", [64, 256])
def test_serve_fused_matches_dense_bucket_shapes(graph, bucket):
    """Whole-batch parity, pad lanes included (the runtime's
    well-defined-garbage contract), across serve bucket widths."""
    from hypergraphdb_tpu.ops.serving import bfs_serve_batch

    make_random_hypergraph(graph, n_nodes=90, n_links=180, seed=8)
    mgr = graph.enable_incremental()
    dev, delta = mgr.device()
    n = mgr.base.num_atoms
    r = np.random.default_rng(5)
    seeds = np.full(bucket, n, np.int32)
    live = min(bucket - 3, 50)
    seeds[:live] = r.integers(0, 90, size=live)
    seeds_d = jnp.asarray(seeds)
    top_r = 9

    c_ref, f_ref = bfs_serve_batch(dev, delta, seeds_d, 2, top_r)
    c_f, f_f = _serve_fused(mgr.base, delta, seeds_d, 2, top_r, bucket)
    assert np.array_equal(np.asarray(c_ref), np.asarray(c_f))
    assert np.array_equal(np.asarray(f_ref), np.asarray(f_f))
    # truncation prefixes: some live seed must have count > top_r for the
    # prefix contract to be exercised at all
    assert (np.asarray(c_ref)[:live] > top_r).any()


def test_first_r_top_r_beyond_row_block():
    """``top_r`` wider than the 4096-row streaming block (a config the
    dense path serves fine) must not over-ask the per-block top_k — the
    block contributes at most its own row count of candidates, and the
    merge still yields the global ``top_r`` prefix."""
    from hypergraphdb_tpu.ops.setops import SENTINEL

    R, K, top_r, n1 = 8200, 32, 4100, 8000
    r = np.random.default_rng(2)
    vis = np.zeros((R, 1), np.uint32)
    rows0 = np.unique(r.integers(0, n1, size=7000))  # > top_r reached
    assert len(rows0) > top_r
    vis[rows0, 0] |= 1
    vis[[5, 4097, 8100], 0] |= 2  # seed 1: one row past n1 (masked)
    out = np.asarray(pb.first_r_from_bitmap(
        jnp.asarray(vis), jnp.int32(n1), top_r, K
    ))
    assert out.shape == (K, top_r)
    assert np.array_equal(out[0], rows0[:top_r])  # truncated prefix
    assert np.array_equal(out[1][:2], [5, 4097])
    assert (out[1][2:] == SENTINEL).all()         # 8100 >= n1 masked out
    assert (out[2:] == SENTINEL).all()


def test_serve_fused_delta_overlay_path(graph):
    """The delta-overlay path used by ``bfs_serve_batch``: fresh links in
    the memtable must flow through the fused kernel's overlay plan with
    exact parity against the dense base∪delta sweep."""
    from hypergraphdb_tpu.ops.serving import bfs_serve_batch

    make_random_hypergraph(graph, n_nodes=100, n_links=150, seed=12)
    mgr = graph.enable_incremental()
    r = np.random.default_rng(9)
    # delta: new links bridging previously-unlinked node pairs
    for i in range(40):
        a, b = int(r.integers(0, 50)), int(r.integers(50, 100))
        graph.add_link([a, b], value=f"delta{i}")
    dev, delta = mgr.device()
    assert int(np.asarray(delta.inc_links).min()) < mgr.base.num_atoms

    seeds = np.full(64, mgr.base.num_atoms, np.int32)
    seeds[:48] = r.integers(0, 100, size=48)
    seeds_d = jnp.asarray(seeds)
    for hops in (1, 3):
        c_ref, f_ref = bfs_serve_batch(dev, delta, seeds_d, hops, 7)
        c_f, f_f = _serve_fused(mgr.base, delta, seeds_d, hops, 7, 64)
        assert np.array_equal(np.asarray(c_ref), np.asarray(c_f)), hops
        assert np.array_equal(np.asarray(f_ref), np.asarray(f_f)), hops


def test_serve_fused_declines_without_breaking(graph, monkeypatch):
    """Gate behavior the runtime relies on: off-TPU the backend gate is
    False from the platform (fallback exercised by the whole serve
    suite), and a pinned view with tombstones is refused by the executor
    gate."""
    from hypergraphdb_tpu.serve import ServeConfig
    from hypergraphdb_tpu.serve.runtime import DeviceExecutor

    assert jax.default_backend() == "cpu"
    assert pb.pallas_bfs_ok() is False

    make_random_hypergraph(graph, n_nodes=40, n_links=80, seed=3)
    ex = DeviceExecutor(graph, ServeConfig(manual=True))
    view = ex.mgr.pinned_view()
    assert ex._fused_bfs_kwargs(view, 64) is None  # backend gate
    # force the backend gate open; the tombstone gate must still decline
    monkeypatch.setattr(pb, "pallas_bfs_ok", lambda: True)
    view2 = view._replace(dead={5})
    assert ex._fused_bfs_kwargs(view2, 64) is None
    # and with the gates open the kwargs bundle materializes
    assert ex._fused_bfs_kwargs(view, 64) is not None


@pytest.mark.parametrize("mod,ok", [("pallas_bfs", "pallas_bfs_ok"),
                                    ("pallas_gather", "pallas_ok")])
def test_probe_failure_on_tpu_raises(monkeypatch, mod, ok):
    """On a TPU backend a kernel the chip refuses must RAISE out of the
    gate (and keep raising: a failed probe is never cached as a quiet
    False); off-TPU the gate is False from the platform alone."""
    import importlib

    m = importlib.import_module(f"hypergraphdb_tpu.ops.{mod}")
    gate = getattr(m, ok)
    assert gate() is False                      # cpu: platform says no
    monkeypatch.setattr(m.jax, "default_backend", lambda: "tpu")

    def refused(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    # the real kernel entry cannot run here; make it fail the way a
    # refusing compiler does
    monkeypatch.setattr(m, "_hop_call" if mod == "pallas_bfs"
                        else "gather_or", refused)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="Mosaic"):
            gate()
    monkeypatch.setenv("HG_PALLAS_BFS" if mod == "pallas_bfs"
                       else "HG_PALLAS_GATHER", "0")
    assert gate() is False                      # the veto still wins


def test_wide_rows_decline_with_a_reason(graph):
    """Rows wider than 128 words are refused by the v5e compiler (kept
    as a test in tests/test_tpu_compile.py), so BOTH gates must decline
    them with a reason instead of admitting a MosaicError."""
    from hypergraphdb_tpu.ops import pallas_gather as pg

    make_random_hypergraph(graph, n_nodes=40, n_links=80, seed=3)
    snap = graph.snapshot()
    assert pb.plan_supported(snap, 4096) is None
    assert "128" in pb.plan_supported(snap, 8192)
    assert pg.declined(8, 128) is None
    assert "128" in pg.declined(8, 256)
    with pytest.raises(ValueError, match="128"):
        pg.gather_or(jnp.zeros((8, 256), jnp.uint32),
                     jnp.zeros((16,), jnp.int32), 8, interpret=True)


def test_plan_supported_reports_budget_overflow(graph, monkeypatch):
    """A hub row too wide for the SMEM window declines with a reason —
    the window math hglint HG5xx models, enforced at runtime."""
    make_random_hypergraph(graph, n_nodes=60, n_links=120, seed=10)
    snap = graph.snapshot()
    assert pb.plan_supported(snap, 64) is None
    monkeypatch.setattr(pb, "SMEM_BUDGET", 64)  # absurdly small
    assert "SMEM" in pb.plan_supported(snap, 64)
    assert pb.fused_ready(snap, 64) is False


def test_hub_decline_skips_adjacency_materialization(graph):
    """A hub whose composed adjacency blows the SMEM window declines
    BEFORE the O(composition) flat index array is built (review fix:
    a 40 GB np.full on a hub-heavy graph would be a regression vs the
    staged chain), and bfs_pull still serves via the fallback."""
    nodes = list(graph.add_nodes_bulk([f"h{i}" for i in range(520)]))
    # one 500-ary link: every target's fused row is 500 wide → the
    # segment chunk cap overflows half the 1 MB SMEM budget
    graph.add_link([int(n) for n in nodes[:500]], value="hub")
    snap = graph.snapshot()
    plan = pb.fused_plans_for(snap)
    assert plan.blk_off.shape[0] == 0 and plan.idx.size == 0  # no build
    assert not plan.smem_ok
    assert "SMEM" in pb.plan_supported(snap, 64)
    assert pb.fused_ready(snap, 64) is False
    with pytest.raises(ValueError, match="declined"):
        pb.device_fused_plan(snap)
    res = bfs_pull(snap, np.asarray([int(nodes[0])], np.int32), 2)
    assert int(np.asarray(res.reach_counts)[0]) >= 500


def test_fused_traffic_model_counts_real_entries(graph):
    make_random_hypergraph(graph, n_nodes=50, n_links=100, seed=0)
    snap = graph.snapshot()
    geom = pb.fused_plans_for(snap).geom
    per_hop = pb.fused_bytes_per_hop(geom, 4096)
    assert per_hop > geom.total_entries * 512  # gathered 512-byte rows
    assert geom.total_entries > 0


# ----------------------------------------------------------- aot lifecycle


@pytest.fixture
def jit_fn():
    return jax.jit(lambda x, n: x * n + 1, static_argnames=("n",))


def test_aot_cache_lifecycle(tmp_path, jit_fn):
    """cold miss → persist → warm hit → fingerprint mismatch → quiet
    rebuild → version mismatch → quiet rebuild → corrupt → warn+rebuild."""
    from hypergraphdb_tpu.ops import aot_cache as ac

    args = (jnp.zeros((16,), jnp.float32),)
    statics = {"n": 2}

    c1 = ac.AOTCache(root=str(tmp_path), content_key="fp-a")
    comp = c1.get_or_compile("t.mul", jit_fn, args, statics)
    assert float(comp(jnp.ones((16,), jnp.float32))[0]) == 3.0
    assert c1.stats.misses == 1 and c1.stats.puts == 1

    # same process: memory hit; fresh cache object: disk hit (no compile)
    c1.get_or_compile("t.mul", jit_fn, args, statics)
    assert c1.stats.mem_hits == 1
    c2 = ac.AOTCache(root=str(tmp_path), content_key="fp-a")
    comp2 = c2.get_or_compile("t.mul", jit_fn, args, statics)
    assert c2.stats.disk_hits == 1 and c2.stats.misses == 0
    assert float(comp2(jnp.full((16,), 2.0))[0]) == 5.0

    # fingerprint mismatch at the SAME file path → StaleEntry → quiet
    # rebuild (simulated by planting fp-b's blob under fp-a's key)
    cb = ac.AOTCache(root=str(tmp_path), content_key="fp-b")
    cb.get_or_compile("t.mul", jit_fn, args, statics)
    import os

    key_a = c2.key_for("t.mul", args, statics)
    key_b = cb.key_for("t.mul", args, statics)
    os.replace(cb._path(key_b), c2._path(key_a))
    c3 = ac.AOTCache(root=str(tmp_path), content_key="fp-a")
    c3.get_or_compile("t.mul", jit_fn, args, statics)
    assert c3.stats.stale == 1 and c3.stats.misses == 1

    # format-version mismatch is stale too
    import json as _json

    path = c3._path(key_a)
    with open(path, "rb") as f:
        magic = f.read(len(ac._MAGIC))
        header = _json.loads(f.readline())
        rest = f.read()
    header["format"] = ac.FORMAT + 1
    with open(path, "wb") as f:
        f.write(magic + (_json.dumps(header) + "\n").encode() + rest)
    c4 = ac.AOTCache(root=str(tmp_path), content_key="fp-a")
    c4.get_or_compile("t.mul", jit_fn, args, statics)
    assert c4.stats.stale == 1

    # corrupt file → warning + rebuild; next cache instance hits again
    with open(path, "wb") as f:
        f.write(b"\x00 not an aot entry")
    c5 = ac.AOTCache(root=str(tmp_path), content_key="fp-a")
    c5.get_or_compile("t.mul", jit_fn, args, statics)
    assert c5.stats.corrupt == 1 and c5.stats.puts == 1
    c6 = ac.AOTCache(root=str(tmp_path), content_key="fp-a")
    c6.get_or_compile("t.mul", jit_fn, args, statics)
    assert c6.stats.hits == 1 and c6.stats.misses == 0


def test_aot_cache_corrupt_logs_warning(tmp_path, jit_fn, caplog):
    import logging

    from hypergraphdb_tpu.ops import aot_cache as ac

    args = (jnp.zeros((4,), jnp.float32),)
    c = ac.AOTCache(root=str(tmp_path))
    c.get_or_compile("t.x", jit_fn, args, {"n": 1})
    path = c._path(c.key_for("t.x", args, {"n": 1}))
    with open(path, "wb") as f:
        f.write(b"junk")
    with caplog.at_level(logging.WARNING, "hypergraphdb_tpu.aot"):
        ac.AOTCache(root=str(tmp_path)).get_or_compile(
            "t.x", jit_fn, args, {"n": 1}
        )
    assert any("rebuilding" in r.message for r in caplog.records)


def test_aot_gc_sweeps_superseded_generations(tmp_path, jit_fn):
    """ROADMAP 4f: the open-time sweep deletes entries whose header
    content_key is a SUPERSEDED generation once past the age bound; the
    current generation is never touched (the prewarm relies on it)."""
    import os
    import time as _time

    from hypergraphdb_tpu.ops import aot_cache as ac

    args = (jnp.zeros((16,), jnp.float32),)
    old = ac.AOTCache(root=str(tmp_path), content_key="gen-old")
    old.get_or_compile("t.mul", jit_fn, args, {"n": 2})
    old.get_or_compile("t.mul", jit_fn, args, {"n": 3})
    cur = ac.AOTCache(root=str(tmp_path), content_key="gen-new",
                      gc_max_age_s=None)          # no sweep at open
    cur.get_or_compile("t.mul", jit_fn, args, {"n": 2})

    def aot_files():
        return [f for f in os.listdir(cur.dir) if f.endswith(".aot")]

    assert len(aot_files()) == 3
    # young superseded entries survive a lenient sweep...
    cur.gc_max_age_s = 3600.0
    assert cur.gc(now=_time.time() + 1.0) == 0
    # ...and go once older than the bound — current generation stays
    assert cur.gc(now=_time.time() + 2 * 3600.0) == 2
    assert cur.stats.gc_removed == 2
    assert len(aot_files()) == 1
    # the survivor really is the current generation: a fresh open (the
    # default sweep runs) still disk-hits without a compile
    c2 = ac.AOTCache(root=str(tmp_path), content_key="gen-new")
    c2.get_or_compile("t.mul", jit_fn, args, {"n": 2})
    assert c2.stats.disk_hits == 1 and c2.stats.misses == 0


def test_aot_gc_size_bound_and_tmp_leftovers(tmp_path, jit_fn):
    """The size bound deletes oldest-superseded-first even when young,
    never the current generation; abandoned ``*.tmp.*`` writer leftovers
    go once past the age bound."""
    import os
    import time as _time

    from hypergraphdb_tpu.ops import aot_cache as ac

    args = (jnp.zeros((16,), jnp.float32),)
    old = ac.AOTCache(root=str(tmp_path), content_key="gen-old")
    for n in (2, 3, 4):
        old.get_or_compile("t.mul", jit_fn, args, {"n": n})
    cur = ac.AOTCache(root=str(tmp_path), content_key="gen-new",
                      gc_max_age_s=None)
    cur.get_or_compile("t.mul", jit_fn, args, {"n": 2})
    leftover = os.path.join(cur.dir, "deadbeef.aot.tmp.123")
    with open(leftover, "wb") as f:
        f.write(b"crashed writer leftover")

    cur.gc_max_age_s = 3600.0
    cur.gc_max_bytes = 1                    # force over-budget
    assert cur.gc(now=_time.time() + 1.0) == 3   # young, but over budget
    survivors = [f for f in os.listdir(cur.dir) if f.endswith(".aot")]
    assert survivors and all(
        cur._entry_content_key(os.path.join(cur.dir, f)) == "gen-new"
        for f in survivors
    )
    # the young tmp leftover survived; past the age bound it goes too
    assert os.path.exists(leftover)
    assert cur.gc(now=_time.time() + 2 * 3600.0) == 1
    assert not os.path.exists(leftover)


def test_aot_key_separates_shapes_and_statics(tmp_path, jit_fn):
    from hypergraphdb_tpu.ops import aot_cache as ac

    c = ac.AOTCache(root=str(tmp_path))
    k1 = c.key_for("e", (jnp.zeros((4,), jnp.float32),), {"n": 2})
    k2 = c.key_for("e", (jnp.zeros((8,), jnp.float32),), {"n": 2})
    k3 = c.key_for("e", (jnp.zeros((4,), jnp.float32),), {"n": 3})
    assert len({k1, k2, k3}) == 3


def test_serve_runtime_warm_start_skips_compiles(graph, tmp_path):
    """Acceptance: a fresh ServeRuntime over a populated AOT cache
    reaches first dispatch without recompiling the warmed buckets —
    asserted via the cache-hit counters."""
    from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

    make_random_hypergraph(graph, n_nodes=60, n_links=120, seed=5)
    cfg = dict(buckets=(4, 8), max_linger_s=0.001, top_r=8,
               aot_cache_dir=str(tmp_path), prewarm_hops=(2, 3),
               prewarm_pattern_arities=(1, 2))
    rt1 = ServeRuntime(graph, ServeConfig(**cfg))
    r1 = rt1.submit_bfs(3, max_hops=2).result(timeout=60)
    p1 = rt1.submit_pattern([3]).result(timeout=60)
    cold = rt1.stats_snapshot()["aot"]
    rt1.close()
    # 2 buckets x (2 hops + 2 pattern arities)
    assert cold["misses"] >= 8 and cold["puts"] >= 8

    rt2 = ServeRuntime(graph, ServeConfig(**cfg))
    r2 = rt2.submit_bfs(3, max_hops=2).result(timeout=60)
    # a NON-default hops the config declared must be warm too — the
    # dispatch thread never compiles for any (bucket, hops) in the plan
    rt2.submit_bfs(3, max_hops=3).result(timeout=60)
    # the pattern lane (ROADMAP 4d): first dispatch of BOTH warmed
    # anchor arities must be compile-free too
    p2 = rt2.submit_pattern([3]).result(timeout=60)
    rt2.submit_pattern([3, 5]).result(timeout=60)
    warm = rt2.stats_snapshot()["aot"]
    rt2.close()
    assert warm["misses"] == 0, warm
    assert warm["disk_hits"] >= 8 and warm["hits"] >= 8, warm
    assert r1.count == r2.count and np.array_equal(r1.matches, r2.matches)
    assert p1.count == p2.count and np.array_equal(p1.matches, p2.matches)


def test_aot_dispatch_results_match_plain_jit(graph, tmp_path):
    """The compiled-executable dispatch path returns exactly what the
    plain jitted call returns (same kernels, same pinned view)."""
    from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

    make_random_hypergraph(graph, n_nodes=70, n_links=140, seed=6)
    res = {}
    for dir_ in (str(tmp_path), None):
        cfg = ServeConfig(buckets=(4,), max_linger_s=0.001, top_r=8,
                          aot_cache_dir=dir_, prewarm_aot=dir_ is not None)
        rt = ServeRuntime(graph, cfg)
        res[dir_] = rt.submit_bfs(7, max_hops=2).result(timeout=60)
        rt.close()
    a, b = res.values()
    assert a.count == b.count and np.array_equal(a.matches, b.matches)


def test_aot_gc_disabled_by_none_is_inert(tmp_path, jit_fn):
    """``gc_max_age_s=None`` is the documented off switch: a MANUAL
    ``gc()`` must be a no-op too — reading None as age 0 would delete
    every superseded entry and any tmp a concurrent writer is
    mid-writing."""
    import os

    from hypergraphdb_tpu.ops import aot_cache as ac

    args = (jnp.zeros((16,), jnp.float32),)
    old = ac.AOTCache(root=str(tmp_path), content_key="gen-old")
    old.get_or_compile("t.mul", jit_fn, args, {"n": 2})
    cur = ac.AOTCache(root=str(tmp_path), content_key="gen-new",
                      gc_max_age_s=None)
    with open(os.path.join(cur.dir, "w.tmp.123"), "wb") as f:
        f.write(b"half-written")
    assert cur.gc() == 0
    names = set(os.listdir(cur.dir))
    assert "w.tmp.123" in names
    assert any(n.endswith(".aot") for n in names)
