"""AOT compile cache (``ops/aot_cache``) and the serving runtime's use of it.

The cache lifecycle: cold miss → persist → warm hit → fingerprint/version
mismatch → quiet rebuild, corrupt file → warning + rebuild; the open-time
sweep of superseded generations; and a fresh ``ServeRuntime`` over a
populated cache reaching first dispatch without a compile.
"""

from __future__ import annotations

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from tests.conftest import make_random_hypergraph


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
