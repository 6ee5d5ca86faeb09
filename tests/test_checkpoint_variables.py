"""Checkpoint/export/import, subgraph copy, and parameterized queries."""

import numpy as np
import pytest

import hypergraphdb_tpu as hg
from hypergraphdb_tpu.ops.checkpoint import (
    copy_subgraph,
    export_graph,
    import_graph,
    load_snapshot,
    save_snapshot,
)
from hypergraphdb_tpu.query import dsl as q
from hypergraphdb_tpu.query.variables import prepare, substitute, var
from hypergraphdb_tpu.core.errors import QueryError

from conftest import make_random_hypergraph


# ---------------------------------------------------------------- snapshot ckpt


def test_snapshot_save_load_roundtrip(graph, tmp_path):
    make_random_hypergraph(graph, n_nodes=60, n_links=90, seed=5)
    snap = graph.snapshot()
    p = str(tmp_path / "snap.npz")
    save_snapshot(snap, p)
    back = load_snapshot(p)
    assert back.num_atoms == snap.num_atoms
    np.testing.assert_array_equal(back.inc_offsets, snap.inc_offsets)
    np.testing.assert_array_equal(back.inc_links, snap.inc_links)
    np.testing.assert_array_equal(back.value_rank, snap.value_rank)
    for k, v in snap.by_type.items():
        np.testing.assert_array_equal(back.by_type[k], v)
    # the reloaded snapshot serves kernels without a graph
    from hypergraphdb_tpu.ops.frontier import bfs_levels
    import jax.numpy as jnp

    seeds = jnp.asarray([0], dtype=jnp.int32)
    lv1, _ = bfs_levels(snap.device, seeds, 2)
    lv2, _ = bfs_levels(back.device, seeds, 2)
    np.testing.assert_array_equal(np.asarray(lv1), np.asarray(lv2))


# ---------------------------------------------------------------- logical dump


def test_export_import_roundtrip(graph, tmp_path):
    a = graph.add("alpha")
    b = graph.add(42)
    l = graph.add_link((a, b), value="edge")
    meta = graph.add_link((l,), value="meta")
    p = str(tmp_path / "dump.jsonl")
    n = export_graph(graph, p)
    assert n >= 4

    g2 = hg.HyperGraph()
    mapping = import_graph(g2, p)
    na, nb, nl = mapping[int(a)], mapping[int(b)], mapping[int(l)]
    assert g2.get(na) == "alpha"
    assert g2.get(nb) == 42
    assert g2.get(nl).targets == (na, nb)
    assert g2.get(mapping[int(meta)]).targets == (nl,)
    # queries work on the imported graph
    assert q.find_all(g2, q.value("edge")) == [nl]
    g2.close()


def test_copy_subgraph_closure(graph):
    a = graph.add("root")
    b = graph.add("reach")
    c = graph.add("unreached")
    lab = graph.add_link((a, b), value="ab")
    graph.add_link((c,), value="lonely")

    g2 = hg.HyperGraph()
    mapping = copy_subgraph(graph, g2, [int(a)])
    assert mapping[int(a)] is not None
    assert g2.get(mapping[int(b)]) == "reach"
    assert g2.get(mapping[int(lab)]).targets == (
        mapping[int(a)], mapping[int(b)]
    )
    assert int(c) not in mapping  # not reachable from a
    g2.close()


# ---------------------------------------------------------------- variables


def test_prepared_query_rebinds(graph):
    graph.add("hello")
    graph.add("world")
    pq = prepare(graph, q.and_(q.type_("string"), q.value(var("v"))))
    assert pq.variables == {"v"}
    r1 = pq.execute(v="hello")
    r2 = pq.execute(v="world")
    assert len(r1) == 1 and len(r2) == 1 and r1 != r2


def test_unbound_variable_raises(graph):
    pq = prepare(graph, q.value(var("x")))
    with pytest.raises(QueryError, match="unbound"):
        pq.execute()


def test_substitute_nested(graph):
    cond = q.or_(q.incident(var("t")), q.and_(q.value(var("v")), q.arity(2)))
    out = substitute(cond, {"t": 7, "v": "z"})
    assert out == q.or_(q.incident(7), q.and_(q.value("z"), q.arity(2)))


# ------------------------------------------- review regressions (round 4)


def test_var_in_link_targets(graph):
    a = graph.add("a")
    b = graph.add("b")
    l = graph.add_link((a, b))
    pq = prepare(graph, q.link(var("t"), int(b)))
    assert pq.execute(t=int(a)) == [int(l)]


def test_substitute_tree_with_link_and_var(graph):
    cond = q.and_(q.link(1, 2), q.value(var("v")))
    out = substitute(cond, {"v": "x"})
    assert out == q.and_(q.link(1, 2), q.value("x"))


def test_snapshot_path_without_extension(graph, tmp_path):
    graph.add("p")
    snap = graph.snapshot()
    p = str(tmp_path / "noext")
    save_snapshot(snap, p)
    back = load_snapshot(p)  # both sides normalize to .npz
    assert back.num_atoms == snap.num_atoms


def test_plans_persist_with_snapshot(tmp_path, graph):
    """save_snapshot(with_plans=True) writes a sidecar the loader attaches,
    and the restored plans drive bit-identical BFS results."""
    import numpy as np

    from tests.conftest import make_random_hypergraph
    from hypergraphdb_tpu.ops import checkpoint as cp
    from hypergraphdb_tpu.ops.ellbfs import bfs_pull, plans_for

    make_random_hypergraph(graph, n_nodes=150, n_links=300, seed=11)
    snap = graph.snapshot()
    path = str(tmp_path / "snap.npz")
    cp.save_snapshot(snap, path, with_plans=True)
    loaded = cp.load_snapshot(path)
    assert getattr(loaded, "_pull_plans", None) is not None  # no rebuild
    seeds = np.arange(24, dtype=np.int32)
    a = bfs_pull(snap, seeds, 3)
    b = bfs_pull(loaded, seeds, 3)
    assert np.array_equal(a.edges_touched, b.edges_touched)
    assert np.array_equal(np.asarray(a.visited_t), np.asarray(b.visited_t))
    # plan pyramids round-trip exactly
    p0, p1 = plans_for(snap), loaded._pull_plans
    assert p0.stage2_widths == p1.stage2_widths
    for x, y in zip(p0.stage1.levels, p1.stage1.levels):
        assert np.array_equal(x, y)
    assert np.array_equal(p0.out_map, p1.out_map)
    # the classes too: which levels are level 0, at which widths
    assert p0.stage1.n_lvl0 == p1.stage1.n_lvl0 > 1
    assert p0.stage2_n_lvl0 == p1.stage2_n_lvl0 > 1
    assert p0.stage1.widths == p1.stage1.widths
    assert len(p0.stage2_levels) == len(p1.stage2_levels)
    for x, y in zip(p0.stage2_levels, p1.stage2_levels):
        assert np.array_equal(x, y)
    assert np.array_equal(p0.stage1.out_map, p1.stage1.out_map)
    assert (p0.total_indices, p0.upper_indices) == \
        (p1.total_indices, p1.upper_indices)


# ------------------------------------------- crash-atomic saves (hgfault)


@pytest.fixture
def faults():
    from hypergraphdb_tpu.fault import global_faults

    f = global_faults()
    f.reset()
    yield f
    f.reset()
    f.disable()


def _two_snapshots(graph):
    make_random_hypergraph(graph, n_nodes=40, n_links=60, seed=3)
    snap_a = graph.snapshot()
    for i in range(25):
        graph.add(f"extra-{i}")
    snap_b = graph.snapshot()
    assert snap_b.num_atoms > snap_a.num_atoms
    return snap_a, snap_b


def test_crash_mid_npz_save_previous_checkpoint_survives(graph, tmp_path,
                                                         faults):
    from hypergraphdb_tpu.fault import InjectedCrash

    snap_a, snap_b = _two_snapshots(graph)
    p = str(tmp_path / "snap.npz")
    save_snapshot(snap_a, p)
    faults.enable(seed=0)
    faults.arm("ckpt.save_npz", at={1}, error=InjectedCrash)
    with pytest.raises(InjectedCrash):
        save_snapshot(snap_b, p)
    # the "kill" happened after the tmp write, before publish: the
    # previous checkpoint is fully loadable, never a torn file
    back = load_snapshot(p)
    assert back.num_atoms == snap_a.num_atoms
    np.testing.assert_array_equal(back.inc_offsets, snap_a.inc_offsets)
    # once the schedule clears, the next save publishes normally
    save_snapshot(snap_b, p)
    assert load_snapshot(p).num_atoms == snap_b.num_atoms


def test_crash_mid_plans_save_leaves_loadable_state(graph, tmp_path,
                                                    faults):
    from hypergraphdb_tpu.fault import InjectedCrash
    from hypergraphdb_tpu.ops.checkpoint import _plans_path

    snap_a, snap_b = _two_snapshots(graph)
    p = str(tmp_path / "snap.npz")
    save_snapshot(snap_a, p, with_plans=True)
    faults.enable(seed=0)
    faults.arm("ckpt.save_plans", at={1}, error=InjectedCrash)
    with pytest.raises(InjectedCrash):
        save_snapshot(snap_b, p, with_plans=True)
    # npz published (B), sidecar still A's: the fingerprint mismatch is
    # the DESIGNED stale shape — load succeeds, plans rebuild quietly
    back = load_snapshot(p)
    assert back.num_atoms == snap_b.num_atoms
    assert getattr(back, "_pull_plans", None) is None
    import os

    assert os.path.exists(_plans_path(p))  # old sidecar intact on disk
    save_snapshot(snap_b, p, with_plans=True)
    assert getattr(load_snapshot(p), "_pull_plans", None) is not None


def test_ordinary_save_failure_cleans_tmp(graph, tmp_path, faults):
    from hypergraphdb_tpu.fault import PermanentFault

    snap_a, snap_b = _two_snapshots(graph)
    p = str(tmp_path / "snap.npz")
    save_snapshot(snap_a, p)
    import os

    # a real (non-crash) failure between write and publish cleans up: the
    # Exception path unlinks the tmp, the BaseException crash path leaves
    # it (like a real kill would) — test the crash side leaves tmp behind
    from hypergraphdb_tpu.fault import InjectedCrash

    faults.enable(seed=0)
    faults.arm("ckpt.save_npz", at={1}, error=InjectedCrash)
    with pytest.raises(InjectedCrash):
        save_snapshot(snap_b, p)
    assert os.path.exists(p + ".tmp")
    faults.disarm("ckpt.save_npz")
    save_snapshot(snap_b, p)          # next save overwrites + publishes
    assert not os.path.exists(p + ".tmp")
    assert load_snapshot(p).num_atoms == snap_b.num_atoms
    with pytest.raises(PermanentFault):  # Exception path: tmp cleaned
        faults.arm("ckpt.save_npz", at={1}, error=PermanentFault)
        save_snapshot(snap_a, p)
    assert not os.path.exists(p + ".tmp")


def test_stale_sidecar_rebuilds_quietly_corrupt_sidecar_counts(
        graph, tmp_path, faults):
    """The load_snapshot triage: fingerprint mismatch (stale by design) is
    silent; an unreadable sidecar logs + bumps fault.sidecar_corrupt."""
    from hypergraphdb_tpu.ops.checkpoint import _plans_path
    from hypergraphdb_tpu.utils.metrics import global_metrics

    snap_a, snap_b = _two_snapshots(graph)
    pa_ = str(tmp_path / "a.npz")
    pb_ = str(tmp_path / "b.npz")
    save_snapshot(snap_a, pa_, with_plans=True)
    save_snapshot(snap_b, pb_, with_plans=True)

    c = global_metrics.registry.counter("fault.sidecar_corrupt")
    before = c.value

    # stale: b's npz with a's plans → quiet rebuild, counter untouched
    import shutil

    shutil.copyfile(_plans_path(pa_), _plans_path(pb_))
    back = load_snapshot(pb_)
    assert back.num_atoms == snap_b.num_atoms
    assert getattr(back, "_pull_plans", None) is None
    assert c.value == before

    # corrupt: garbage bytes → logged warning + counter, load still fine
    with open(_plans_path(pb_), "wb") as f:
        f.write(b"this is not an npz file at all")
    back = load_snapshot(pb_)
    assert back.num_atoms == snap_b.num_atoms
    assert getattr(back, "_pull_plans", None) is None
    assert c.value == before + 1


@pytest.mark.parametrize("sidecar_format", [1, 3])
def test_sidecar_of_another_plan_format_rebuilds_quietly(
        graph, tmp_path, faults, sidecar_format):
    """A sidecar written before level 0 had width classes (format 1: one
    width-8 array a stage, no ``n_lvl0``), or by a later layout, beside a
    checkpoint: ``StalePlans`` — no plan attached, ``plans_for`` rebuilds,
    ``fault.sidecar_corrupt`` untouched — whatever its arrays hold."""
    import numpy as np

    from hypergraphdb_tpu.ops import ellbfs as E
    from hypergraphdb_tpu.ops.checkpoint import _plans_path
    from hypergraphdb_tpu.utils.metrics import global_metrics

    assert E.PLAN_FORMAT == 2
    snap, _ = _two_snapshots(graph)
    path = str(tmp_path / "a.npz")
    save_snapshot(snap, path, with_plans=True)
    with np.load(_plans_path(path)) as z:
        arrs = {k: z[k] for k in z.files}
    arrs["format"] = np.int64(sidecar_format)
    if sidecar_format == 1:  # as PR 29's save_plans wrote it
        for k in ("s1_n_lvl0", "s2_n_lvl0"):
            del arrs[k]
    with open(_plans_path(path), "wb") as f:
        np.savez(f, **arrs)
    with pytest.raises(E.StalePlans, match="format"):
        E.load_plans(_plans_path(path))

    c = global_metrics.registry.counter("fault.sidecar_corrupt")
    before = c.value
    back = load_snapshot(path)
    assert getattr(back, "_pull_plans", None) is None
    assert c.value == before
    rebuilt = E.plans_for(back)
    assert rebuilt.stage1.n_lvl0 == E.plans_for(snap).stage1.n_lvl0
    assert np.array_equal(rebuilt.out_map, E.plans_for(snap).out_map)


def test_plan_cache_env_roundtrip(tmp_path, graph, monkeypatch):
    import numpy as np

    from tests.conftest import make_random_hypergraph
    from hypergraphdb_tpu.ops import ellbfs as E

    make_random_hypergraph(graph, n_nodes=100, n_links=200, seed=5)
    snap = graph.snapshot()
    monkeypatch.setenv("HG_PLAN_CACHE", str(tmp_path / "plancache"))
    p0 = E.plans_for(snap)
    # a content-identical snapshot hits the disk cache, not the builder
    snap2 = graph.snapshot()
    calls = []
    monkeypatch.setattr(E, "build_pull_plans",
                        lambda *a, **k: calls.append(1))
    p1 = E.plans_for(snap2)
    assert not calls  # loaded, not rebuilt
    assert np.array_equal(p0.out_map, p1.out_map)
    for x, y in zip(p0.stage2_levels, p1.stage2_levels):
        assert np.array_equal(x, y)
