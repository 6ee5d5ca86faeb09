"""``obs.phase`` and the names it and the traversal path give their work.

One primitive for a coarse, synced host step (``obs/device.py``): seconds in
the default registry always, a ``TraceAnnotation`` while a profile session
is open, a child span under the thread's current trace. The staged BFS
(``ops/ellbfs.py``) wires it per hop, names its device operations with
``jax.named_scope`` and its eight programs' XLA modules ``jit_hg_bfs_*``;
``benchmarks/harness/scope_reduce.py`` reads those scopes back out of a
profiler trace. ``PERF.md`` section 3 lists every name and its reader.

Since PR 36 every phase INSTANCE also leaves a record in ``obs.phase_log()``
(who called it, steps, thread CPU, JAX's trace / lower / compile / load
seconds, a stall flagged as it happens), and ``benchmarks/harness/
phase_log.py`` cuts the ring into a run's operations for nine readers.
"""

from __future__ import annotations

import importlib.util
import json
import logging
import os
import sys
import threading
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypergraphdb_tpu import obs
from hypergraphdb_tpu.obs import device as obs_device
from hypergraphdb_tpu.ops import ellbfs as eb
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hist(name: str) -> dict:
    h = obs.default_registry().get(f"phase.{name}")
    return h.summary() if h is not None else {"count": 0, "total": 0.0}


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names."""

    def __init__(self, monkeypatch):
        self.names: list = []
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", self._make)

    def _make(self, name):
        self.names.append(name)
        return nullcontext()


@pytest.fixture
def global_tracing():
    tracer = obs.tracer()
    tracer.enable()
    tracer.drain()
    try:
        yield tracer
    finally:
        tracer.disable()
        tracer.drain()


# ------------------------------------------------------------ the primitive


def test_phase_records_total_and_count():
    ticks = iter([10.0, 10.5, 20.0, 20.25])
    before = _hist("hg.test.total")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs_device.time, "perf_counter", lambda: next(ticks))
        for _ in range(2):
            with obs.phase("hg.test.total"):
                pass
    after = _hist("hg.test.total")
    assert after["count"] - before["count"] == 2
    assert after["total"] - before["total"] == pytest.approx(0.75)


def test_phase_nests():
    b_out, b_in = _hist("hg.test.outer"), _hist("hg.test.inner")
    with obs.phase("hg.test.outer"):
        for _ in range(3):
            with obs.phase("hg.test.inner"):
                pass
    a_out, a_in = _hist("hg.test.outer"), _hist("hg.test.inner")
    assert a_out["count"] - b_out["count"] == 1
    assert a_in["count"] - b_in["count"] == 3
    assert (a_out["total"] - b_out["total"]
            >= a_in["total"] - b_in["total"] >= 0.0)


def test_phase_is_a_span_under_the_current_trace(global_tracing):
    with global_tracing.trace_ctx("embedded.call"):
        with obs.phase("hg.test.outer"):
            with obs.phase("hg.test.inner"):
                pass
    (tr,) = [t for t in global_tracing.drain() if t.name == "embedded.call"]
    assert [s.name for s in tr.spans()] == [
        "embedded.call", "hg.test.outer", "hg.test.inner"]
    outer, inner = tr.find("hg.test.outer"), tr.find("hg.test.inner")
    assert outer.parent_id == tr.find("embedded.call").span_id
    assert inner.parent_id == outer.span_id
    assert inner.t1 is not None and outer.t1 >= inner.t1


def test_phase_opens_no_trace_of_its_own(global_tracing):
    before = _hist("hg.test.lonely")["count"]
    with obs.phase("hg.test.lonely"):
        pass
    assert global_tracing.drain() == []
    assert global_tracing.current_trace() is None
    assert _hist("hg.test.lonely")["count"] == before + 1


def test_phase_annotates_only_inside_a_profile_session(monkeypatch,
                                                       tmp_path):
    seen = _Annotations(monkeypatch)
    started: list = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda logdir, **kw: started.append((logdir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with obs.phase("hg.test.quiet"):
        pass
    assert seen.names == [] and not obs.profiling()
    with obs.profile(str(tmp_path)) as on:
        assert on and obs.profiling()
        with obs.phase("hg.test.loud"):
            pass
    assert seen.names == ["hg.test.loud"] and not obs.profiling()
    with obs.phase("hg.test.quiet"):
        pass
    assert seen.names == ["hg.test.loud"]
    # the session traces annotations and the device, not the interpreter
    (logdir, kw), = started
    assert logdir == str(tmp_path)
    assert kw["profiler_options"].python_tracer_level == 0
    assert kw["profiler_options"].host_tracer_level >= 1


def test_phase_records_when_the_body_raises(global_tracing):
    before = _hist("hg.test.raises")["count"]
    with global_tracing.trace_ctx("embedded.call"):
        with pytest.raises(KeyError):
            with obs.phase("hg.test.raises"):
                raise KeyError("boom")
    assert _hist("hg.test.raises")["count"] == before + 1
    (tr,) = [t for t in global_tracing.drain() if t.name == "embedded.call"]
    assert tr.find("hg.test.raises").t1 is not None


# ------------------------------------------- a record per phase instance


def _records(kind: str = "phase") -> list:
    return [f for _, k, f in obs.phase_log().records() if k == kind]


def _last(name: str) -> dict:
    return [r for r in _records() if r["name"] == name][-1]


def test_phase_leaves_one_record_with_its_fields():
    n = len(_records())
    with obs.phase("hg.test.record") as ph:
        pass
    (rec,) = _records()[n:]
    assert rec["name"] == "hg.test.record" and rec["id"] == ph.id > 0
    assert rec["parent"] == 0 and rec["op"] == rec["id"]
    assert rec["t1"] >= rec["t0"] and rec["cpu_s"] >= 0.0
    if obs_device._RUSAGE_THREAD is not None:
        assert all(isinstance(rec[k], int) and rec[k] >= 0
                   for k in ("nivcsw", "minflt", "majflt"))
    # scalars only: the ring's JSONL contract
    assert all(isinstance(v, (bool, int, float, str)) for v in rec.values())
    ring = obs.phase_log()
    assert isinstance(ring, obs.FlightRecorder)
    assert ring.capacity == 16_384 and ring is not obs.global_flight()


def test_op_and_parent_chain_over_nested_phases():
    with obs.phase("hg.test.op") as op:
        with obs.phase("hg.test.child") as child:
            with obs.phase("hg.test.grandchild") as grandchild:
                pass
        with obs.phase("hg.test.sibling") as sibling:
            pass
    with obs.phase("hg.test.op") as again:
        pass
    got = {r["id"]: (r["parent"], r["op"]) for r in _records()[-5:]}
    assert got == {grandchild.id: (child.id, op.id),
                   child.id: (op.id, op.id), sibling.id: (op.id, op.id),
                   op.id: (0, op.id), again.id: (0, again.id)}
    # a child's record is written before its parent's: exit order
    assert [r["id"] for r in _records()[-5:]] == [
        grandchild.id, child.id, sibling.id, op.id, again.id]


def test_two_threads_keep_their_own_chains():
    inside, release = threading.Barrier(3), threading.Event()
    seen: dict = {}

    def work(tag: str) -> None:
        with obs.phase(f"hg.test.thread.{tag}") as op:
            inside.wait(timeout=10)   # both operations are open at once
            with obs.phase(f"hg.test.thread.{tag}.child") as child:
                release.wait(timeout=10)
            seen[tag] = (op.id, child.id)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    inside.wait(timeout=10)
    release.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    for tag in "ab":
        op_id, child_id = seen[tag]
        assert _last(f"hg.test.thread.{tag}")["parent"] == 0
        child = _last(f"hg.test.thread.{tag}.child")
        assert (child["id"], child["parent"], child["op"]) == \
            (child_id, op_id, op_id)


def test_steps_sum_to_no_more_than_the_wall():
    with obs.phase("hg.test.steps") as ph:
        with ph.step("dispatch"):
            time.sleep(0.002)
        with ph.step("free"):
            pass
        with ph.step("dispatch"):   # a step taken twice adds up
            time.sleep(0.002)
    rec = _last("hg.test.steps")
    steps = {k: v for k, v in rec.items() if k.startswith("step.")}
    assert sorted(steps) == ["step.dispatch", "step.free"]
    assert steps["step.dispatch"] >= 0.004
    assert sum(steps.values()) <= rec["t1"] - rec["t0"]


def test_wait_is_block_until_ready_as_step_wait(monkeypatch):
    blocked: list = []
    monkeypatch.setattr(jax, "block_until_ready", blocked.append)
    x = jnp.arange(4)
    with obs.phase("hg.test.wait") as ph:
        assert ph.wait(x) is x
    assert blocked == [x]
    rec = _last("hg.test.wait")
    assert [k for k in rec if k.startswith("step.")] == ["step.wait"]


def test_step_annotates_only_inside_a_profile_session(monkeypatch):
    seen = _Annotations(monkeypatch)
    with obs.phase("hg.test.quiet") as ph, ph.step("dispatch"):
        pass
    assert seen.names == []
    monkeypatch.setattr(obs_device, "_PROFILING", True)
    with obs.phase("hg.test.loud") as ph:
        with ph.step("dispatch"):
            pass
        ph.wait(jnp.arange(4))
    assert seen.names == ["hg.test.loud", "hg.test.loud.dispatch",
                          "hg.test.loud.wait"]


def test_a_fresh_jit_lands_on_the_phase_that_called_it():
    def hg_test_fresh(x):   # its jnp calls are traced jits of their own
        return jnp.sum(jnp.where(x > 1, x + 1, x * 2))

    fn = jax.jit(hg_test_fresh)
    x = jnp.arange(8)   # made out here: an eager op is a program too
    hists = [obs.default_registry().histogram(f"jit.{h}")
             for h in ("trace", "lower", "compile")]
    counts = [h.count for h in hists]
    n_jit = len(_records("jit"))
    with obs.phase("hg.test.jit.outer") as outer:
        with obs.phase("hg.test.jit.first"):
            fn(x).block_until_ready()
        with obs.phase("hg.test.jit.second"):
            fn(x).block_until_ready()
    first, second = _last("hg.test.jit.first"), _last("hg.test.jit.second")
    assert all(first[f"jit.{k}"] > 0.0
               for k in ("trace_s", "lower_s", "compile_s"))
    assert not [k for k in second if k.startswith("jit.")]
    assert not [k for k in _last("hg.test.jit.outer")
                if k.startswith("jit.")]   # the innermost phase paid
    mine = _records("jit")[n_jit:]
    # ONE trace record: the nested jnp traces lie inside its seconds
    assert [(r["stage"], r["fun_name"]) for r in mine] == [
        ("trace_s", "hg_test_fresh"), ("lower_s", "jit(hg_test_fresh)"),
        ("compile_s", "jit(hg_test_fresh)")]
    assert all(r["op"] == outer.id and r["secs"] > 0.0 for r in mine)
    assert [h.count - was for h, was in zip(hists, counts)] == [1, 1, 1]
    assert first["jit.trace_s"] == mine[0]["secs"]
    assert sum(first[f"jit.{k}"] for k in ("trace_s", "lower_s",
                                            "compile_s")) \
        <= first["t1"] - first["t0"]


def test_the_ring_stays_bounded(monkeypatch):
    from hypergraphdb_tpu.obs.flight import FlightRecorder

    small = FlightRecorder(capacity=32, clock=time.perf_counter)
    monkeypatch.setattr(obs_device, "_PHASE_LOG", small)
    for _ in range(100):
        with obs.phase("hg.test.bounded"):
            pass
    assert obs.phase_log() is small and len(small.records()) == 32
    assert all(f["name"] == "hg.test.bounded"
               for _, _, f in small.records())


def _stalls() -> int:
    return obs.default_registry().counter("obs.phase.stalls").value


def test_a_planted_stall_is_counted_and_logged_once(caplog):
    before = _stalls()
    with caplog.at_level(logging.WARNING, logger="hypergraphdb_tpu.obs"):
        with obs.phase("hg.test.stall.op"):
            for i in range(12):
                with obs.phase("hg.test.stall.hop") as ph:
                    with ph.step("dispatch"):
                        pass
                    with ph.step("wait"):
                        if i == 8:   # the ninth instance
                            time.sleep(0.3)
    assert _stalls() == before + 1
    (line,) = [r.getMessage() for r in caplog.records]
    said = json.loads(line.split("obs.phase stall ", 1)[1])
    assert said["name"] == "hg.test.stall.hop" and said["stall"] is True
    assert said["op_name"] == "hg.test.stall.op" and said["op_index"] >= 0
    assert said["step.wait"] >= 0.3 > said["step.dispatch"]
    assert said["wall_s"] >= 0.3 > said["median_s"] and "cpu_s" in said
    if obs_device._RUSAGE_THREAD is not None:
        assert {"nivcsw", "minflt", "majflt"} <= set(said)
    assert set(said["memory"]) in (set(), {
        "bytes_in_use", "peak_bytes_in_use", "largest_free_block_bytes",
        "num_allocs"})
    flagged = [r for r in _records() if r.get("stall")
               and r["name"] == "hg.test.stall.hop"]
    assert len(flagged) == 1 and flagged[0]["op"] == said["op"]


@pytest.mark.parametrize("why", ["it_compiled", "too_few_samples",
                                 "under_a_quarter_second"])
def test_a_slow_instance_that_is_no_stall(why, caplog):
    """Slow for a reason on record, too early to have a median, or under
    the one compare the normal path pays."""
    name = f"hg.test.nostall.{why}"
    before = _stalls()
    slow_at = 2 if why == "too_few_samples" else 8
    fn, x = jax.jit(lambda x: x * 3 + 1), jnp.arange(3)
    with caplog.at_level(logging.WARNING, logger="hypergraphdb_tpu.obs"):
        for i in range(10):
            with obs.phase(name):
                if i == slow_at:
                    time.sleep(0.2 if why == "under_a_quarter_second"
                               else 0.3)
                    if why == "it_compiled":
                        fn(x)
    assert _stalls() == before and not caplog.records
    assert not [r for r in _records() if r["name"] == name
                and r.get("stall")]


# ------------------------------------------------------ the traversal path

HOP_PHASES = ("hg.bfs.hop.stage1", "hg.bfs.hop.stage2_lvl0",
              "hg.bfs.hop.stage2_upper_update")
# once a seed block that counts edges and has a dense hop: the one Σ deg
# `edges_touched` reads, on the bitmap entering the block's last hop
DEG_SUM_PHASE = "hg.bfs.hop.deg_sum"
SPARSE_PHASE = "hg.bfs.hop.sparse"   # once a block whose first hop is sparse
CALL_PHASES = ("hg.bfs.seeds_upload", "hg.bfs.reach_counts",
               "hg.bfs.edges_to_host")
ONCE_PHASES = ("hg.bfs.plan", "hg.bfs.plan.upload")


def _small_snapshot(seed: int = 7):
    r = np.random.default_rng(seed)
    n_nodes, n_links = 300, 200
    n = n_nodes + n_links
    is_link = np.zeros(n, dtype=bool)
    is_link[n_nodes:] = True
    arities = r.integers(2, 5, size=n_links)
    offsets = np.zeros(n + 1, dtype=np.int64)
    offsets[n_nodes + 1:] = np.cumsum(arities)
    flat = r.integers(0, n_nodes, size=int(arities.sum()))
    return CSRSnapshot.from_tables(np.zeros(n, np.int32), is_link, offsets,
                                   flat)


def test_bfs_pull_leaves_every_phase_with_the_right_counts():
    names = HOP_PHASES + (DEG_SUM_PHASE, SPARSE_PHASE) + CALL_PHASES \
        + ONCE_PHASES + ("hg.snapshot.from_tables",)
    before = {n: _hist(n)["count"] for n in names}
    snap = _small_snapshot()
    # few seeds: the rule takes the sparse side, the first hop is the
    # seeds' own neighbourhood and the pull chain runs hops 2..H
    seeds = np.arange(16, dtype=np.int32)
    hops = 3
    eb.bfs_pull(snap, seeds, hops)
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew == {**{n: hops - 1 for n in HOP_PHASES},
                    DEG_SUM_PHASE: 1, SPARSE_PHASE: 1,
                    **{n: 1 for n in CALL_PHASES + ONCE_PHASES},
                    "hg.snapshot.from_tables": 1}
    # a second call on the snapshot: the memoised plan and its upload
    # record nothing, the per-call and per-hop phases again
    eb.bfs_pull(snap, seeds, 2)
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew == {**{n: hops - 1 + 1 for n in HOP_PHASES},
                    DEG_SUM_PHASE: 2, SPARSE_PHASE: 2,
                    **{n: 2 for n in CALL_PHASES},
                    **{n: 1 for n in ONCE_PHASES},
                    "hg.snapshot.from_tables": 1}
    # every node a seed: too many pairs for the rule, so all H hops run on
    # the pull chain and the sparse phase records nothing
    eb.bfs_pull(snap, np.arange(300, dtype=np.int32), hops)
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew == {**{n: hops + hops for n in HOP_PHASES},
                    DEG_SUM_PHASE: 3, SPARSE_PHASE: 2,
                    **{n: 3 for n in CALL_PHASES},
                    **{n: 1 for n in ONCE_PHASES},
                    "hg.snapshot.from_tables": 1}
    assert all(_hist(n)["total"] > 0.0 for n in names)
    # nothing counts edges: no degree sum and no download, the hops as ever
    res = eb.bfs_pull(snap, seeds, hops, count_edges=False)
    assert not res.edges_touched.any()
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew[DEG_SUM_PHASE] == 3 and grew["hg.bfs.edges_to_host"] == 3
    assert grew["hg.bfs.hop.stage1"] == hops + hops + hops - 1
    assert grew["hg.bfs.reach_counts"] == 4 and grew[SPARSE_PHASE] == 3
    # one sparse hop and no other: S is the seeds' own degrees, which the
    # host holds — no pass over the bitmap
    eb.bfs_pull(snap, seeds, 1)
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew[DEG_SUM_PHASE] == 3 and grew["hg.bfs.edges_to_host"] == 4


UPDATE_COUNTERS = ("bfs.update.rows_visited", "bfs.update.rows_total",
                   "bfs.update.rows_kernel")


@pytest.mark.parametrize("operator", ["bfs_pull", "path_match"])
def test_update_counters_grow_once_a_dense_hop(operator):
    """Beside the phases: what an update's loop folded, the bitmap's rows
    and what of the folded rows the kernel fetched, from numbers the host
    holds, once an update dispatch — a sparse first hop dispatches none.
    On a graph of one row block the first two are the bitmap's rows both;
    on the CPU the kernel fetches none (the counter is there, at 0)."""
    def read():
        got = [obs.default_registry().get(n) for n in UPDATE_COUNTERS]
        return [0 if c is None else c.value for c in got]

    snap = _small_snapshot(11)
    n_pad = eb.plans_for(snap).n_pad
    assert n_pad <= eb.UPDATE_ROWS
    seeds = np.arange(16, dtype=np.int32)
    before = read()
    if operator == "bfs_pull":
        eb.bfs_pull(snap, seeds, 3)        # sparse, dense, dense
    else:
        eb.path_match(snap, seeds, [None] * 3)
    assert [now - was for now, was in zip(read(), before)] == \
        [2 * n_pad, 2 * n_pad, 0]
    assert obs.default_registry().get("bfs.update.rows_kernel") is not None


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


#: either update's: a bitmap of one row block, listed or not
_UPDATE_ARGS = (_u32(64, 1), _u32(9, 1),
                eb._UpdateRows(_i32(64), _i32(1), _i32()), _i32())

#: a whole-graph program's plan: stage 1 two classes and an upper level,
#: stage 2 one class and an upper level
_WHOLE_GRAPH_STAGES = (((32, 64, 16), (2, 8, 8), 2), ((32, 16), (2, 8), 1))

#: attribute -> (module name, scopes in its operations' paths, args, statics)
STAGE_PROGRAMS = {
    "_seed_bitmap": ("hg_bfs_seed_bitmap", ("hg.bfs.seed_bitmap",),
                     (_i32(32), _i32()), {"n_pad": 64}),
    # a counting pass: the bitmap, and the row blocks it folds
    "_deg_sum": ("hg_bfs_deg_sum", ("hg.bfs.deg_sum",),
                 (_u32(64, 1), _i32(64), _i32(1), _i32()), {}),
    "_sparse_hop": ("hg_bfs_sparse_hop", ("hg.bfs.sparse_hop",),
                    (_u32(64, 1), _i32(2, 16), _i32()), {}),
    "_stage": ("hg_bfs_stage1",
               ("hg.bfs.stage1.lvl0", "hg.bfs.stage1.upper"),
               (_u32(64, 1), (_i32(32), _i32(128), _i32(16))),
               {"route": eb._stage_route((32, 128, 16), (2, 8, 8), 2,
                                         _u32(64, 1), 4, kernel=False)}),
    "_stage_lvl0_consume": ("hg_bfs_stage2_lvl0", ("hg.bfs.stage2.lvl0",),
                            (_u32(64, 1), (_i32(32), _i32(128))),
                            {"route": eb._stage_route(
                                (32, 128), (2, 8), 2, _u32(64, 1), 4,
                                kernel=False)}),
    "_stage_upper": ("hg_bfs_stage2_upper", ("hg.bfs.stage2.upper",),
                     (_u32(32, 1), (_i32(16),)),
                     {"route": eb._stage_route((32, 16), (2, 8), 1,
                                               _u32(64, 1), 4,
                                               kernel=False)}),
    "_visited_update": ("hg_bfs_visited_update", ("hg.bfs.visited_update",),
                        _UPDATE_ARGS, {}),
    "_frontier_replace": ("hg_bfs_frontier_replace",
                          ("hg.bfs.frontier_replace",), _UPDATE_ARGS, {}),
    # a pair search's update: the traversal's fold, under its scope
    "_ball_update": ("hg_bfs_ball_update", ("hg.bfs.visited_update",),
                     _UPDATE_ARGS, {}),
    "_meet": ("hg_bfs_meet", ("hg.bfs.meet",),
              (_u32(64, 1), _u32(64, 1)), {}),
    "_reach_counts": ("hg_bfs_reach_counts", ("hg.bfs.reach_counts",),
                      (_u32(64, 1), _i32(1), _i32()), {}),
    # connected components: one program a round, its three scopes at its
    # top level, and the two around it
    "_wcc_init": ("hg_wcc_init", ("hg.wcc.init",), (_i32(),),
                  {"n_pad": 64}),
    "_wcc_round": ("hg_wcc_round",
                   ("hg.wcc.stage1", "hg.wcc.stage2", "hg.wcc.fold"),
                   (_i32(64), (_i32(32), _i32(64), _i32(16)),
                    (_i32(32), _i32(16)),
                    eb._UpdateRows(_i32(64), _i32(1), _i32()), _i32()),
                   {"route": eb._route(*_WHOLE_GRAPH_STAGES, _i32(64), 4,
                                       kernel=False)}),
    "_wcc_count": ("hg_wcc_count", ("hg.wcc.count",), (_i32(64),), {}),
    # PageRank: one program an iteration, its three scopes at its top level
    # (stage 1's buffer: 16 + 8 + 2 chunks and the zero row), and its init
    "_pr_init": ("hg_pr_init", ("hg.pr.init",), (_i32(),), {"n_pad": 64}),
    "_pr_iter": ("hg_pr_iter",
                 ("hg.pr.stage1", "hg.pr.stage2", "hg.pr.update"),
                 (_f32(64), (_i32(32), _i32(64), _i32(16)),
                  (_i32(32), _i32(16)),
                  eb._PRWeights(_f32(64), _f32(27), _f32(64)),
                  eb._UpdateRows(_i32(64), _i32(1), _i32()), _i32(),
                  _f32()),
                 {"route": eb._route(*_WHOLE_GRAPH_STAGES, _f32(64), 4,
                                     kernel=False)}),
}


@pytest.mark.parametrize("attr", sorted(STAGE_PROGRAMS))
def test_stage_program_carries_its_names(attr):
    module, scopes, args, statics = STAGE_PROGRAMS[attr]
    fn = getattr(eb, attr)
    # the Python attribute and the hgverify key stay; the XLA module is new
    assert fn.__wrapped__.__qualname__ == attr
    text = fn.lower(*args, **statics).compile().as_text()
    assert text.startswith(f"HloModule jit_{module},"), text[:80]
    for scope in scopes:
        assert f'op_name="jit({module})/{scope}/' in text, scope
    # every operation that has a path has one of the program's scopes in it
    paths = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in text.splitlines() if 'op_name="jit(' in ln]
    assert paths and all(
        any(f"/{s}/" in p for s in scopes) for p in paths), paths[:5]


PAIR_COUNTERS = ("bfs.pairs.batches", "bfs.pairs.expansions.sparse",
                 "bfs.pairs.expansions.dense", "bfs.pairs.meet_tests",
                 "bfs.pairs.early_exits")


def test_pair_distances_leaves_its_phase_and_its_five_counters():
    """Phase ``hg.bfs.pairs.meet`` once a test; the chain's own hop phases
    once an expansion, as once a hop; the five ``bfs.pairs.*`` counters
    from numbers the host holds."""
    def counters():
        got = [obs.default_registry().get(n) for n in PAIR_COUNTERS]
        return [0 if c is None else c.value for c in got]

    names = HOP_PHASES + (SPARSE_PHASE, DEG_SUM_PHASE, "hg.bfs.pairs.meet",
                          "hg.bfs.seeds_upload", "hg.bfs.reach_counts")
    snap = _small_snapshot(13)
    r = np.random.default_rng(13)
    sources = r.integers(0, 300, size=16).astype(np.int32)
    targets = (sources + 1 + r.integers(0, 298, size=16)) % 300  # s != t
    before, c0 = {n: _hist(n)["count"] for n in names}, counters()
    res = eb.pair_distances(snap, sources, targets, 5)
    # few pairs: either side's first hop is sparse, the rest dense
    dense = res.expansions - 2
    assert dense >= 1
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew == {**{n: dense for n in HOP_PHASES}, SPARSE_PHASE: 2,
                    DEG_SUM_PHASE: 0, "hg.bfs.pairs.meet": res.expansions,
                    "hg.bfs.seeds_upload": 2, "hg.bfs.reach_counts": 0}
    assert _hist("hg.bfs.pairs.meet")["total"] > 0.0
    assert [now - was for now, was in zip(counters(), c0)] == \
        [1, 2, dense, res.expansions, int(res.expansions < 5)]
    # nothing to search for: a batch and an early exit, no expansion
    c0 = counters()
    eb.pair_distances(snap, sources[:2], sources[:2], 5)  # s == t: no work
    assert [now - was for now, was in zip(counters(), c0)] == [1, 0, 0, 0, 1]


def test_pallas_gather_kernel_is_named():
    from hypergraphdb_tpu.ops import pallas_gather as pg

    jaxpr = jax.make_jaxpr(
        lambda v, i: pg.gather_or(v, i, 8, interpret=True))(
        _u32(8, pg.ROW_WORDS), _i32(8 * pg.G))
    assert "hg_gather_or" in str(jaxpr)


def test_dispatch_thread_annotations_are_off_the_unprofiled_path(
        monkeypatch):
    from hypergraphdb_tpu.serve import runtime as rt

    seen = _Annotations(monkeypatch)
    cfg = rt.ServeConfig()
    assert rt._thread_cm(cfg, "hg.serve.park") is rt._NULL_CM
    assert seen.names == []
    monkeypatch.setattr(obs_device, "_PROFILING", True)
    with rt._thread_cm(cfg, "hg.serve.park"):
        pass
    monkeypatch.setattr(obs_device, "_PROFILING", False)
    with rt._thread_cm(rt.ServeConfig(device_timing=True),
                       "hg.serve.launch"):
        pass
    assert seen.names == ["hg.serve.park", "hg.serve.launch"]


WCC_COUNTERS = ("wcc.runs", "wcc.rounds", "wcc.rows_lowered",
                "wcc.rows_folded")


def test_connected_components_leaves_its_phases_and_its_four_counters():
    """Phase ``hg.wcc`` once a call (with its ``count`` step), phase
    ``hg.wcc.round`` once a round (``dispatch`` / ``wait``), the bitmap
    chain's hop phases never; the four ``wcc.*`` counters from numbers the
    host holds: runs, rounds, rows lowered (the rounds' own counts) and
    rows folded (the plan's listed rows a round)."""
    def counters():
        got = [obs.default_registry().get(n) for n in WCC_COUNTERS]
        return [0 if c is None else c.value for c in got]

    names = ("hg.wcc", "hg.wcc.round") + HOP_PHASES
    snap = _small_snapshot(23)
    before, c0 = {n: _hist(n)["count"] for n in names}, counters()
    n = len(_records())
    res = eb.connected_components(snap)
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew == {"hg.wcc": 1, "hg.wcc.round": res.rounds,
                    **{n: 0 for n in HOP_PHASES}}
    mine = _records()[n:]
    (op,) = [r for r in mine if r["name"] == "hg.wcc"]
    rounds = [r for r in mine if r["name"] == "hg.wcc.round"]
    assert all(r["parent"] == op["id"] and r["op"] == op["id"]
               and sorted(k for k in r if k.startswith("step.")) ==
               ["step.dispatch", "step.wait"] for r in rounds)
    assert "step.count" in op
    n_pad = eb.plans_for(snap).n_pad  # one row block: every row listed
    labels = np.asarray(res.labels)[: snap.num_atoms]
    moved = int(np.count_nonzero(labels != np.arange(snap.num_atoms)))
    runs, rounds, lowered, folded = (
        now - was for now, was in zip(counters(), c0))
    assert (runs, rounds, folded) == (1, res.rounds, res.rounds * n_pad)
    assert lowered >= moved  # a label not its own fell once at least


# ------------------------------------------------ an operation's own span


def _operate(operator: str, snap) -> None:
    seeds = np.arange(16, dtype=np.int32)
    if operator == "bfs_pull":
        eb.bfs_pull(snap, seeds, 3)
    elif operator == "path_match":
        eb.path_match(snap, seeds, [None] * 3)
    else:
        eb.pair_distances(snap, seeds, (seeds + 7) % 300, 4)


OPERATIONS = {"bfs_pull": "hg.bfs.pull", "path_match": "hg.bfs.match",
              "pair_distances": "hg.bfs.pairs"}


@pytest.mark.parametrize("operator", sorted(OPERATIONS))
def test_an_operation_leaves_one_record_whose_children_carry_its_id(
        operator):
    snap = _small_snapshot(17)
    n = len(_records())
    _operate(operator, snap)
    mine = _records()[n:]
    (op,) = [r for r in mine if r["name"] in OPERATIONS.values()]
    assert op["name"] == OPERATIONS[operator] and op["parent"] == 0
    assert mine[-1] is op and len(mine) > 5
    assert {r["op"] for r in mine} == {op["id"]}
    # every sync inside a phase is a step, and the table's steps are there
    steps = {r["name"]: sorted(k[5:] for k in r if k.startswith("step."))
             for r in mine}
    assert steps["hg.bfs.hop.sparse"] == ["expand", "place", "wait"]
    assert steps["hg.bfs.hop.stage1"] == ["dispatch", "wait"]
    assert steps["hg.bfs.hop.stage2_lvl0"] == ["dispatch", "free", "wait"]
    assert steps["hg.bfs.hop.stage2_upper_update"] == ["dispatch", "wait"]
    assert steps["hg.bfs.plan.upload"] == ["upload", "wait"]
    assert steps["hg.bfs.seeds_upload"] == steps["hg.bfs.plan"] == []
    if operator == "pair_distances":
        assert steps["hg.bfs.pairs.meet"] == ["decide", "dispatch", "wait"]
    if operator == "bfs_pull":
        assert steps["hg.bfs.hop.deg_sum"] == ["dispatch", "wait"]
    # the direct children lie inside the operation, one after another
    children = [r for r in mine if r["parent"] == op["id"]]
    assert all(op["t0"] <= r["t0"] <= r["t1"] <= op["t1"] for r in children)
    assert sum(r["t1"] - r["t0"] for r in children) <= op["t1"] - op["t0"]


# ------------------------------------------- the readers of the records

NEW_READERS = ("traverse_host_s.unwaited", "traverse_host_s.sparse_expand",
               "traverse_host_s.self", "traverse_stall_s", "warm_trace_s",
               "warm_lower_s", "warm_compile_s", "warm_cache_load_s",
               "warm_other_s")


def _reader(name: str):
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        f"layer_metrics.{name}",
        os.path.join(bench, "layer_metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def a_run(monkeypatch):
    """A ring of its own holding what a run of the benchmark leaves: a
    warm-up operation on a fresh snapshot (it compiles or loads every
    program), then a window of three."""
    from hypergraphdb_tpu.obs.flight import FlightRecorder

    monkeypatch.setattr(obs_device, "_PHASE_LOG", FlightRecorder(
        capacity=obs_device.PHASE_LOG_CAPACITY, clock=time.perf_counter))
    jax.clear_caches()          # the warm-up traces and lowers anew
    snap = _small_snapshot(19)  # built before the clock, as a builder does
    t0 = time.perf_counter()
    _operate("bfs_pull", snap)
    warm_s = time.perf_counter() - t0
    for _ in range(3):
        _operate("bfs_pull", snap)
    return {"window": {"attempted": 3}, "setup": {"warm_s": warm_s}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_a_number_and_none_on_an_emptied_ring(
        name, a_run):
    value = _reader(name)(a_run)
    assert isinstance(value, float) and value >= 0.0
    assert (value == 0.0) == (name in ("traverse_stall_s",
                                       "warm_cache_load_s"))
    obs.phase_log().reset()
    assert _reader(name)(a_run) is None


def test_the_readers_add_up(a_run):
    from harness import phase_log  # benchmarks/ is on the path by now

    read = {name: _reader(name)(a_run) for name in NEW_READERS}
    # the warm-up by stage and the rest: warm_s, exactly
    assert sum(read[n] for n in NEW_READERS[4:]) == \
        pytest.approx(a_run["setup"]["warm_s"], abs=1e-12)
    assert 0.0 < read["warm_other_s"] < a_run["setup"]["warm_s"]
    # self + the children's seconds = the operations' wall, exactly
    window = phase_log.window_of(a_run)
    assert len(window.ops) == 3 and len(phase_log.warm_of(a_run).ops) == 1
    walls = sum(map(phase_log.wall, window.ops))
    assert read["traverse_host_s.self"] * 3 + sum(
        map(phase_log.wall, window.children())) == pytest.approx(
            walls, abs=1e-12)
    assert read["traverse_host_s.sparse_expand"] \
        <= read["traverse_host_s.unwaited"] <= walls / 3
    # a window longer than the ring reaches back to: nothing to read
    assert _reader("traverse_host_s.self")(
        dict(a_run, window={"attempted": 5})) is None


# ------------------------------------------------- the reader of the scopes


def _check_scope_reduce():
    path = os.path.join(ROOT, "benchmarks", "tests", "check_scope_reduce.py")
    spec = importlib.util.spec_from_file_location("check_scope_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("check", ["check_by_hand", "check_readers",
                                   "check_on_recorded_trace"])
def test_scope_reduce(check):
    """The benchmark's own checks of its reducer (hand-made bytes: a nest
    counts once, an unscoped child inherits, coverage; the recorded TPU
    fixture), run here so that tier-1 holds them."""
    getattr(_check_scope_reduce(), check)()


PR_COUNTERS = ("pr.runs", "pr.iterations", "pr.rows_folded")


def test_pagerank_leaves_its_phases_and_its_three_counters():
    """Phase ``hg.pr`` once a call (with its ``mass`` step: the one read),
    phase ``hg.pr.iter`` once an iteration with a ``dispatch`` step and no
    ``wait`` (nothing is read between iterations), the bitmap chain's hop
    phases never; the three ``pr.*`` counters from numbers the host holds:
    runs, iterations and the plan's listed rows an iteration."""
    def counters():
        got = [obs.default_registry().get(n) for n in PR_COUNTERS]
        return [0 if c is None else c.value for c in got]

    names = ("hg.pr", "hg.pr.iter") + HOP_PHASES
    snap = _small_snapshot(29)
    before, c0 = {n: _hist(n)["count"] for n in names}, counters()
    n = len(_records())
    res = eb.pagerank(snap, iterations=4)
    grew = {n: _hist(n)["count"] - before[n] for n in names}
    assert grew == {"hg.pr": 1, "hg.pr.iter": 4,
                    **{n: 0 for n in HOP_PHASES}}
    mine = _records()[n:]
    (op,) = [r for r in mine if r["name"] == "hg.pr"]
    iters = [r for r in mine if r["name"] == "hg.pr.iter"]
    assert len(iters) == 4 and all(
        r["parent"] == op["id"] and r["op"] == op["id"]
        and sorted(k for k in r if k.startswith("step.")) ==
        ["step.dispatch"] for r in iters)
    assert "step.mass" in op and abs(res.mass - 1.0) < 1e-5
    n_pad = eb.plans_for(snap).n_pad  # one row block: every row listed
    assert [now - was for now, was in zip(counters(), c0)] == \
        [1, 4, 4 * n_pad]
