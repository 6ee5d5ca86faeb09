"""Behavior pins for the hgexc (HG10xx) real-tree runtime fixes.

Every broad swallow the analyzer flagged was either narrowed, given
evidence (a log line or a counter), or pragma-audited. These tests pin
the EVIDENCE, not the analyzer: each fix must observably change runtime
behavior, so a revert fails here before it ever reaches hglint.
"""

from __future__ import annotations

import logging
import time

import pytest

import hypergraphdb_tpu as hg
from hypergraphdb_tpu.algorithms.traversals import HyperTraversal
from hypergraphdb_tpu.core.errors import NotFoundError
from hypergraphdb_tpu.obs.http import runtime_health
from hypergraphdb_tpu.peer import HyperGraphPeer, LoopbackNetwork
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime
from hypergraphdb_tpu.serve.stats import ServeStats
from tests.test_serve_runtime import FakeClock, FakeExecutor


def _wait(cond, timeout=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.01)
    return False


def _counter(registry, name):
    c = registry.get(name)
    return 0 if c is None else c.value


# ------------------------------------------ traversals: narrowed swallow


def test_hypertraversal_skips_plain_atoms():
    """``get_targets`` on a plain atom raises NotFoundError — the
    flattened traversal treats that as "no targets" and keeps walking."""
    g = hg.HyperGraph()
    try:
        a, b = int(g.add("a")), int(g.add("b"))
        link = int(g.add_link([a, b]))
        seen = {nbr for _, nbr in HyperTraversal(g, a)}
        assert link in seen and b in seen
    finally:
        g.close()


def test_hypertraversal_propagates_unexpected_errors():
    """The old broad swallow ate storage faults and evaluation bugs
    alongside the benign miss; only NotFoundError is absorbed now."""

    class TornGraph:
        def get_incidence_set(self, node):
            return []

        def get_targets(self, node):
            raise RuntimeError("storage fault")

    with pytest.raises(RuntimeError, match="storage fault"):
        list(HyperTraversal(TornGraph(), 0))

    class EmptyGraph(TornGraph):
        def get_targets(self, node):
            raise NotFoundError("plain atom")

    assert list(HyperTraversal(EmptyGraph(), 0)) == []


# ------------------------------------- /healthz: named torn enrichments


class _Breaker:
    def states(self):
        return {("bfs", 4): "closed"}

    def worst_code(self):
        return 0


class _Queue:
    closed = False

    def depth(self):
        return 0


def _fake_rt(executor, perf):
    class RT:
        pass

    rt = RT()
    rt.breaker = _Breaker()
    rt.queue = _Queue()
    rt.executor = executor
    rt.perf = perf
    return rt


def test_health_probe_names_torn_enrichments():
    """A raising mesh/perf enrichment must not 500 the probe OR vanish
    silently — the payload names the degraded field."""

    class TornExecutor:
        def mesh_report(self):
            raise RuntimeError("mesh probe torn")

    class TornPerf:
        def health_summary(self):
            raise RuntimeError("sentinel bug")

    healthy, payload = runtime_health(
        _fake_rt(TornExecutor(), TornPerf()))()
    assert healthy                        # enrichment never flips health
    assert payload["degraded"] == ["mesh", "perf"]
    assert "mesh" not in payload and "perf" not in payload


def test_health_probe_clean_enrichments_carry_no_degraded_marker():
    class Executor:
        def mesh_report(self):
            return {"mesh_shape": [1]}

    class Perf:
        def health_summary(self):
            return {"status": "ok"}

    healthy, payload = runtime_health(_fake_rt(Executor(), Perf()))()
    assert healthy
    assert "degraded" not in payload
    assert payload["mesh"] == {"mesh_shape": [1]}
    assert payload["perf"] == {"status": "ok"}


# ------------------------- serve: dropped perf observations are counted


def test_record_perf_error_counts_and_resets():
    stats = ServeStats()
    assert _counter(stats.registry, "serve.perf_observe_errors") == 0
    stats.record_perf_error()
    stats.record_perf_error()
    assert _counter(stats.registry, "serve.perf_observe_errors") == 2
    stats.reset()
    assert _counter(stats.registry, "serve.perf_observe_errors") == 0


def test_broken_sentinel_is_counted_not_silent():
    """The dispatch loop swallows a raising perf sentinel (a perf bug
    must never fail the request) — but the swallow now leaves evidence:
    ``serve.perf_observe_errors`` counts every dropped observation."""

    class ExplodingSentinel:
        def observe(self, *a, **k):
            raise RuntimeError("boom")

        def observe_batch(self, *a, **k):
            raise RuntimeError("boom")

        def maybe_tick(self):
            raise RuntimeError("boom")

    clock = FakeClock()
    cfg = ServeConfig(buckets=(4,), max_linger_s=0.0, clock=clock,
                      manual=True, perf=ExplodingSentinel())
    rt = ServeRuntime(graph=None, config=cfg, executor=FakeExecutor())
    try:
        fut = rt.submit_bfs(1)
        rt.step(drain=True)
        assert fut.result(timeout=0).kind == "bfs"   # request unharmed
        assert _counter(rt.stats.registry,
                        "serve.perf_observe_errors") >= 1
    finally:
        rt.close()


# --------------------------- peer replication: failure-path counters


@pytest.fixture
def two_peers():
    net = LoopbackNetwork()
    g1, g2 = hg.HyperGraph(), hg.HyperGraph()
    p1 = HyperGraphPeer.loopback(g1, net, identity="peer-1")
    p2 = HyperGraphPeer.loopback(g2, net, identity="peer-2")
    p1.start()
    p2.start()
    yield p1, p2
    p1.stop()
    p2.stop()
    g1.close()
    g2.close()


def test_ack_send_failure_is_counted(two_peers):
    """A torn ack pipe used to vanish into ``except Exception: pass`` —
    now ``peer.ack_send_failures`` counts it (the sender just re-serves
    from the last durable ack, so counting IS the whole remedy)."""
    p1, p2 = two_peers
    p2.replication.publish_interest(None)
    assert _wait(lambda: "peer-2" in p1.replication.peer_interests)

    orig_send = p2.interface.send

    def flaky_send(to, msg):
        if "ack" in str(msg):
            raise ConnectionError("ack pipe torn")
        return orig_send(to, msg)

    p2.interface.send = flaky_send
    p1.graph.add("hello")
    reg2 = p2.graph.metrics.registry
    assert _wait(
        lambda: _counter(reg2, "peer.ack_send_failures") >= 1
    ), "ack-send failure left no counter evidence"


def test_catch_up_failure_is_counted(two_peers):
    """A raising catch-up continuation (peer gone mid-page) increments
    ``peer.catch_up_failures`` instead of disappearing."""
    _, p2 = two_peers
    p2.replication._apply = lambda sender, kind, entry: None

    def gone(pid):
        raise ConnectionError("peer gone")

    p2.replication.catch_up = gone
    # a continuation page: applied items + continue_catchup=True drives
    # the drain loop into the catch-up pull that now fails
    p2.replication._enqueue_apply(
        "peer-1", [("record", {}, 999, None)], True)
    reg2 = p2.graph.metrics.registry
    assert _wait(
        lambda: _counter(reg2, "peer.catch_up_failures") >= 1
    ), "catch-up failure left no counter evidence"


# ----------------------------- serve: prewarm failures log, never block


def test_failed_prewarm_logs_and_startup_still_serves(tmp_path, caplog,
                                                      monkeypatch):
    """Join/range prewarm failures must not block startup (first
    dispatch builds cold) — and must not be silent: each names what went
    cold on the ``hypergraphdb_tpu.serve`` logger."""
    graph = hg.HyperGraph()
    try:
        nodes = [int(graph.add(i)) for i in range(12)]
        for i in range(6):
            graph.add_link([nodes[i], nodes[i + 1]], value=100 + i)

        from hypergraphdb_tpu.ops import join as join_ops
        from hypergraphdb_tpu.storage import value_index

        def torn(*a, **k):
            raise RuntimeError("prewarm torn")

        cfg = ServeConfig(buckets=(4,), max_linger_s=0.001,
                          aot_cache_dir=str(tmp_path),
                          prewarm_join_nbr=True,
                          prewarm_range_dims=(ord("i"),))
        with monkeypatch.context() as mp:
            mp.setattr(join_ops, "neighbor_csr_device", torn)
            mp.setattr(value_index, "value_index_column", torn)
            with caplog.at_level(logging.WARNING, "hypergraphdb_tpu.serve"):
                rt = ServeRuntime(graph, cfg)
        messages = [r.getMessage() for r in caplog.records]
        assert any("join prewarm failed" in m for m in messages), messages
        assert any("range-column prewarm failed" in m for m in messages), \
            messages
        # the patches are gone: first dispatch builds cold and serves
        res = rt.submit_range(lo=3, hi=9).result(timeout=60)
        assert res.matches.tolist()       # nonempty window over 0..11
        rt.close()
    finally:
        graph.close()
