"""ReplicaNode: bootstrap → follow → serve, the lag-bounded staleness
contract, the /healthz payload, and rejoin-by-resume."""

from __future__ import annotations

import time

import pytest

import hypergraphdb_tpu as hg
from hypergraphdb_tpu.peer import transfer
from hypergraphdb_tpu.peer.peer import HyperGraphPeer
from hypergraphdb_tpu.peer.transport import LoopbackNetwork
from hypergraphdb_tpu.replica import ReplicaConfig, ReplicaNode
from hypergraphdb_tpu.serve import AdmissionGated, ServeConfig


def serve_cfg(**kw):
    kw.setdefault("max_linger_s", 0.001)
    kw.setdefault("prewarm_aot", False)
    return ServeConfig(**kw)


def wait_digest_equal(ga, gb, timeout=30.0):
    """Poll for content convergence. ``wait_converged`` alone is the
    replica's ADVERTISED lag — a push still in flight (sent, not yet
    dispatched) is invisible to it, so equality tests poll the digest."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if transfer.content_digest(ga) == transfer.content_digest(gb):
            return True
        time.sleep(0.02)
    return False


def make_primary(net, n_nodes=16):
    gp = hg.HyperGraph()
    pp = HyperGraphPeer.loopback(gp, net, identity="primary")
    pp.replication.debounce_s = 0.005
    pp.start()
    nodes = [int(gp.add(f"n{i}")) for i in range(n_nodes)]
    for i in range(n_nodes - 1):
        gp.add_link([nodes[i], nodes[i + 1]], value=f"e{i}")
    return gp, pp, nodes


def make_replica(net, ident="replica-1", **cfg_kw):
    gr = hg.HyperGraph()
    pr = HyperGraphPeer.loopback(gr, net, identity=ident)
    pr.replication.debounce_s = 0.005
    cfg_kw.setdefault("anti_entropy_interval_s", 0.1)
    cfg_kw.setdefault("serve", serve_cfg())
    node = ReplicaNode(gr, pr, ReplicaConfig(primary="primary", **cfg_kw))
    return node


def test_bootstrap_follow_serve():
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net)
    node = make_replica(net)
    try:
        node.start()
        assert node.bootstrap_mode == "transfer"
        assert pp.replication.flush()
        assert node.wait_converged(timeout=30)
        # content converged exactly
        assert wait_digest_equal(gp, node.graph)
        # serve a read LOCALLY (the replica's own runtime + graph)
        local_seed = int(transfer.lookup_local(
            node.graph, transfer.gid_of(gp, nodes[0], "primary")))
        res = node.runtime.submit_bfs(local_seed, max_hops=1) \
                  .result(timeout=30)
        assert res.count >= 2              # seed + its neighbor
        # live follow: a new primary atom shows up on the replica
        gp.add("fresh")
        assert pp.replication.flush()
        assert wait_digest_equal(gp, node.graph)
        assert node.wait_converged(timeout=30)
    finally:
        node.stop()
        pp.stop()
        gp.close()
        node.graph.close()


def test_lag_gate_refuses_reads_and_unhealths():
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=6)
    node = make_replica(net, max_replication_lag=4,
                        anti_entropy_interval_s=0)  # manual control
    try:
        node.start()
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        ok, payload = node.health_probe()()
        assert ok and payload["replication_lag"] == 0
        assert payload["role"] == "replica"
        assert payload["lag_bound"] == 4
        assert payload["bootstrapped"] is True
        assert "breakers" in payload       # runtime_health merged in
        # simulate trailing far behind: the primary's advertised head
        # races ahead of our applied clock
        node.peer.replication.peer_heads["primary"] = (
            node.peer.replication.last_seen.get("primary") + 100)
        assert node.replication_lag == 100
        with pytest.raises(AdmissionGated):
            node.runtime.submit_bfs(0, max_hops=1)
        assert node.runtime.stats.gated == 1
        ok, payload = node.health_probe()()
        assert not ok and "read_gate" in payload
        # catch-up heals the advertised lag → reads re-admit
        node.peer.replication.peer_heads["primary"] = (
            node.peer.replication.last_seen.get("primary"))
        assert node._read_gate() is None
        ok, _ = node.health_probe()()
        assert ok
    finally:
        node.stop()
        pp.stop()
        gp.close()
        node.graph.close()


def test_rejoin_resumes_without_full_transfer():
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=8)
    node = make_replica(net, ident="replica-r")
    try:
        node.start()
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        transfers_before = gp.metrics.counters.get("peer.transfer_chunks",
                                                   0)
        node.stop()                        # clean shutdown (clock persisted
        # in RAM graph object we keep — the graph IS the surviving state)
        gp.add("while-down-1")
        gp.add("while-down-2")
        pp.replication.flush()
        # rejoin: same graph, fresh peer with the same identity
        gr = node.graph
        pr2 = HyperGraphPeer.loopback(gr, net, identity="replica-r")
        pr2.replication.debounce_s = 0.005
        node2 = ReplicaNode(gr, pr2, ReplicaConfig(
            primary="primary", anti_entropy_interval_s=0.1,
            serve=serve_cfg()))
        node2.start()
        assert node2.bootstrap_mode == "resume"   # no re-transfer
        assert gp.metrics.counters.get("peer.transfer_chunks", 0) \
            == transfers_before
        assert node2.wait_converged(timeout=30)
        assert wait_digest_equal(gp, gr)
        node2.stop()
    finally:
        pp.stop()
        gp.close()
        node.graph.close()


def test_failed_bootstrap_does_not_leak_started_peer():
    """start() must tear the peer back down when the bootstrap fails —
    otherwise its worker/transport threads keep running (and the primary
    keeps pushing to a zombie interest) while stop() is a no-op because
    ``_started`` never flipped."""
    net = LoopbackNetwork()              # NO primary on the wire
    gr = hg.HyperGraph()
    pr = HyperGraphPeer.loopback(gr, net, identity="orphan")
    node = ReplicaNode(gr, pr, ReplicaConfig(
        primary="primary", bootstrap_timeout_s=10.0,
        bootstrap_retry_after_s=0.02, bootstrap_max_resumes=2,
        serve=serve_cfg()))
    try:
        with pytest.raises(Exception):
            node.start()
        assert not pr._started           # peer fully stopped again
        assert node.runtime is None
        node.stop()                      # and stop() stays a safe no-op
    finally:
        gr.close()


def test_runtime_truncation_forces_in_place_rebootstrap():
    """A RUNNING replica whose primary truncated past it
    (``needs_full_sync`` raised by a digest/catch-up response) must
    re-bootstrap in place from the follow phase — not wedge permanently
    gated until an operator restart."""
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=6)
    node = make_replica(net, anti_entropy_interval_s=0.05)
    try:
        node.start()
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        chunks_before = gp.metrics.counters.get("peer.transfer_chunks", 0)
        # the divergence a digest would report: primary's log no longer
        # covers us — incremental repair cannot converge
        node.peer.replication.needs_full_sync.add("primary")
        assert wait_for_rebootstrap(node, gp, chunks_before)
        assert node.bootstrapped
        # and the re-bootstrapped replica still follows live pushes
        gp.add("post-rebootstrap")
        assert pp.replication.flush()
        assert wait_digest_equal(gp, node.graph)
    finally:
        node.stop()
        pp.stop()
        gp.close()
        node.graph.close()


def wait_for_rebootstrap(node, gp, chunks_before, timeout=30.0):
    """The repair ran to its end: the mark is cleared, a chunk moved, and
    the node has set ``bootstrapped`` again — which ``_rebootstrap`` does a
    moment after the transfer clears the mark, so a caller that waited for
    the mark alone could read the flag too early."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ("primary" not in node.peer.replication.needs_full_sync
                and gp.metrics.counters.get("peer.transfer_chunks", 0)
                > chunks_before
                and node.bootstrapped):
            return True
        time.sleep(0.02)
    return False


def test_anti_entropy_loop_drives_convergence_during_push_outage():
    """With pushes entirely suppressed (no interest published — the
    primary logs but never pushes), the replica's periodic digest probe
    alone must still converge it."""
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=4)
    node = make_replica(net, anti_entropy_interval_s=0.05)
    try:
        node.start()
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        # sever the push path: primary forgets the replica's interest
        pp.replication.peer_interests.clear()
        gp.add("push-less")
        assert pp.replication.flush()
        assert wait_digest_equal(gp, node.graph)
        assert node.graph.metrics.counters.get(
            "peer.anti_entropy_probes", 0) >= 1
    finally:
        node.stop()
        pp.stop()
        gp.close()
        node.graph.close()


def test_truncation_lazy_rebootstrap_with_ae_loop_disabled():
    """With the AE loop OFF (anti_entropy_interval_s=0) a
    ``needs_full_sync`` mark must still be actionable: the read gate
    kicks the re-bootstrap lazily, so a gated read — not an operator
    restart — is what repairs a truncated-past replica."""
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=6)
    node = make_replica(net, anti_entropy_interval_s=0)
    try:
        node.start()
        assert node._ae_thread is None          # the loop really is off
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        chunks_before = gp.metrics.counters.get("peer.transfer_chunks", 0)
        node.peer.replication.needs_full_sync.add("primary")
        # the kick happens on the gate path, and the refusal is typed
        # as "diverged", not a permanent "bootstrapping" wedge
        reason = node._read_gate()
        assert reason is not None and "re-bootstrapping" in reason
        assert wait_for_rebootstrap(node, gp, chunks_before)
        assert node.bootstrapped
        assert node._read_gate() is None
        # and the repaired replica still follows live pushes
        gp.add("post-lazy-rebootstrap")
        assert pp.replication.flush()
        assert wait_digest_equal(gp, node.graph)
    finally:
        node.stop()
        pp.stop()
        gp.close()
        node.graph.close()


def test_resume_gate_until_primary_head_known():
    """A RESUMED replica reads replication_lag 0 until the primary's
    head arrives this incarnation (peer_heads is per-process) — the gate
    must refuse until then, or hour-old data serves at advertised lag 0."""
    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=4)
    node = make_replica(net, anti_entropy_interval_s=0)
    try:
        node.start()
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        # the resumed-and-silent state: no head heard since restart
        node.bootstrap_mode = "resume"
        node.peer.replication.peer_heads.pop("primary", None)
        reason = node._read_gate()
        assert reason is not None and "head unknown" in reason
        ok, payload = node.health_probe()()
        assert not ok and "read_gate" in payload
        # the first head-carrying message (push/catch-up/digest) heals it
        node.peer.replication.peer_heads["primary"] = (
            node.peer.replication.last_seen.get("primary"))
        assert node._read_gate() is None
    finally:
        node.stop()
        pp.stop()
        gp.close()
        node.graph.close()


def test_resume_catch_up_send_failure_fails_bootstrap_typed():
    """Resume mode's catch-up request is its ONLY wake-up signal: if the
    reliable send cannot reach the primary, start() must fail typed
    (TransientFault) instead of parking the node gated at 'head unknown'
    until unrelated traffic happens by."""
    from hypergraphdb_tpu.fault import TransientFault

    net = LoopbackNetwork()
    gp, pp, nodes = make_primary(net, n_nodes=4)
    node = make_replica(net, ident="replica-rf")
    try:
        node.start()
        pp.replication.flush()
        assert node.wait_converged(timeout=30)
        node.stop()
        gr = node.graph
        pr2 = HyperGraphPeer.loopback(gr, net, identity="replica-rf")
        pr2.replication.debounce_s = 0.005
        pr2.replication.catch_up = lambda pid: False   # unreachable
        node2 = ReplicaNode(gr, pr2, ReplicaConfig(
            primary="primary", anti_entropy_interval_s=0,
            serve=serve_cfg()))
        with pytest.raises(TransientFault):
            node2.start()
        assert not node2._started                      # nothing leaked
    finally:
        pp.stop()
        gp.close()
        node.graph.close()
