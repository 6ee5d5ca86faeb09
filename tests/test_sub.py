"""hgsub unit coverage: the SubscriptionManager's envelopes, dirty
tracking, delivery semantics, and wire decoding.

The chaos-style acceptance soak (multi-seed differential equality,
1k-subscription coalescing, door resume across a replica kill) lives in
tests/test_sub_soak.py; this file pins the per-component contracts:

- subscribe/unsubscribe envelopes and the initial-snapshot seq anchor;
- incremental deltas: adds, removals, range window movement, BFS
  pre-commit target capture — each chained (``seq_from`` == previous
  ``seq_to``) and digest-audited;
- backpressure: window overflow sheds the WHOLE queue and resyncs
  (shed-not-hang, counted ``sub.shed``) while an independent fast
  consumer stays current;
- long-poll park/wake, close-wakes-pollers, typed refusals;
- the ``sub.*`` metric namespace drift gate and the perf-sentinel
  ``sub`` lane feed.
"""

from __future__ import annotations

import threading
import time

import pytest

import hypergraphdb_tpu as hg
from hypergraphdb_tpu.query import conditions as c
from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime
from hypergraphdb_tpu.serve.types import QueueFull, RuntimeClosed, \
    Unservable
from hypergraphdb_tpu.sub import SubConfig, SubscriptionManager
from hypergraphdb_tpu.sub import wire as sub_wire
from hypergraphdb_tpu.sub.registry import match_digest
from hypergraphdb_tpu.sub.stats import DOTTED_NAMES, SubStats


def serve_cfg(**kw):
    kw.setdefault("buckets", (4,))
    kw.setdefault("max_linger_s", 0.001)
    kw.setdefault("prewarm_aot", False)
    return ServeConfig(**kw)


@pytest.fixture
def rig():
    """A small live graph + serving runtime + attached manager."""
    g = hg.HyperGraph()
    nodes = [int(g.add(i)) for i in range(8)]
    links = [int(g.add_link((nodes[0], nodes[k]), value=100 + k))
             for k in (1, 2, 3)]
    rt = ServeRuntime(g, serve_cfg())
    mgr = SubscriptionManager(g, rt)
    rt.attach_subscriptions(mgr)
    try:
        yield g, rt, mgr, nodes, links
    finally:
        mgr.close()
        rt.close(drain=False)
        g.close()


def settle(mgr, timeout=30.0):
    """Drive the evaluator until nothing is dirty or in flight."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        mgr.pump()
        with mgr._lock:
            busy = any(s.dirty or s.inflight is not None
                       for s in mgr.subs.all())
        if not busy:
            return
        time.sleep(0.005)
    raise AssertionError("subscriptions never settled")


def fold(matches, notes):
    """Client-side delta fold, asserting the chain + digest audit."""
    out = set(matches)
    for n in notes:
        assert n["what"] == "notification"
        out.difference_update(int(h) for h in n["removed"])
        out.update(int(h) for h in n["added"])
        assert n["digest"] == match_digest(out)
    return out


# --------------------------------------------------------------- envelopes


def test_subscribe_envelope_is_the_exact_initial_snapshot(rig):
    g, rt, mgr, nodes, links = rig
    resp = mgr.subscribe("pattern", {"anchors": [nodes[0]]})
    assert resp["what"] == "subscribed" and resp["kind"] == "pattern"
    assert resp["id"].startswith("sub-")
    want = {int(h) for h in g.find_all(c.Incident(nodes[0]))}
    assert set(resp["matches"]) == want == set(links)
    assert resp["digest"] == match_digest(want)
    assert resp["window"] == SubConfig().default_window
    out = mgr.unsubscribe(resp["id"])
    assert out == {"what": "unsubscribed", "id": resp["id"]}
    with pytest.raises(Unservable):
        mgr.poll(resp["id"], timeout_s=0.0)


def test_typed_refusals(rig):
    g, rt, mgr, nodes, links = rig
    with pytest.raises(Unservable):
        mgr.subscribe("tensor", {})                   # unknown kind
    with pytest.raises(Unservable):
        mgr.subscribe("pattern", {"anchors": [nodes[0]]}, window=0)
    with pytest.raises(Unservable):
        # top-k has no incremental delta semantics
        mgr.subscribe("range", {"lo": 1, "hi": 9, "limit": 4})
    with pytest.raises(Unservable):
        mgr.subscribe("range", {"lo": 1, "hi": 9, "desc": True})
    with pytest.raises(Unservable):
        mgr.poll("sub-999", timeout_s=0.0)
    with pytest.raises(Unservable):
        mgr.unsubscribe("sub-999")


def test_capacity_is_queue_full(rig):
    g, rt, mgr, nodes, links = rig
    mgr.config.max_subscriptions = 1
    mgr.subscribe("pattern", {"anchors": [nodes[0]]})
    with pytest.raises(QueueFull):
        mgr.subscribe("pattern", {"anchors": [nodes[1]]})


def test_closed_manager_refuses_subscribe(rig):
    g, rt, mgr, nodes, links = rig
    mgr.close()
    with pytest.raises(RuntimeClosed):
        mgr.subscribe("pattern", {"anchors": [nodes[0]]})


# ------------------------------------------------------ incremental deltas


def test_pattern_delta_chains_adds_and_removals(rig):
    g, rt, mgr, nodes, links = rig
    resp = mgr.subscribe("pattern", {"anchors": [nodes[0]]})
    sid = resp["id"]
    fresh = int(g.add_link((nodes[0], nodes[4]), value=999))
    settle(mgr)
    env = mgr.poll(sid, timeout_s=0.0)
    assert env["what"] == "notifications" and not env["more"]
    (note,) = env["notes"]
    assert note["seq_from"] == resp["seq"]          # chains off subscribe
    assert note["added"] == [fresh] and note["removed"] == []
    folded = fold(resp["matches"], [note])

    g.remove(fresh)
    settle(mgr)
    (note2,) = mgr.poll(sid, timeout_s=0.0)["notes"]
    assert note2["seq_from"] == note["seq_to"]      # consecutive chain
    assert note2["removed"] == [fresh] and note2["added"] == []
    folded = fold(folded, [note2])
    assert folded == {int(h) for h in g.find_all(c.Incident(nodes[0]))}


def test_two_pumps_one_finished_evaluation(rig):
    """The runtime's dispatch thread and a caller both pump. A finished
    evaluation belongs to exactly one of them: the pump that finds it
    claims it under the lock, and a second pump arriving while the first
    is still resolving leaves it alone — it neither resolves the answer
    again nor clears ``inflight`` under a later evaluation."""
    g, rt, mgr, nodes, links = rig
    rt.subscriptions = None          # this test does all the pumping
    resp = mgr.subscribe("pattern", {"anchors": [nodes[0]]})
    sub = mgr.subs.get(resp["id"])
    entered, release = threading.Event(), threading.Event()
    calls = []

    class Finished:
        truncated = False
        matches = list(resp["matches"]) + [nodes[5]]

        def done(self):
            return True

        def result(self):
            calls.append(threading.current_thread().name)
            entered.set()
            assert release.wait(30)
            return self

    evals0 = mgr.stats.evals
    with mgr._lock:
        sub.inflight = (Finished(), mgr.current_seq())
    first = threading.Thread(target=mgr._resolve_inflight, name="first")
    first.start()
    assert entered.wait(30)          # the first pump is mid-resolve
    mgr._resolve_inflight()          # the second finds nothing to own
    assert calls == ["first"] and mgr.stats.evals == evals0
    assert sub.inflight is not None  # still the first pump's to clear
    release.set()
    first.join(30)
    assert calls == ["first"] and mgr.stats.evals == evals0 + 1
    assert sub.inflight is None and not sub.resolving
    (note,) = mgr.poll(resp["id"], timeout_s=0.0)["notes"]
    assert note["added"] == [nodes[5]]


def test_irrelevant_ingest_never_fires(rig):
    g, rt, mgr, nodes, links = rig
    sid = mgr.subscribe("pattern", {"anchors": [nodes[0]]})["id"]
    evals_before = mgr.stats.evals
    g.add_link((nodes[5], nodes[6]), value=777)     # misses the anchor
    settle(mgr)
    env = mgr.poll(sid, timeout_s=0.0)
    assert env["notes"] == [] and not env["more"]
    # the incremental tier's whole point: no re-evaluation happened
    assert mgr.stats.evals == evals_before


def test_range_window_movement(rig):
    g, rt, mgr, nodes, links = rig
    resp = mgr.subscribe("range", {"lo": 100, "hi": 150})
    sid = resp["id"]
    assert set(resp["matches"]) == set(links)       # values 101..103
    inside = int(g.add(120))
    g.add(4242)                                     # outside the window
    settle(mgr)
    notes = mgr.poll(sid, timeout_s=0.0)["notes"]
    assert [n["added"] for n in notes] == [[inside]]
    # value moves OUT of the window via replace -> removal delta
    g.replace(inside, 9999)
    settle(mgr)
    (note,) = mgr.poll(sid, timeout_s=0.0)["notes"]
    assert note["removed"] == [inside]


def test_bfs_removal_uses_precommit_targets(rig):
    g, rt, mgr, nodes, links = rig
    resp = mgr.subscribe("bfs", {"seed": nodes[0], "max_hops": 1})
    sid = resp["id"]
    assert nodes[1] in set(resp["matches"])
    # removing the link makes nodes[1] unreachable; its targets are only
    # readable BEFORE the commit (the HGAtomRemoveRequestEvent capture)
    g.remove(links[0])
    settle(mgr)
    folded = fold(resp["matches"], mgr.poll(sid, timeout_s=0.0)["notes"])
    want = resp_matches_now = {
        int(nbr) for _, nbr in __import__(
            "hypergraphdb_tpu.algorithms.traversals",
            fromlist=["HGBreadthFirstTraversal"],
        ).HGBreadthFirstTraversal(g, nodes[0], max_distance=1)
    }
    assert folded == want
    assert nodes[1] not in folded


# ------------------------------------------------- backpressure / delivery


def test_slow_consumer_sheds_to_resync_fast_stays_current(rig):
    g, rt, mgr, nodes, links = rig
    slow = mgr.subscribe("pattern", {"anchors": [nodes[0]]}, window=1)
    fast = mgr.subscribe("pattern", {"anchors": [nodes[0]]}, window=64)
    folded = set(fast["matches"])
    for k in range(3):                 # 3 deltas > the slow window of 1
        g.add_link((nodes[0], nodes[4 + k]), value=500 + k)
        settle(mgr)
        # the fast consumer drains every round and stays current
        folded = fold(folded, mgr.poll(fast["id"], timeout_s=0.0)["notes"])
    want = {int(h) for h in g.find_all(c.Incident(nodes[0]))}
    assert folded == want
    # the slow consumer overflowed: typed resync with the EXACT set,
    # never a silent gap
    env = mgr.poll(slow["id"], timeout_s=0.0)
    assert env["what"] == "resync"
    assert set(env["matches"]) == want
    assert env["digest"] == match_digest(want)
    assert mgr.stats.shed > 0
    snap = mgr.stats.snapshot()
    assert snap["sub.resyncs"] == 1
    # after the resync the queue chain restarts cleanly
    g.add_link((nodes[0], nodes[7]), value=909)
    settle(mgr)
    env2 = mgr.poll(slow["id"], timeout_s=0.0)
    assert env2["what"] == "notifications"
    assert env2["notes"][0]["seq_from"] >= env["seq"]


def test_long_poll_parks_until_a_delta_arrives(rig):
    g, rt, mgr, nodes, links = rig
    sid = mgr.subscribe("pattern", {"anchors": [nodes[0]]})["id"]
    out = {}

    def park():
        out["env"] = mgr.poll(sid, timeout_s=10.0)

    t = threading.Thread(target=park)
    t.start()
    time.sleep(0.05)
    g.add_link((nodes[0], nodes[5]), value=321)
    settle(mgr)
    t.join(timeout=10)
    assert not t.is_alive()
    assert out["env"]["notes"], "parked poll never woke on the delta"


def test_close_wakes_parked_pollers(rig):
    g, rt, mgr, nodes, links = rig
    sid = mgr.subscribe("pattern", {"anchors": [nodes[0]]})["id"]
    out = {}

    def park():
        try:
            mgr.poll(sid, timeout_s=30.0)
        except Unservable as e:
            out["err"] = e

    t = threading.Thread(target=park)
    t.start()
    time.sleep(0.05)
    mgr.close()
    t.join(timeout=10)
    assert not t.is_alive() and "err" in out


def test_poll_batches_and_reports_more(rig):
    g, rt, mgr, nodes, links = rig
    sid = mgr.subscribe("pattern", {"anchors": [nodes[0]]},
                        window=16)["id"]
    for k in range(3):
        g.add_link((nodes[0], nodes[4 + k]), value=600 + k)
        settle(mgr)                    # one delta per settled round
    env = mgr.poll(sid, max_notes=2, timeout_s=0.0)
    assert len(env["notes"]) == 2 and env["more"]
    env2 = mgr.poll(sid, max_notes=2, timeout_s=0.0)
    assert len(env2["notes"]) == 1 and not env2["more"]
    assert env2["notes"][0]["seq_from"] == env["notes"][-1]["seq_to"]


# ----------------------------------------------------- seq / health / perf


def test_seq_source_anchors_notifications(rig):
    g, rt, mgr, nodes, links = rig
    ext = {"seq": 41}
    mgr._seq_source = lambda: ext["seq"]
    resp = mgr.subscribe("pattern", {"anchors": [nodes[0]]})
    assert resp["seq"] >= 41           # anchored at the external clock
    ext["seq"] = 57
    g.add_link((nodes[0], nodes[6]), value=808)
    settle(mgr)
    (note,) = mgr.poll(resp["id"], timeout_s=0.0)["notes"]
    assert note["seq_to"] >= 57
    assert note["seq_from"] == resp["seq"]


def test_health_section_shape(rig):
    g, rt, mgr, nodes, links = rig
    mgr.subscribe("pattern", {"anchors": [nodes[0]]})
    h = mgr.health_section()
    assert h["active"] == 1 and h["violating"] is False
    assert h["bound_s"] == mgr.config.staleness_bound_s
    assert {"dirty", "inflight", "staleness_s", "notified_total",
            "shed_total"} <= set(h)


def test_manager_feeds_the_perf_sentinel_sub_lane(rig):
    g, rt, mgr, nodes, links = rig
    samples = []

    class Tap:
        def observe(self, kind, latency_s, path="device", t=None):
            samples.append((kind, latency_s))

    rt.perf = Tap()
    sid = mgr.subscribe("pattern", {"anchors": [nodes[0]]})["id"]
    g.add_link((nodes[0], nodes[4]), value=111)
    settle(mgr)
    assert mgr.poll(sid, timeout_s=0.0)["notes"]
    subs = [(k, lat) for k, lat in samples if k == "sub"]
    assert len(subs) == 1 and subs[0][1] >= 0.0


def test_metrics_namespace_no_drift():
    assert set(SubStats().snapshot()) == set(DOTTED_NAMES)


# ------------------------------------------------------------ wire decoding


def test_wire_subscribe_and_poll_payloads(rig):
    g, rt, mgr, nodes, links = rig
    resp = sub_wire.subscribe_payload(mgr, {
        "what": "subscribe", "kind": "pattern", "anchors": [nodes[0]],
        "window": 8,
    })
    assert resp["what"] == "subscribed" and resp["window"] == 8
    g.add_link((nodes[0], nodes[5]), value=222)
    settle(mgr)
    env = sub_wire.poll_payload(mgr, {"id": resp["id"],
                                      "timeout_s": "0", "max": "16"})
    assert env["what"] == "notifications" and env["notes"]
    out = sub_wire.subscribe_payload(mgr, {"what": "unsubscribe",
                                           "id": resp["id"]})
    assert out["what"] == "unsubscribed"


def test_wire_refusals_are_typed(rig):
    g, rt, mgr, nodes, links = rig
    with pytest.raises(Unservable):
        sub_wire.subscribe_payload(mgr, {"what": "subscribe"})
    with pytest.raises(Unservable):
        sub_wire.subscribe_payload(mgr, {"what": "subscribe",
                                         "kind": "pattern"})
    with pytest.raises(Unservable):
        sub_wire.subscribe_payload(mgr, {"what": "subscribe",
                                         "kind": "bfs"})
    with pytest.raises(Unservable):
        sub_wire.subscribe_payload(mgr, {"what": "frobnicate"})
    with pytest.raises(Unservable):
        sub_wire.poll_payload(mgr, {})
    with pytest.raises(Unservable):
        sub_wire.poll_payload(mgr, {"id": "sub-1", "timeout_s": "soon"})


def test_wire_poll_timeout_is_clamped(rig):
    g, rt, mgr, nodes, links = rig
    sid = sub_wire.subscribe_payload(mgr, {
        "what": "subscribe", "kind": "pattern", "anchors": [nodes[0]],
    })["id"]
    t0 = time.monotonic()
    env = sub_wire.poll_payload(mgr, {"id": sid, "timeout_s": 9999},
                                max_timeout_s=0.05)
    assert time.monotonic() - t0 < 5.0
    assert env["notes"] == []
