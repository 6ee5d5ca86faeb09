"""Deterministic serving-runtime tests: injectable clock + fake executor.

Every test here drives the admission/batching/dispatch machinery with
``ServeConfig(manual=True)`` (no thread), a :class:`FakeClock`, and a
:class:`FakeExecutor` — deadline shedding, backpressure, flush policy,
double-buffer ordering, and drains are exactly reproducible with zero
device work. The real device path is covered by
``test_serve_differential.py``; the threaded soak runs under ``slow``.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from hypergraphdb_tpu.serve import (
    Batcher,
    DeadlineExceeded,
    QueueFull,
    RuntimeClosed,
    ServeConfig,
    ServeResult,
    ServeRuntime,
    bucket_for,
)
from hypergraphdb_tpu.serve.types import BFSRequest, PatternRequest, Ticket


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeExecutor:
    """Records launch/collect ordering; completes every ticket with a
    stub result."""

    def __init__(self):
        self.events: list[tuple] = []
        self.batches: list = []

    def launch(self, batch):
        self.events.append(("launch", len(self.batches)))
        self.batches.append(batch)
        return (len(self.batches) - 1, batch)

    def collect(self, token):
        idx, batch = token
        self.events.append(("collect", idx))
        return [
            (t, ServeResult(t.request.kind, 0,
                            np.empty(0, dtype=np.int64), False, 0, "fake"))
            for t in batch.tickets
        ]


def make_runtime(clock=None, buckets=(4, 16), max_queue=64,
                 policy="block", linger=0.010, **kw):
    cfg = ServeConfig(buckets=buckets, max_queue=max_queue, policy=policy,
                      max_linger_s=linger, clock=clock or FakeClock(),
                      manual=True, **kw)
    ex = FakeExecutor()
    return ServeRuntime(graph=None, config=cfg, executor=ex), ex, cfg.clock


# ---------------------------------------------------------------- buckets


def test_bucket_for_picks_smallest_fitting():
    assert bucket_for(1, (64, 256, 1024)) == 64
    assert bucket_for(64, (64, 256, 1024)) == 64
    assert bucket_for(65, (64, 256, 1024)) == 256
    assert bucket_for(1024, (64, 256, 1024)) == 1024
    with pytest.raises(ValueError):
        bucket_for(1025, (64, 256, 1024))


# ---------------------------------------------------------------- deadlines


def test_deadline_expiry_sheds_before_dispatch():
    rt, ex, clock = make_runtime()
    fut = rt.submit_bfs(1, max_hops=2, deadline_s=0.5)
    clock.advance(1.0)  # expire in the queue
    assert rt.step(drain=True) is False  # shed, nothing left to dispatch
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=0)
    assert ex.batches == []  # the dead request never cost a dispatch
    assert rt.stats.shed_deadline == 1
    assert rt.stats.batches == 0


def test_expired_requests_shed_live_ones_dispatch():
    rt, ex, clock = make_runtime()
    dead = rt.submit_bfs(1, deadline_s=0.5)
    live = rt.submit_bfs(2, deadline_s=10.0)
    clock.advance(1.0)
    assert rt.step(drain=True) is True
    with pytest.raises(DeadlineExceeded):
        dead.result(timeout=0)
    assert live.result(timeout=0).kind == "bfs"
    (batch,) = ex.batches
    assert [t.request.seed for t in batch.tickets] == [2]


def test_already_expired_submit_sheds_immediately():
    rt, ex, clock = make_runtime(policy="block", max_queue=1)
    rt.submit_bfs(1)  # fill the queue
    fut = rt.submit_bfs(2, deadline_s=0.0)  # would block; already expired
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=0)
    assert rt.queue.depth() == 1  # the shed request never entered
    # accounting identity: submitted == completed + shed + cancelled + live
    assert rt.stats.submitted == 2
    assert rt.stats.shed_deadline == 1


def test_serve_result_eq_and_hash_do_not_raise():
    r1 = ServeResult("bfs", 2, np.asarray([1, 2]), False, 0)
    r2 = ServeResult("bfs", 2, np.asarray([1, 2]), False, 0)
    assert (r1 == r2) is False      # identity eq — never elementwise
    assert r1 == r1
    assert isinstance(hash(r1), int)
    assert len({r1, r2}) == 2


# ---------------------------------------------------------------- backpressure


def test_fail_fast_policy_raises_queue_full():
    rt, ex, _ = make_runtime(policy="fail", max_queue=2)
    rt.submit_bfs(1)
    rt.submit_bfs(2)
    with pytest.raises(QueueFull):
        rt.submit_bfs(3)
    assert rt.stats.rejected_queue_full == 1
    assert rt.stats.submitted == 2


def test_block_policy_blocks_until_space():
    rt, ex, clock = make_runtime(policy="block", max_queue=1, linger=0.0)
    rt.submit_bfs(1)
    admitted = threading.Event()

    def submit_second():
        rt.submit_bfs(2)
        admitted.set()

    t = threading.Thread(target=submit_second, daemon=True)
    t.start()
    assert not admitted.wait(0.15)  # genuinely blocked on the full queue
    assert rt.step(drain=True)      # drain frees a slot
    assert admitted.wait(2.0)       # blocked submit completes
    t.join(2.0)
    assert rt.queue.depth() == 1


# ---------------------------------------------------------------- flush policy


def test_flush_on_batch_full_ignores_linger():
    rt, ex, clock = make_runtime(linger=1e9)  # linger can never expire
    futs = [rt.submit_bfs(i) for i in range(16)]  # == largest bucket
    assert rt.step() is True
    (batch,) = ex.batches
    assert batch.bucket == 16 and len(batch.tickets) == 16
    assert all(f.result(timeout=0).kind == "bfs" for f in futs)
    assert rt.stats.batches == 1


def test_key_cap_bounds_what_one_flush_takes():
    """An executor's per-key cap (a bucket whose device program does not
    fit the chip): that key's group is FULL at the cap, one flush takes
    at most the cap, and a bucket past it is never formed; other keys
    keep the largest bucket."""
    rt, ex, clock = make_runtime(linger=1e9)  # linger can never expire
    rt.batcher.key_cap = lambda key: 4 if key[0] == "bfs" else None
    bfs = [rt.submit_bfs(i) for i in range(10)]
    pat = [rt.submit_pattern([1, 2]) for _ in range(10)]
    assert rt.step() is True and rt.step() is True   # 4 + 4: full at cap
    assert rt.step() is False           # 2 left: neither full nor lingered
    assert [(b.key[0], b.bucket, len(b.tickets)) for b in ex.batches] == [
        ("bfs", 4, 4), ("bfs", 4, 4)]
    while rt.step(drain=True):
        pass
    assert [(b.key[0], b.bucket, len(b.tickets)) for b in ex.batches[2:]] \
        == [("bfs", 4, 2), ("pattern", 16, 10)]
    assert all(f.result(timeout=0) is not None for f in bfs + pat)


def test_no_flush_before_linger_then_flush_after():
    rt, ex, clock = make_runtime(linger=0.010)
    fut = rt.submit_bfs(7)
    assert rt.step() is False           # neither full nor lingered
    assert ex.batches == []
    clock.advance(0.011)
    assert rt.step() is True            # linger expired → flush partial
    (batch,) = ex.batches
    assert batch.bucket == 4            # padded to the SMALLEST fitting bucket
    assert len(batch.tickets) == 1
    assert fut.result(timeout=0).served_by == "fake"
    assert rt.stats.snapshot()["batch_occupancy"] == pytest.approx(0.25)


def test_batches_group_by_key_oldest_first():
    rt, ex, clock = make_runtime(linger=0.0)
    b1 = rt.submit_bfs(1, max_hops=2)
    p1 = rt.submit_pattern([1, 2])
    b2 = rt.submit_bfs(2, max_hops=2)
    b3 = rt.submit_bfs(3, max_hops=3)   # different statics → different key
    assert rt.step() is True
    assert rt.step() is True
    assert rt.step() is True
    assert rt.step() is False
    keys = [b.key for b in ex.batches]
    # oldest ticket defines each flushed group; FIFO across keys
    assert keys == [("bfs", 2), ("pattern", 2), ("bfs", 3)]
    assert [t.request.seed for t in ex.batches[0].tickets] == [1, 2]
    for f in (b1, p1, b2, b3):
        assert f.result(timeout=0) is not None


# ---------------------------------------------------------------- pipelining


def test_pump_launches_next_before_collecting_previous():
    rt, ex, clock = make_runtime(linger=0.0)
    rt.submit_bfs(1)
    assert rt.pump() is True            # launch B0, nothing to collect yet
    rt.submit_bfs(2)
    assert rt.pump() is True            # launch B1 THEN collect B0
    rt.pump()                           # nothing new: collect B1
    assert ex.events == [
        ("launch", 0), ("launch", 1), ("collect", 0), ("collect", 1),
    ]


# ---------------------------------------------------------------- shutdown


def test_close_drains_queued_and_inflight():
    rt, ex, clock = make_runtime(linger=1e9)
    futs = [rt.submit_bfs(i) for i in range(6)]
    rt.submit_pattern([1, 2])
    rt.pump(drain=True)                 # leave one batch in flight
    rt.close(drain=True)
    for f in futs:
        assert f.result(timeout=0).served_by == "fake"
    assert rt.stats.completed == 7
    with pytest.raises(RuntimeClosed):
        rt.submit_bfs(99)


def test_close_without_drain_cancels_queued():
    rt, ex, clock = make_runtime(linger=1e9)
    futs = [rt.submit_bfs(i) for i in range(3)]
    rt.close(drain=False)
    for f in futs:
        with pytest.raises(RuntimeClosed):
            f.result(timeout=0)
    assert rt.stats.cancelled == 3
    assert ex.batches == []


def test_context_manager_drains():
    clock = FakeClock()
    cfg = ServeConfig(buckets=(4,), clock=clock, manual=True,
                      max_linger_s=1e9)
    ex = FakeExecutor()
    with ServeRuntime(graph=None, config=cfg, executor=ex) as rt:
        fut = rt.submit_bfs(1)
    assert fut.result(timeout=0).kind == "bfs"


# ---------------------------------------------------------------- stats


def test_stats_surface_shape():
    rt, ex, clock = make_runtime(linger=0.0)
    rt.submit_bfs(1)
    clock.advance(0.004)
    rt.step(drain=True)
    snap = rt.stats_snapshot()
    assert snap["submitted"] == 1 and snap["completed"] == 1
    assert snap["queue_depth"] == 0
    assert snap["batches"] == 1
    assert snap["latency_ms"]["p50"] == pytest.approx(4.0)
    assert snap["latency_ms"]["p99"] == pytest.approx(4.0)
    assert snap["batch_occupancy"] == pytest.approx(0.25)


def _benchmark_counters() -> tuple:
    """``COUNTERS`` of ``benchmarks/drivers/closed_loop.py``, read from the
    file's source: importing the driver needs ``benchmarks/`` on the path
    and pulls in its harness."""
    import ast
    from pathlib import Path

    src = (Path(__file__).resolve().parents[1] / "benchmarks" / "drivers"
           / "closed_loop.py").read_text()
    for node in ast.parse(src).body:
        if (isinstance(node, ast.Assign)
                and [getattr(t, "id", None) for t in node.targets]
                == ["COUNTERS"]):
            return tuple(ast.literal_eval(node.value))
    raise AssertionError("closed_loop.py no longer assigns COUNTERS")


@pytest.mark.parametrize("name", _benchmark_counters())
def test_stats_snapshot_holds_every_counter_the_benchmark_reads(name):
    """The served cell's driver reads ``snap[name]`` for every name of its
    ``COUNTERS`` and subtracts two snapshots: each must be an integer key
    of ``stats_snapshot()`` from the start, before any request. The guard
    on a key that is kept for that reader alone (``serve/stats.py``)."""
    rt, _, _ = make_runtime()
    snap = rt.stats_snapshot()
    assert name in snap
    assert type(snap[name]) is int and snap[name] == 0


# ---------------------------------------------------------------- requests


def test_pattern_request_validation():
    from hypergraphdb_tpu.serve.types import Unservable

    with pytest.raises(Unservable):
        PatternRequest(())
    assert PatternRequest((np.int64(3), 4)).anchors == (3, 4)
    assert BFSRequest(1, 2).batch_key != BFSRequest(1, 3).batch_key
    assert PatternRequest((1, 2)).batch_key == PatternRequest((9, 8)).batch_key
    assert PatternRequest((1, 2)).batch_key != PatternRequest((1, 2, 3)).batch_key


def test_batcher_rejects_bad_buckets():
    from hypergraphdb_tpu.serve import AdmissionQueue

    q = AdmissionQueue(4)
    with pytest.raises(ValueError):
        Batcher(q, buckets=(16, 4))  # unsorted
    with pytest.raises(ValueError):
        AdmissionQueue(4, policy="bogus")


# ------------------------------------------------------- review regressions


def test_cancelled_future_does_not_poison_dispatch():
    """A caller cancel()ing a pending future must not raise out of the
    dispatch path (InvalidStateError) or count as a completion."""
    rt, ex, clock = make_runtime(linger=0.0)
    f1 = rt.submit_bfs(1)
    f2 = rt.submit_bfs(2)
    assert f1.cancel()
    assert rt.step(drain=True) is True   # no exception escapes
    assert f2.result(timeout=0).kind == "bfs"
    assert rt.stats.completed == 1       # the cancelled one is not counted
    f3 = rt.submit_bfs(3)                # runtime still serves
    rt.step(drain=True)
    assert f3.result(timeout=0).kind == "bfs"


class ExplodingExecutor(FakeExecutor):
    """Fails the FIRST launch, then behaves."""

    def __init__(self):
        super().__init__()
        self.exploded = False

    def launch(self, batch):
        if not self.exploded:
            self.exploded = True
            raise RuntimeError("device fell over")
        return super().launch(batch)


def test_executor_launch_error_fails_tickets_not_runtime():
    clock = FakeClock()
    cfg = ServeConfig(buckets=(4,), clock=clock, manual=True,
                      max_linger_s=0.0)
    ex = ExplodingExecutor()
    rt = ServeRuntime(graph=None, config=cfg, executor=ex)
    f1 = rt.submit_bfs(1)
    assert rt.step(drain=True) is True
    with pytest.raises(RuntimeError, match="device fell over"):
        f1.result(timeout=0)
    f2 = rt.submit_bfs(2)                # the next batch serves normally
    rt.step(drain=True)
    assert f2.result(timeout=0).kind == "bfs"
    rt.close()
