"""``python benchmarks/run.py --selfcheck``: the yardstick against known
answers. Needs no chip and nothing of the program.

- the trace reducer on hand-made planes whose answers are worked out by
  hand, and on one small trace recorded on a TPU v5e
  (``tests/data/small_trace.xplane.pb``, made by
  ``tests/record_small_trace.py``), whose answers were read off it by hand
  with ``tests/trace_summary.py``;
- the byte functions on one tiny graph, against hand-computed values.
"""

from __future__ import annotations

import os
from types import SimpleNamespace as NS

from harness import bytes_model, trace_reduce

_HERE = os.path.dirname(os.path.abspath(__file__))
SMALL_TRACE = os.path.join(os.path.dirname(_HERE), "tests", "data",
                           "small_trace.xplane.pb")

#: read off the recorded trace by hand (see the module docstring): the
#: modules' three runs each (3611 + 3593 + 3561 ns, 5416 + 5324 + 5387 ns),
#: the nanoseconds that the 33 operations cover, first start to last end
SMALL_TRACE_KNOWN = {
    "module_runs": {"jit_small_matmul": 3, "jit_small_scan": 3},
    "modules_ns": {"jit_small_matmul": 10765, "jit_small_scan": 16127},
    "host_spans": 9,
    "busy_ns": 26828,
    "span_ns": 35884954,
}


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def check_reducer_by_hand() -> None:
    us = 1_000
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(11)", 0, 100 * us),
                                       ev("jit_b(22)", 300 * us, 100 * us),
                                       ev("jit_a(33)", 500 * us, 50 * us)]),
        # two overlapping operations, a short gap, a long gap under a host
        # span, a long gap under none
        NS(name="XLA Ops", events=[ev("fusion.1", 0, 60 * us),
                                   ev("copy.2", 40 * us, 60 * us),
                                   ev("fusion.1", 110 * us, 40 * us),
                                   ev("gather.3", 300 * us, 100 * us),
                                   ev("fusion.1", 500 * us, 50 * us)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.outer", 100 * us, 300 * us),
        ev("hg.serve.bfs[K=64,s=(2,),slot=1]", 200 * us, 60 * us),
        ev("unrelated", 0, 900 * us)])])
    got = trace_reduce.reduce_planes([device, host], window_s=1e-3)
    # busy: [0,100] + [110,150] + [300,400] + [500,550] = 290 us
    assert abs(got["busy_s"] - 290e-6) < 1e-12, got["busy_s"]
    assert abs(got["idle_share"] - 0.71) < 1e-9, got["idle_share"]
    assert {k: round(v * 1e6) for k, v in got["modules"].items()} == \
        {"jit_a": 150, "jit_b": 100}, got["modules"]
    assert got["module_runs"] == {"jit_a": 2, "jit_b": 1}
    ops = dict(got["breakdown"]["device_ops"])
    assert abs(ops["fusion.1"] - 150e-6) < 1e-12 and \
        abs(ops["gather.3"] - 100e-6) < 1e-12, ops
    gaps = dict(got["breakdown"]["idle_gaps"])
    # 100-110: short; 150-300 (middle 225): the innermost span is the
    # program's, its slot dropped; 400-500 (middle 450): no span
    assert abs(gaps[trace_reduce.SHORT_GAP] - 10e-6) < 1e-12, gaps
    assert abs(gaps["hg.serve.bfs[K=64,s=(2,)]"] - 150e-6) < 1e-12, gaps
    assert abs(gaps["no annotated span"] - 100e-6) < 1e-12, gaps
    assert trace_reduce.reduce_planes([host])["devices"] == 0


def check_reducer_on_recorded_trace() -> None:
    known = SMALL_TRACE_KNOWN
    got = trace_reduce.reduce_file(SMALL_TRACE, window_s=0.04)
    assert got["devices"] == 1, got
    assert got["module_runs"] == known["module_runs"], got["module_runs"]
    assert {k: round(v * 1e9) for k, v in got["modules"].items()} == \
        known["modules_ns"], got["modules"]
    assert got["host_spans"] == known["host_spans"], got["host_spans"]
    assert round(got["busy_s"] * 1e9) == known["busy_ns"], got["busy_s"]
    assert round(got["traced_span_s"] * 1e9) == known["span_ns"]
    assert abs(got["idle_share"] - (1 - 26828e-9 / 0.04)) < 1e-12
    gaps = dict(got["breakdown"]["idle_gaps"])
    # the gaps are the span less the busy time; three pauses of 10 ms each
    # lie between a matmul and the scan after it
    assert round(sum(gaps.values()) * 1e9) == \
        known["span_ns"] - known["busy_ns"], gaps
    assert gaps["bench.pause"] > 0.03 and gaps["bench.pause"] == max(
        gaps.values()), gaps
    ops = dict(got["breakdown"]["device_ops"])
    assert "multiply_add_fusion" in ops and "copy-done" in ops, ops


def check_bytes_by_hand() -> None:
    # a tiny graph: 10 atoms, 6 incidence and 6 target entries, 64 seeds
    assert bytes_model.relation_bytes(10, 6, 6) == 4 * 12 + 8 * 11 == 136
    # per hop: bitmap 2 * 10 rows * 8 bytes = 160, relations 136
    assert bytes_model.traverse_bytes(10, 6, 6, seeds=64, hops=3) == 3 * 296
    # 64 two-hop and 32 three-hop questions: 2*(160+136) + 3*(80+136)
    assert bytes_model.served_bfs_bytes(10, 6, 6, {2: 64, 3: 32, 4: 0}) \
        == 592 + 648
    # the issue's arithmetic for the 10M-atom traversal: ~32 GB, ~39 ms
    n, e = 10_000_065, 48_012_742
    b = bytes_model.traverse_bytes(n, e, e, seeds=4096, hops=3)
    assert b == 3 * (2 * n * 512 + 8 * e + 8 * (n + 1)) == 32_112_507_072, b
    share = bytes_model.roofline_share_pct(b, 13.0, "TPU v5 lite")
    assert abs(share - 100 * (b / 819e9) / 13.0) < 1e-12 and 0.30 < share < 0.31
    try:
        bytes_model.peaks("TPU v99")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def main() -> int:
    for check in (check_reducer_by_hand, check_reducer_on_recorded_trace,
                  check_bytes_by_hand):
        check()
        print(f"selfcheck: {check.__name__} ok")
    return 0
