"""Calibration sweep for the planner's duality constants (VERDICT r4
item 10): the zig-zag/merge size ratio in ``query/compiler.intersect_sorted``
and ``QueryConfig.device_min_batch`` gating host vs device intersections.

Run on the TPU host: ``python tools/calibrate_duality.py``. Prints a
machine-readable JSON block. No run on the current chip is recorded:
the pinned constants date from the round-5 set-up (ROADMAP queue 1
item 3).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ID_SPACE = 10_000_000  # the 10M-atom graph's id space (BASELINE configs 3/4)


def _sorted_sample(rng, n: int) -> np.ndarray:
    return np.unique(rng.integers(0, ID_SPACE, size=int(n * 1.1)))[: n].astype(
        np.int64
    )


def _time(fn, reps: int = 5) -> float:
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def sweep_zigzag() -> dict:
    """Crossover ratio where searchsorted probing beats np.intersect1d."""
    rng = np.random.default_rng(7)
    out = {}
    for small_n in (1_000, 10_000, 100_000):
        small = _sorted_sample(rng, small_n)
        rows = {}
        for ratio in (2, 4, 8, 16, 32, 64, 128, 256):
            large = _sorted_sample(rng, min(small_n * ratio, 9_000_000))
            if len(large) < small_n * ratio * 0.9:
                continue  # id space exhausted; ratio not reachable

            def probe():
                pos = np.minimum(
                    np.searchsorted(large, small), len(large) - 1
                )
                return small[large[pos] == small]

            def merge():
                return np.intersect1d(small, large, assume_unique=True)

            rows[ratio] = {
                "probe_ms": round(_time(probe) * 1e3, 3),
                "merge_ms": round(_time(merge) * 1e3, 3),
            }
        # first ratio where probing wins and keeps winning
        cross = None
        for r in sorted(rows):
            if rows[r]["probe_ms"] < rows[r]["merge_ms"]:
                if all(
                    rows[r2]["probe_ms"] <= rows[r2]["merge_ms"]
                    for r2 in rows if r2 >= r
                ):
                    cross = r
                    break
        out[small_n] = {"rows": rows, "crossover_ratio": cross}
    return out


def sweep_device_min_batch() -> dict:
    """Crossover size where the device intersection (incl. transfers)
    beats the host path, for a 2-way intersection with an 8× larger
    partner — the planner's gating shape (smallest child's estimate)."""
    import hypergraphdb_tpu.query.compiler as qc
    from hypergraphdb_tpu.ops.setops import device_intersect_sorted

    rng = np.random.default_rng(11)
    rows = {}
    for n in (64, 256, 1_024, 4_096, 16_384, 65_536, 262_144):
        a = _sorted_sample(rng, n)
        b = _sorted_sample(rng, min(n * 8, 8_000_000))

        host_ms = _time(lambda: qc.intersect_sorted(None, a, b)) * 1e3
        dev_ms = _time(lambda: device_intersect_sorted([a, b])) * 1e3
        rows[n] = {
            "host_ms": round(host_ms, 3),
            "device_ms": round(dev_ms, 3),
        }
    cross = None
    for n in sorted(rows):
        if rows[n]["device_ms"] < rows[n]["host_ms"]:
            cross = n
            break
    return {"rows": rows, "crossover_smallest_child": cross}


def sweep_value_conj() -> dict:
    """Crossover for the OTHER device_min_batch consumer: a single ad-hoc
    And(incident(hub), value) query through the snapshot-RESIDENT value
    kernel (DeviceValueConjPlan — no bulk upload per query, just a launch)
    vs the host fallback, at varying hub incidence size."""
    from hypergraphdb_tpu import HyperGraph
    from hypergraphdb_tpu.query import dsl as q
    from hypergraphdb_tpu.query.compiler import (
        DeviceValueConjPlan,
        compile_query,
    )

    g = HyperGraph()
    rng = np.random.default_rng(3)
    hubs = {}
    spokes = list(g.add_nodes_bulk([f"s{i}" for i in range(1024)]))
    for n in (1_024, 8_192, 65_536, 262_144):
        hub = g.add(f"hub{n}")
        g.bulk_import(
            values=[int(x) for x in rng.integers(0, 1000, size=n)],
            target_lists=[
                [int(hub), int(spokes[i % 1024])] for i in range(n)
            ],
        )
        hubs[n] = hub
    g.snapshot()  # resident base
    rows = {}
    cross = None
    for n, hub in hubs.items():
        cond = q.and_(q.incident(hub), q.value(500, "gt"))
        cq = compile_query(g, cond)
        assert isinstance(cq.plan, DeviceValueConjPlan)
        g.config.query.device_min_batch = 0        # force device
        dev_ms = _time(lambda: cq.plan.run(g), reps=3) * 1e3
        g.config.query.device_min_batch = 1 << 60  # force host fallback
        host_ms = _time(lambda: cq.plan.run(g), reps=3) * 1e3
        rows[n] = {
            "host_ms": round(host_ms, 3), "device_ms": round(dev_ms, 3),
        }
        if cross is None and dev_ms < host_ms:
            cross = n
    g.close()
    return {"rows": rows, "crossover_incidence": cross}


def sweep_parallel_or() -> dict:
    """Does the OrToParellelQuery-style thread pool actually buy anything
    for index-read children (VERDICT r4 weak #5: 'GIL mirage')? Or of 8
    by-value eq sets over a 400K-atom graph, parallel vs sequential."""
    from hypergraphdb_tpu import HyperGraph
    from hypergraphdb_tpu.query import dsl as q
    from hypergraphdb_tpu.query.compiler import compile_query

    g = HyperGraph()
    rng = np.random.default_rng(5)
    g.bulk_import(
        values=[int(x) for x in rng.integers(0, 8, size=400_000)]
    )
    cond = q.or_(*[q.eq(i) for i in range(8)])
    g.config.query.parallel_or = False
    seq = compile_query(g, cond)
    g.config.query.parallel_or = True
    par = compile_query(g, cond)
    seq_ms = _time(lambda: seq.plan.run(g), reps=3) * 1e3
    par_ms = _time(lambda: par.plan.run(g), reps=3) * 1e3
    g.close()
    return {
        "sequential_ms": round(seq_ms, 2),
        "parallel_ms": round(par_ms, 2),
        "parallel_speedup": round(seq_ms / par_ms, 2),
    }


def main() -> None:
    import jax

    report = {
        "platform": str(jax.devices()[0]),
        "zigzag": sweep_zigzag(),
        "device_min_batch": sweep_device_min_batch(),
        "value_conj": sweep_value_conj(),
        "parallel_or": sweep_parallel_or(),
    }
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
