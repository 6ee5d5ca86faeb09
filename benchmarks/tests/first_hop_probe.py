#!/usr/bin/env python3
"""The sparse first hop of ``ops.bfs_pull`` at a cell's size, outside the cell.

    python3 benchmarks/tests/first_hop_probe.py --workload <cell> --seed <n>

One process on the chip, one JSON line per part (``PERF.md`` section 6, PR 26,
holds the readings):

- ``density``: the share of the bitmap's rows that hold a bit entering hops
  2 and 3 (after 1 and 2 hops from the cell's own kind of seeds);
- ``hub``: a whole traversal whose seeds hold the graph's top hub — programs
  compiled or loaded while it ran (none: the placement has one shape), and
  the hub's column and a few more against the plain reference;
- ``crossover``: one hop from seeds whose first ``m`` columns are the ``m``
  widest entities, on the sparse side and on the pull chain, whichever the
  rule would take (the rule's constant is set in this process, as a test
  sets it): seconds and pair counts on both sides of where the two meet.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from harness import phase_total, refs  # noqa: E402


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def sparse_phase_s() -> float:
    return phase_total.seconds("hg.bfs.hop.sparse") or 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--widest", default="0,1,4,16,64,256",
                    help="the crossover's m values")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import bfs_pull
    from hypergraphdb_tpu.ops import ellbfs as eb

    spec = run.load_cell(args.workload, args.rehearse)
    cfg, traffic = spec["config"], spec["traffic"]
    run.place_caches()
    compiles = run.CompileCount()
    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=dev.device_kind)
    sut = run.load_module("builders", cfg["builder"]).build(cfg, args.seed, {})
    snap, n = sut.snap, sut.n_atoms
    e0, e1 = sut.entities
    rng = np.random.default_rng([args.seed, 26])
    k, hops = traffic["seeds"], traffic["hops"]
    plans = eb.plans_for(snap)
    limit = plans.total_indices // eb.SPARSE_SHARE

    def fresh() -> np.ndarray:
        return rng.integers(e0, e1, size=k).astype(np.int32)

    def traverse(seeds, h):
        t0 = time.perf_counter()
        res = bfs_pull(snap, seeds, h, chunk=traffic["chunk"],
                       k_block=traffic["k_block"])
        jax.block_until_ready((res.visited_t, res.reach_counts))
        return res, time.perf_counter() - t0

    @jax.jit
    def rows_with_a_bit(bitmap):
        return jnp.sum(jnp.any(bitmap != 0, axis=1))

    # every shape the parts below use, compiled before any is timed
    for h in (hops, 1):
        res, _ = traverse(fresh(), h)
        rows_with_a_bit(res.visited_t).block_until_ready()
        res = None

    # ---- density entering hops 2 and 3
    seeds = fresh()
    for h in range(1, hops):
        res, s = traverse(seeds, h)
        say("density", entering_hop=h + 1, seconds=s,
            rows_with_a_bit=int(rows_with_a_bit(res.visited_t)), rows=n,
            mean_reach=float(np.mean(np.asarray(res.reach_counts))))
        res = None

    # ---- a traversal whose seeds hold the top hub
    deg = np.diff(snap.inc_offsets[: n + 1].astype(np.int64))
    widest = e0 + np.argsort(-deg[e0:e1], kind="stable")
    seeds = fresh()
    seeds[0] = widest[0]
    sl = eb._seed_links(snap, seeds, 1 << 62)
    t_mark, sparse0 = time.perf_counter(), sparse_phase_s()
    res, s = traverse(seeds, hops)
    programs = compiles.since(t_mark)
    cols = [0] + [int(c) for c in rng.choice(np.arange(1, k), 7,
                                             replace=False)]
    want = refs.host_bfs_bits(n, sut.flat, sut.link_of, n, seeds[cols], hops)
    got = np.zeros(n, dtype=np.uint64)
    for j, c in enumerate(cols):
        word = np.asarray(res.visited_t[:n, c // 32])
        got |= ((word >> np.uint32(c % 32)) & np.uint32(1)).astype(
            np.uint64) << np.uint64(j)
    counts = np.asarray(res.reach_counts)[cols]
    want_counts = [len(c) for c in refs.bits_columns(want, len(cols))]
    say("hub", hub=int(widest[0]), hub_degree=int(deg[widest[0]]),
        pairs=int(sl.arity.sum()), limit=limit, seconds=s,
        sparse_phase_s=sparse_phase_s() - sparse0,
        programs_while_it_ran=programs,
        bitmap_rows_differ=int(np.count_nonzero(got != want)),
        counts_differ=int(sum(int(a) != b
                              for a, b in zip(counts, want_counts))),
        columns_compared=len(cols))
    res = None

    # ---- one hop on each side, by pair count
    share = eb.SPARSE_SHARE
    for m in [int(x) for x in args.widest.split(",")]:
        seeds = fresh()
        seeds[:m] = widest[:m]
        sl = eb._seed_links(snap, seeds, 1 << 62)
        out = {"widest": m, "links": len(sl.links),
               "pairs": int(sl.arity.sum()), "limit": limit}
        answers = {}
        for side, forced in (("sparse", 1), ("dense", 1 << 62)):
            eb.SPARSE_SHARE = forced
            sparse0 = sparse_phase_s()
            res, s = traverse(seeds, 1)
            out[f"{side}_s"] = s
            if side == "sparse":
                out["sparse_phase_s"] = sparse_phase_s() - sparse0
            answers[side] = (np.asarray(res.reach_counts),
                             res.edges_touched,
                             int(rows_with_a_bit(res.visited_t)))
            res = None
        eb.SPARSE_SHARE = share
        out["answers_equal"] = bool(
            np.array_equal(answers["sparse"][0], answers["dense"][0])
            and np.array_equal(answers["sparse"][1], answers["dense"][1])
            and answers["sparse"][2] == answers["dense"][2])
        say("crossover", **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
