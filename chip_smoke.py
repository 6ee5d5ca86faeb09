#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on ONE TPU chip, through the entry points a user
calls, and checks every answer against a plain host reference:

- ``device``   JAX finds a TPU (else: exit 1, no result); the XLA compile
               cache is placed by the rule ``bench.py`` shares.
- ``kernels``  the 10,000,065-atom / 48M-arity benchmark snapshot
               (``models.dbpedia_snapshot``): 1024 conjunctive patterns
               through ``plan_pattern → execute_pattern → collect_pattern``;
               a 3-hop pull BFS from 4096 seeds through ``ops.bfs_pull``,
               once as the code selects it (the staged chain on the
               Pallas gather) and once with the Pallas gather switched
               off (the XLA gather), the two equal
               in all 4096 columns and, in 64 columns spread over the
               bitmap's words, equal to a numpy BFS; ``gather_or`` and
               ``intersect_sorted_pallas`` at one real-width shape each.
- ``serve``    a ``HyperGraph`` loaded through ``bulk_import`` (3M atoms
               through the real store and type system), ``enable_incremental``,
               a ``ServeRuntime`` with the default ``ServeConfig``: BFS,
               pattern, range, join and planned requests on a quiet graph;
               a same-key BFS burst wide enough to fill the largest bucket
               (the executor caps BFS batches at the widest bucket whose
               dense program fits the chip — the burst must run at that
               width, full, and form nothing wider); the mix again under
               concurrent ingest, and — after a forced compaction — over
               the new atoms; a second runtime must warm-hit the AOT cache
               and serve from the loaded executables; the runtime's own
               counters must show the DEVICE answered.
- ``--four-chips``  ONLY the mesh-sharded serving phase and what it is
               compared with (needs four devices; the driver runs one chip).

One JSON object per phase on stdout; the last line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
One process; never sets ``JAX_PLATFORMS``; exits non-zero the moment a
phase fails. ``--scale tiny`` is the CPU rehearsal and is refused unless
the caller set ``JAX_PLATFORMS=cpu``. All data comes from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import threading
import time
from functools import partial

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: sizes per scale. ``full`` is what the driver runs on the chip; ``tiny``
#: is the CPU rehearsal (same control flow, Pallas kernels interpreted).
SCALES = {
    "full": dict(
        # kernels: the r05 benchmark graph (T + entities + links =
        # 10,000,065 rows)
        kern_entities=2_000_000, kern_links=8_000_000,
        pairs=1024, seeds=4096, ref_seeds=64, hops=3,
        gather_rows=1 << 20, gather_idx=1 << 17,
        isect_base=60_000, isect_other=65_536,
        # serve: 3M atoms. Every valued atom takes a second handle and
        # enable_incremental()'s default headroom doubles the lot: an id
        # space of 12,582,912 after the pad — a coarse one, the streaming
        # lever (SnapshotManager): the base keeps its shapes, and so its
        # executables, across the compaction
        serve_entities=1_000_000, serve_links=2_000_000,
        pad_multiple=1 << 21,
        stage1=256, burst=2048, stage2=128, stage3=64, new_entities=5_000,
        shard_bfs=128, shard_pattern=128,
    ),
    "tiny": dict(
        kern_entities=300, kern_links=900,
        pairs=48, seeds=64, ref_seeds=64, hops=3,
        gather_rows=512, gather_idx=2048,
        isect_base=900, isect_other=1024,
        serve_entities=420, serve_links=520,
        pad_multiple=128,
        stage1=48, burst=160, stage2=24, stage3=24, new_entities=40,
        shard_bfs=16, shard_pattern=16,
    ),
}

#: link values live above every entity value, new links above those —
#: so range windows address exactly one population
LINK_VALUE0 = 1_000_000_000
NEW_LINK_VALUE0 = 2_000_000_000


class PhaseFailed(Exception):
    """A phase's check did not hold; the message says which."""


class NoAccelerator(Exception):
    """JAX found no TPU and nobody asked for the CPU rehearsal."""


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def note(msg: str) -> None:
    """Progress, on stderr: stdout carries the phase lines only."""
    print(f"chip_smoke [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ------------------------------------------------------- host references
#
# Plain numpy, independent of the code under test: they read the
# generator's own arrays (or a snapshot's host target table), never a
# device result and never the system's planners.


def host_bfs_bits(n_ids: int, flat: np.ndarray, link_of: np.ndarray,
                  n_links: int, seeds: np.ndarray, hops: int) -> np.ndarray:
    """Bit-parallel BFS for up to 64 seeds: bit k of ``out[v]`` says seed
    k reaches atom v within ``hops`` (seed included). ``flat[e]`` is the
    target atom of incidence entry e and ``link_of[e]`` (non-decreasing)
    its link: a hop is 'a link is live when any of its targets is
    visited; every target of a live link is reached'."""
    require(len(seeds) <= 64, "host_bfs_bits takes at most 64 seeds")
    order = np.argsort(flat, kind="stable")
    flat_s, link_s = flat[order], link_of[order]
    grp = np.flatnonzero(np.r_[True, flat_s[1:] != flat_s[:-1]])
    grp_ids = flat_s[grp]
    lst = np.flatnonzero(np.r_[True, link_of[1:] != link_of[:-1]])
    lst_ids = link_of[lst]
    vis = np.zeros(n_ids, dtype=np.uint64)
    np.bitwise_or.at(vis, seeds,
                     np.uint64(1) << np.arange(len(seeds), dtype=np.uint64))
    for _ in range(hops):
        live = np.zeros(n_links, dtype=np.uint64)
        live[lst_ids] = np.bitwise_or.reduceat(vis[flat], lst)
        vis[grp_ids] |= np.bitwise_or.reduceat(live[link_s], grp)
    return vis


def bits_column(vis: np.ndarray, k: int) -> np.ndarray:
    """Sorted atom ids whose bit ``k`` is set."""
    return np.flatnonzero((vis >> np.uint64(k)) & np.uint64(1))


def snapshot_incidence(snap):
    """(flat, link_of, n_links) of a CSRSnapshot's HOST target table."""
    n1 = snap.num_atoms + 1
    off = np.asarray(snap.tgt_offsets[: n1 + 1], dtype=np.int64)
    arity = np.diff(off)
    flat = np.asarray(snap.tgt_flat[: int(off[-1])], dtype=np.int64)
    link_of = np.repeat(np.arange(n1, dtype=np.int64), arity)
    return flat, link_of, n1


def bitmap_columns(visited_t: np.ndarray, n_rows: int,
                   cols: np.ndarray) -> np.ndarray:
    """Seed columns ``cols`` (at most 64) of a transposed ``(rows, Kw)``
    uint32 bitmap as one uint64 word per row: bit j is column ``cols[j]``."""
    out = np.zeros(n_rows, dtype=np.uint64)
    for j, k in enumerate(cols.tolist()):
        bit = (visited_t[:n_rows, k // 32] >> np.uint32(k % 32)) & np.uint32(1)
        out |= bit.astype(np.uint64) << np.uint64(j)
    return out


# ------------------------------------------------------------- the phases


class Smoke:
    def __init__(self, scale_name: str, seed: int, rehearsal: bool):
        self.scale_name = scale_name
        self.s = SCALES[scale_name]
        self.seed = seed
        self.rehearsal = rehearsal
        self.dev = None

    # -- device ---------------------------------------------------------------
    def phase_device(self) -> dict:
        import jax

        from hypergraphdb_tpu.utils.compile_cache import (
            cache_entries,
            place_compile_cache,
        )

        t0 = time.perf_counter()
        devices = jax.devices()
        self.dev = devices[0]
        if self.dev.platform != "tpu" and not self.rehearsal:
            raise NoAccelerator(self.dev.platform)
        cache_dir = place_compile_cache(HERE)
        self.compile_events = {"hits": 0, "misses": 0}

        def on_event(name: str, **_kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.compile_events["hits"] += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.compile_events["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        self.device_json = {
            "platform": self.dev.platform,
            "kind": self.dev.device_kind,
            "count": len(devices),
        }
        return {
            **self.device_json,
            "rehearsal": self.rehearsal,
            "scale": self.scale_name,
            "seed": self.seed,
            "compile_cache_dir": cache_dir,
            "compile_cache_from_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "compile_cache_was_empty": cache_entries(cache_dir) == 0,
            "memory_bytes_limit": (self.dev.memory_stats() or {}).get(
                "bytes_limit"),
            "seconds": round(time.perf_counter() - t0, 3),
        }

    def peak_bytes(self):
        stats = self.dev.memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    # -- kernels --------------------------------------------------------------
    def phase_kernels(self) -> dict:
        t_phase = time.perf_counter()
        out: dict = {}
        from hypergraphdb_tpu import models
        from hypergraphdb_tpu.ops import pallas_gather

        on_tpu = self.dev.platform == "tpu"
        if on_tpu:
            # a refused kernel raises out of this; False would mean the
            # program decided to serve without its kernel
            require(pallas_gather.pallas_ok(), "pallas_ok() is False on a "
                    "TPU (HG_PALLAS_GATHER veto set?)")
        t0 = time.perf_counter()
        snap, info = models.dbpedia_snapshot(
            n_entities=self.s["kern_entities"], n_links=self.s["kern_links"],
            seed=self.seed + 13,
        )
        out["graph"] = {"atoms": info["n_atoms"],
                        "total_arity": info["total_arity"],
                        "build_s": round(time.perf_counter() - t0, 1)}
        for leg, run in (
                ("pattern", lambda: self._leg_pattern(snap, info)),
                ("bfs", lambda: self._leg_bfs(snap, info)),
                ("gather_or", self._leg_gather_or),
                ("intersect", self._leg_intersect)):
            out[leg] = run()
            self._drop_device_state(snap)
            note(f"kernels: {leg} {out[leg]}")
        out["seconds"] = round(time.perf_counter() - t_phase, 1)
        return out

    @staticmethod
    def _drop_device_state(snap) -> None:
        """Free a snapshot's cached device arrays (bench.py's discipline
        between configs): the next leg needs most of the chip."""
        snap.__dict__.pop("device", None)  # cached_property storage
        for attr in ("_tgt_ell", "_value_cols", "_pull_device"):
            if hasattr(snap, attr):
                object.__delattr__(snap, attr)

    def _leg_pattern(self, snap, info) -> dict:
        import jax

        from hypergraphdb_tpu.ops.setops import (
            collect_pattern,
            execute_pattern,
            plan_pattern,
        )

        r = np.random.default_rng(self.seed + 1)
        K = self.s["pairs"]
        th = int(max(info["property_types"],
                     key=lambda t: len(snap.type_set(t))))
        cands = snap.type_set(th)
        n_hit = (3 * K) // 4
        links = cands[r.integers(0, len(cands), size=n_hit)].astype(np.int64)
        starts = snap.tgt_offsets[links].astype(np.int64)
        pairs = np.stack([snap.tgt_flat[starts], snap.tgt_flat[starts + 1]],
                         axis=1).astype(np.int64)
        e0, e1 = info["entities"]
        pairs = np.concatenate(
            [pairs, r.integers(e0, e1, size=(K - n_hit, 2))])
        # cold = plan + compile + one run; warm = one run
        t0 = time.perf_counter()
        plan = plan_pattern(snap, pairs.astype(np.int32), th)
        pending = execute_pattern(plan)
        jax.block_until_ready([x for _, c, f in pending for x in (c, f)])
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        pending = execute_pattern(plan)
        jax.block_until_ready([x for _, c, f in pending for x in (c, f)])
        warm = time.perf_counter() - t0
        got = collect_pattern(plan, pending)
        # host engine: the smaller anchor's incidence row, each candidate
        # link kept when its own target tuple holds the other anchor and
        # its type is the asked one
        inc_off, inc_links = snap.inc_offsets, snap.inc_links
        tgt_off, tgt_flat, type_of = (snap.tgt_offsets, snap.tgt_flat,
                                      snap.type_of)
        nonempty = 0
        for qi, (a, b) in enumerate(pairs.tolist()):
            if inc_off[a + 1] - inc_off[a] > inc_off[b + 1] - inc_off[b]:
                a, b = b, a
            want = []
            for lk in inc_links[inc_off[a]: inc_off[a + 1]].tolist():
                ts = tgt_flat[tgt_off[lk]: tgt_off[lk + 1]]
                if type_of[lk] == th and (ts == b).any() and (ts == a).any():
                    want.append(lk)
            want = sorted(set(want))
            nonempty += bool(want)
            require(got[qi].tolist() == want,
                    f"pattern {qi} {(a, b)}: device {got[qi].tolist()[:8]} "
                    f"!= host {want[:8]}")
        require(nonempty >= n_hit, "pattern leg: too few non-empty results")
        return {"queries": K, "nonempty": nonempty, "equal_host": True,
                "buckets": [int(p) for _, _, p in plan.buckets],
                "compile_s": round(cold - warm, 3), "run_s": round(warm, 4),
                "peak_bytes": self.peak_bytes()}

    def _bfs_twice(self, fn):
        """``fn()`` cold then warm; returns (host bitmap of the warm run,
        reach counts, edges touched, compile_s, run_s). Each result is
        dropped before the next run — one 4096-seed bitmap at 10M rows is
        5.1 GB and the staged chain needs the rest of the chip."""
        import jax

        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready(res.visited_t)
        cold = time.perf_counter() - t0
        del res
        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready(res.visited_t)
        warm = time.perf_counter() - t0
        host = np.asarray(res.visited_t)
        reach = np.asarray(res.reach_counts)
        edges = np.asarray(res.edges_touched)
        del res
        return host, reach, edges, max(cold - warm, 0.0), warm

    def _check_vs_host_bfs(self, snap, seeds, host_t, reach, what) -> int:
        """Hold ``ref_seeds`` columns — spread over the whole width of the
        bitmap, so every second word of a row is looked at — to a numpy
        BFS on the host CSR. Returns how many columns were held."""
        n_ref = min(self.s["ref_seeds"], len(seeds))
        cols = (np.arange(n_ref) * (len(seeds) - 1)) // max(n_ref - 1, 1)
        flat, link_of, n1 = snapshot_incidence(snap)
        ref = host_bfs_bits(n1, flat, link_of, n1, seeds[cols],
                            self.s["hops"])
        got = bitmap_columns(host_t, snap.num_atoms, cols)
        bad = np.flatnonzero(got != ref[: snap.num_atoms])
        require(len(bad) == 0,
                f"{what}: visited sets differ from the host BFS at "
                f"{len(bad)} atoms (first {bad[:4].tolist()})")
        for j, k in enumerate(cols.tolist()):
            require(int(reach[k]) == len(bits_column(ref, j)),
                    f"{what}: reach count of seed {k} differs from host")
        return n_ref

    #: the gather chunk bench.py c4 runs a 4096-seed block at: the XLA
    #: gather's transient is chunk x 8 rows of 512 bytes, and at 10M atoms
    #: the hop's widest step has ~1.5 GB to spare on the Pallas gather and
    #: half of that on the XLA gather (tests/test_tpu_compile.py) — which
    #: therefore runs at half the chunk
    PULL_CHUNK = 1 << 16

    def _pull(self, snap, seeds, chunk=PULL_CHUNK):
        from hypergraphdb_tpu.ops import bfs_pull

        return bfs_pull(snap, seeds, self.s["hops"], chunk=chunk,
                        k_block=self.s["seeds"])

    def _counting_gather(self):
        """Swap ``pallas_gather.gather_or`` for a counting twin: whether
        a leg traced the Pallas gather is read from what ran."""
        from hypergraphdb_tpu.ops import pallas_gather

        calls = {"n": 0}
        real = pallas_gather.gather_or

        def counted(values, idx, w, interpret=False):
            calls["n"] += 1
            require(not interpret, "gather_or ran in interpret mode")
            return real(values, idx, w, interpret)

        pallas_gather.gather_or = counted
        return calls, lambda: setattr(pallas_gather, "gather_or", real)

    def _leg_bfs(self, snap, info) -> dict:
        """3-hop pull BFS from 4096 seeds on the benchmark graph through
        ``ops.bfs_pull``, the entry the traversal API calls. First with
        what the code selects there: the staged chain with the Pallas
        gather under its 128-word rows. Then with the program's own switch
        for that gather off (``HG_PALLAS_GATHER=0``: the XLA gather). The
        two must agree in every column, and the first is held to the host
        BFS."""
        from hypergraphdb_tpu.ops.ellbfs import plans_for

        r = np.random.default_rng(self.seed + 2)
        e0, e1 = info["entities"]
        seeds = r.integers(e0, e1, size=self.s["seeds"]).astype(np.int32)
        t0 = time.perf_counter()
        plans_for(snap)
        plan_s = time.perf_counter() - t0
        on_tpu = self.dev.platform == "tpu"
        legs: dict = {}
        ref = None
        for leg, env, chunk in (
                ("pallas_gather", {}, self.PULL_CHUNK),
                ("xla_gather", {"HG_PALLAS_GATHER": "0"},
                 self.PULL_CHUNK // 2)):
            calls, restore = self._counting_gather()
            os.environ.update(env)
            try:
                got = self._bfs_twice(lambda: self._pull(snap, seeds, chunk))
            finally:
                restore()
                for name in env:
                    del os.environ[name]
            legs[leg] = {"compile_s": round(got[3], 2),
                         "run_s": round(got[4], 3), "chunk": chunk,
                         "peak_bytes": self.peak_bytes(),
                         "pallas_gather_traced": calls["n"] > 0}
            self._drop_device_state(snap)
            if ref is None:
                ref = got
            else:
                require(np.array_equal(ref[0], got[0]),
                        "visited sets differ between the Pallas and the "
                        "XLA gather")
                require(np.array_equal(ref[1], got[1])
                        and np.array_equal(ref[2], got[2]),
                        "reach/edge counts differ between the Pallas and "
                        "the XLA gather")
            del got
        if on_tpu:
            require(legs["pallas_gather"]["pallas_gather_traced"]
                    and not legs["xla_gather"]["pallas_gather_traced"],
                    f"gather legs did not take their paths: {legs}")
        n_ref = self._check_vs_host_bfs(snap, seeds, ref[0], ref[1],
                                        "bfs_pull")
        return {
            "seeds": len(seeds), "hops": self.s["hops"],
            "plan_build_s": round(plan_s, 1),
            "edges_touched": int(ref[2].sum()),
            **legs, "legs_equal": True, "equal_host_seeds": n_ref,
        }

    def _cold_warm(self, fn):
        """``fn()`` cold (compile + run) then warm (run); the interpreted
        rehearsal runs it once — there is no compile to separate."""
        t0 = time.perf_counter()
        out = fn()
        cold = warm = time.perf_counter() - t0
        if self.dev.platform == "tpu":
            t0 = time.perf_counter()
            out = fn()
            warm = time.perf_counter() - t0
        return out, cold, warm

    def _leg_gather_or(self) -> dict:
        import jax
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops import pallas_gather as pg

        r = np.random.default_rng(self.seed + 5)
        rows, n_idx, w = self.s["gather_rows"], self.s["gather_idx"], 8
        values = r.integers(0, 1 << 32, size=(rows, pg.ROW_WORDS),
                            dtype=np.uint64).astype(np.uint32)
        idx = r.integers(0, rows, size=n_idx).astype(np.int32)
        interpret = self.dev.platform != "tpu"
        vd, idd = jnp.asarray(values), jnp.asarray(idx)
        # jitted, as every caller in the tree runs it (un-jitted, the
        # pallas_call is lowered again on every call)
        fn = jax.jit(partial(pg.gather_or, w=w, interpret=interpret))
        out, cold, warm = self._cold_warm(
            lambda: jax.block_until_ready(fn(vd, idd)))
        want = np.bitwise_or.reduce(
            values[idx].reshape(-1, w, pg.ROW_WORDS), axis=1)
        require(np.array_equal(np.asarray(out), want),
                "gather_or differs from numpy")
        return {"values": [rows, pg.ROW_WORDS], "indices": n_idx, "w": w,
                "interpreted": interpret, "equal_host": True,
                "compile_s": round(max(cold - warm, 0), 3),
                "run_s": round(warm, 4), "peak_bytes": self.peak_bytes()}

    def _leg_intersect(self) -> dict:
        from functools import reduce

        from hypergraphdb_tpu.ops.pallas_kernels import intersect_sorted_pallas

        r = np.random.default_rng(self.seed + 6)
        nb, no = self.s["isect_base"], self.s["isect_other"]
        universe = 4 * no
        arrays = [np.sort(r.choice(universe, size=n, replace=False))
                  .astype(np.int64) for n in (nb, no, no, no)]
        interpret = self.dev.platform != "tpu"
        got, cold, warm = self._cold_warm(
            lambda: intersect_sorted_pallas(arrays, interpret=interpret))
        want = reduce(np.intersect1d, arrays)
        require(np.array_equal(got, want),
                "intersect_sorted_pallas differs from numpy")
        return {"base": nb, "others": [3, no], "matches": int(len(want)),
                "interpreted": interpret, "equal_host": True,
                "compile_s": round(max(cold - warm, 0), 3),
                "run_s": round(warm, 4)}

    # -- serve ----------------------------------------------------------------
    def phase_serve(self) -> dict:
        from hypergraphdb_tpu.obs.trace import Tracer
        from hypergraphdb_tpu.plan import QueryPlanner
        from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

        t_phase = time.perf_counter()
        out: dict = {}
        sg = ServeGraph(self.s, self.seed)
        out["graph"] = sg.describe()
        note(f"serve: graph loaded {out['graph']}")
        g = sg.g
        t0 = time.perf_counter()
        mgr = g.enable_incremental(
            pack_pad_multiple=self.s["pad_multiple"])
        out["graph"]["id_space"] = int(mgr.base.num_atoms)
        out["graph"]["first_pack_s"] = round(time.perf_counter() - t0, 1)
        # the default ServeConfig, told where its AOT cache lives and
        # given a tracer of its own: a BFS request sent with explain=True
        # comes back with the bucket it rode, from the request's own trace
        aot_dir = os.path.join(HERE, ".aot_cache")
        cfg = ServeConfig(aot_cache_dir=aot_dir, tracer=Tracer().enable())
        out["config"] = {"buckets": list(cfg.buckets), "top_r": cfg.top_r,
                         "aot_cache_dir": aot_dir}
        warnings = _WarningTap().install()
        try:
            t0 = time.perf_counter()
            rt = ServeRuntime(g, cfg)
            out["runtime_start_s"] = round(time.perf_counter() - t0, 1)
            note(f"serve: runtime up in {out['runtime_start_s']} s")
            rt.attach_planner(QueryPlanner(g))
            top_r = cfg.top_r
            # the widest BFS batch the executor will form on this chip
            cap = rt.executor.bfs_bucket_cap()
            out["config"]["bfs_bucket_cap"] = cap
            widest = cap or cfg.buckets[-1]
            try:
                # the second runtime: same graph, same AOT directory — it
                # must load what the first one stored AND serve from it
                out["aot_warm"] = self._second_runtime(sg, cfg, top_r)
                note(f"serve: second runtime {out['aot_warm']}")
                # stage 1: a quiet graph
                reqs = sg.requests(self.s["stage1"], self.seed + 21, "old")
                s1 = self._drive(rt, sg, reqs, top_r)
                require(s1["served_by_host"] == 0,
                        f"stage 1 (quiet graph): {s1['served_by_host']} "
                        f"requests fell back to the host: {s1['host_kinds']}")
                out["stage1"] = s1
                note(f"serve: stage 1 {s1}")
                # burst: one key, more requests than the largest bucket
                # holds — the widest bucket the executor admits must run
                # FULL on the chip, and nothing wider may form
                reqs = sg.requests_of({"bfs": self.s["burst"]},
                                      self.seed + 24)
                for q in reqs:
                    q["hops"] = cfg.default_max_hops
                sb = self._drive(rt, sg, reqs, top_r, cross=False)
                require(sb["served_by_host"] == 0,
                        f"burst: {sb['served_by_host']} host answers")
                if len(reqs) >= 2 * widest:
                    require(sb["bfs_buckets"].get(str(widest), {})
                            .get("widest_batch") == widest,
                            f"burst of {len(reqs)}: no full batch at the "
                            f"widest admitted bucket {widest}: "
                            f"{sb['bfs_buckets']}")
                out["burst"] = sb
                note(f"serve: burst {sb}")
                # stage 2: the same kinds of request in flight while a
                # writer ingests a component no old seed can reach
                reqs = sg.requests(self.s["stage2"], self.seed + 22, "old")
                writer = threading.Thread(target=sg.ingest, name="ingest")
                writer.start()
                s2 = self._drive(rt, sg, reqs, top_r)
                writer.join(timeout=600)
                require(not writer.is_alive(), "ingest did not finish")
                require(sg.ingest_error is None,
                        f"ingest failed: {sg.ingest_error!r}")
                by_contract = sum(1 for q in reqs if q["kind"] == "join")
                require(s2["served_by_host"] <= by_contract,
                        f"stage 2: {s2['served_by_host']} host answers, only "
                        f"{by_contract} joins may go to the host under a "
                        f"dirty memtable: {s2['host_kinds']}")
                s2["host_by_contract_max"] = by_contract
                s2["ingested_atoms"] = sg.n_new_atoms
                out["stage2"] = s2
                note(f"serve: stage 2 {s2}")
                # one forced compaction bakes the new atoms into the base
                before = mgr.compactions
                t0 = time.perf_counter()
                mgr._request_compact()
                require(mgr.wait_compacted(timeout=600),
                        "compaction did not finish")
                require(mgr.compactions > before, "no compaction happened")
                out["compaction"] = {
                    "passes": mgr.compactions - before,
                    "seconds": round(time.perf_counter() - t0, 2),
                    "id_space": int(mgr.base.num_atoms),
                    "edges": int(mgr.base.n_edges_inc),
                }
                # stage 3: answers that exist only because of the new atoms
                reqs = sg.requests(self.s["stage3"], self.seed + 23, "new")
                s3 = self._drive(rt, sg, reqs, top_r)
                newer = sum(1 for q in reqs
                            if max(q["atoms"]) >= mgr.base.num_atoms)
                require(s3["served_by_host"] <= newer,
                        f"stage 3 (after compaction): {s3['served_by_host']}"
                        f" host answers, {newer} seeds are newer than the "
                        f"base: {s3['host_kinds']}")
                s3["seeds_newer_than_base"] = newer
                out["stage3"] = s3
                note(f"serve: stage 3 {s3}")
                snap = rt.stats_snapshot()
            finally:
                rt.close()
        finally:
            warnings.remove()
        g.close()
        out["stats"] = {k: snap.get(k) for k in (
            "submitted", "completed", "device_dispatches", "host_fallbacks",
            "range_dispatches", "errors", "retries",
            "breaker_trips", "shed_deadline", "batches", "batch_occupancy")}
        out["aot"] = snap.get("aot")
        out["warnings"] = warnings.messages[:8]
        st = out["stats"]
        require(st["device_dispatches"] > 0, "no device dispatch at all")
        require(st["errors"] == 0, f"serve.errors = {st['errors']}")
        require(st["breaker_trips"] == 0,
                f"breaker tripped {st['breaker_trips']} times")
        allowed = (out["stage2"]["host_by_contract_max"]
                   + out["stage3"]["seeds_newer_than_base"])
        require(st["host_fallbacks"] <= allowed,
                f"host_fallbacks {st['host_fallbacks']} > {allowed} that go "
                f"to the host by contract")
        aot = out["aot"] or {}
        require(aot.get("corrupt", 0) == 0 and aot.get("stale", 0) == 0,
                f"AOT cache reports failures: {aot}")
        # (every message of the AOT paths begins "aot ...")
        require(not any(": aot" in m.lower() for m in warnings.messages),
                f"AOT warnings: {warnings.messages[:3]}")
        # the buckets that ran, from the requests' traces
        ran = sorted({int(b) for k in ("stage1", "burst", "stage2", "stage3")
                      for b in out[k]["bfs_buckets"]})
        require(all(b <= widest for b in ran),
                f"a BFS batch formed past the executor's cap {cap}: {ran}")
        out["bfs_entry_by_bucket"] = {str(b): "unfused" for b in ran}
        out["seconds"] = round(time.perf_counter() - t_phase, 1)
        return out

    def _second_runtime(self, sg, cfg, top_r) -> dict:
        from hypergraphdb_tpu.serve import ServeRuntime

        t0 = time.perf_counter()
        rt2 = ServeRuntime(sg.g, cfg)
        start_s = time.perf_counter() - t0
        try:
            aot = rt2.stats_snapshot().get("aot") or {}
            # (a refusal leaves nothing to cache: where the executor caps
            # BFS batches, the first bucket past the cap is asked again)
            asked_again = rt2.executor.bfs_bucket_cap() is not None
            require(aot.get("disk_hits", 0) > 0
                    and aot.get("misses", 1) == asked_again,
                    f"second runtime did not warm-hit the AOT cache: {aot}")
            # and the LOADED executables answer (on the right devices)
            reqs = [q for q in sg.requests(32, self.seed + 20, "old")
                    if q["kind"] in ("bfs", "pattern")]
            res = self._drive(rt2, sg, reqs, top_r)
            after = rt2.stats_snapshot()
            require(after["errors"] == 0 and after["breaker_trips"] == 0,
                    "loaded executables failed at execute time")
            require(res["served_by_host"] == 0,
                    "second runtime answered from the host")
        finally:
            rt2.close()
        return {"start_s": round(start_s, 2), "aot": aot,
                "requests_served": res["requests"]}

    def _drive(self, rt, sg, reqs, top_r, cross=True) -> dict:
        """Submit every request, wait for every answer, then — outside
        any timing — hold each answer to the host reference (and, with
        ``cross``, the reference to the repo's host engine)."""
        t0 = time.perf_counter()
        futs = [sg.submit(rt, q) for q in reqs]
        results = [f.result(timeout=900) for f in futs]
        wall = time.perf_counter() - t0
        sg.prime_bfs_refs(reqs)     # 64 BFS references per host pass
        kinds: dict = {}
        host_kinds: dict = {}
        buckets: dict = {}
        crossed = 0
        for q, f, res in zip(reqs, futs, results):
            crossed += sg.check(q, res, top_r, cross)
            kinds[q["kind"]] = kinds.get(q["kind"], 0) + 1
            planned_host = (q["kind"] == "planned"
                            and res.plan.get("shape") == "host")
            if res.served_by == "host" and not planned_host:
                host_kinds[q["kind"]] = host_kinds.get(q["kind"], 0) + 1
            elif q["kind"] == "bfs":
                ex = f.explain
                slot = buckets.setdefault(
                    str(ex["bucket"]), {"requests": 0, "widest_batch": 0})
                slot["requests"] += 1
                slot["widest_batch"] = max(slot["widest_batch"],
                                           ex["lanes_real"])
        return {"requests": len(reqs), "kinds": kinds, "all_equal_host": True,
                "also_equal_find_all": crossed, "bfs_buckets": buckets,
                "served_by_host": sum(host_kinds.values()),
                "host_kinds": host_kinds, "wall_s": round(wall, 2)}

    # -- four chips -----------------------------------------------------------
    def phase_sharded(self) -> dict:
        import jax

        from hypergraphdb_tpu.serve import (
            DeviceExecutor,
            ServeConfig,
            ServeRuntime,
            ShardedExecutor,
        )

        t_phase = time.perf_counter()
        require(len(jax.devices()) == 4,
                f"--four-chips needs 4 devices, JAX reports "
                f"{len(jax.devices())}")
        out: dict = {}
        sg = ServeGraph(self.s, self.seed)
        out["graph"] = sg.describe()
        g = sg.g
        mgr = g.enable_incremental(
            pack_pad_multiple=self.s["pad_multiple"])
        cfg = dict(prewarm_aot=False)
        rt_sh = ServeRuntime(g, ServeConfig(sharded=True, **cfg))
        rt_one = ServeRuntime(g, ServeConfig(sharded=False, **cfg))
        try:
            require(isinstance(rt_sh.executor, ShardedExecutor)
                    and type(rt_one.executor) is DeviceExecutor,
                    "executors are not (sharded, single-chip)")
            top_r = ServeConfig().top_r
            reqs = sg.requests_of(
                {"bfs": self.s["shard_bfs"],
                 "pattern": self.s["shard_pattern"]}, self.seed + 31)
            t0 = time.perf_counter()
            futs = [sg.submit(rt_sh, q) for q in reqs]
            got_sh = [f.result(timeout=900) for f in futs]
            sh_wall = time.perf_counter() - t0
            futs = [sg.submit(rt_one, q) for q in reqs]
            got_one = [f.result(timeout=900) for f in futs]
            sg.prime_bfs_refs(reqs)
            for q, a, b in zip(reqs, got_sh, got_one):
                sg.check(q, a, top_r)     # == host
                require(a.count == b.count and a.truncated == b.truncated
                        and np.array_equal(np.asarray(a.matches),
                                           np.asarray(b.matches)),
                        f"sharded != single-chip for {q}")
                require(a.served_by == "device" and b.served_by == "device",
                        f"served_by {a.served_by}/{b.served_by} for {q}")
            # every device of the mesh holds a shard of the snapshot
            view = mgr.pinned_view(sharded=True)
            sb = view.sharded_base
            # (real entries, not padding: pad edges carry the dummy row)
            held = {}
            for name in ("inc_src", "tgt_src"):
                shards = getattr(sb, name).addressable_shards
                held[name] = sorted(
                    (int(s.device.id),
                     int((np.asarray(s.data) != sb.num_atoms).sum()))
                    for s in shards)
                # rows are owned by gid range, so a device whose range is
                # all entities (or all headroom) holds none of a relation:
                # the counts are printed, the check is that the arrays sit
                # on four devices and no relation sits on one alone
                require(len({int(s.device.id) for s in shards}) == 4
                        and all(np.prod(s.data.shape) > 0 for s in shards),
                        f"{name} is not laid out over the mesh")
            real = [sum(n for name in held for d, n in held[name] if d == i)
                    for i in sorted({d for d, _ in held["inc_src"]})]
            require(sum(1 for n in real if n > 0) >= 2,
                    f"every real edge sits on one device: {held}")
            st = rt_sh.stats_snapshot()
            require(st["sharded_dispatches"] > 0, "no sharded dispatch")
            # every answer of the sharded runtime resolved through the
            # mesh lane, every answer of the other through one chip
            lanes = {f"{k}.{p}": n for (k, p), n in
                     rt_sh.stats.lane_counts().items() if n}
            require(lanes == {"bfs.sharded": self.s["shard_bfs"],
                              "pattern.sharded": self.s["shard_pattern"]},
                    f"sharded runtime lanes: {lanes}")
            one = {f"{k}.{p}": n for (k, p), n in
                   rt_one.stats.lane_counts().items() if n}
            require(set(one) == {"bfs.device", "pattern.device"},
                    f"single-chip runtime lanes: {one}")
            require(st["errors"] == 0 and st["breaker_trips"] == 0,
                    f"sharded runtime errors: {st}")
            out.update({
                "requests": len(reqs), "all_equal_host": True,
                "equal_single_chip": True, "wall_s": round(sh_wall, 2),
                "mesh": [int(d.id) for d in rt_sh.executor.mesh.devices.flat],
                "shards": held,
                "sharded_dispatches": st["sharded_dispatches"],
                "lanes": lanes,
                "host_fallbacks": st["host_fallbacks"],
                "peak_bytes_per_device": [
                    (d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in jax.devices()],
            })
        finally:
            rt_sh.close()
            rt_one.close()
        g.close()
        out["seconds"] = round(time.perf_counter() - t_phase, 1)
        return out


# --------------------------------------------- the serve graph + references


class ServeGraph:
    """A store-loaded graph the shape of ``models.dbpedia_like``: entities
    with integer values, zipf-skewed binary links carrying integer values
    — plus everything the host references need, kept as plain arrays as
    the data is generated (never read back from the system under test)."""

    def __init__(self, s: dict, seed: int):
        from hypergraphdb_tpu import HyperGraph

        self.s = s
        r = np.random.default_rng(seed + 11)
        t0 = time.perf_counter()
        self.g = g = HyperGraph()
        ne, nl = s["serve_entities"], s["serve_links"]
        ents = g.bulk_import(values=np.arange(ne).tolist())
        self.e0 = int(ents[0])
        require(len(ents) == ne and int(ents[-1]) == self.e0 + ne - 1,
                "entity handles are not one contiguous range")
        link_h, link_a, link_b = [], [], []
        for s0 in range(0, nl, 100_000):
            m = min(100_000, nl - s0)
            subj = self.e0 + (r.zipf(1.1, size=m) % ne)
            obj = self.e0 + r.integers(0, ne, size=m)
            hs = g.bulk_import(
                values=[LINK_VALUE0 + s0 + i for i in range(m)],
                target_lists=np.stack([subj, obj], axis=1).tolist(),
            )
            link_h.append(np.arange(int(hs[0]), int(hs[0]) + m))
            link_a.append(subj)
            link_b.append(obj)
        self.load_s = time.perf_counter() - t0
        self.link_h = np.concatenate(link_h).astype(np.int64)
        self.link_a = np.concatenate(link_a).astype(np.int64)
        self.link_b = np.concatenate(link_b).astype(np.int64)
        self.link_val = LINK_VALUE0 + np.arange(nl, dtype=np.int64)
        self.n_old_links = nl
        self.link_type = int(g.get_type_handle_of(int(self.link_h[0])))
        deg = np.bincount(np.concatenate([self.link_a, self.link_b]),
                          minlength=self.e0 + ne)
        self.deg = deg
        # the widest row among each entity's neighbours: a join that
        # expands through a hub is cut by the lane's pad cap and re-served
        # on the host BY CONTRACT — the smoke draws anchors that never do
        self.nbr_deg = np.zeros_like(deg)
        np.maximum.at(self.nbr_deg, self.link_a, deg[self.link_b])
        np.maximum.at(self.nbr_deg, self.link_b, deg[self.link_a])
        ent_ids = np.arange(self.e0, self.e0 + ne)
        self.isolated = ent_ids[deg[ent_ids] == 0]
        # the new component (stage 2 ingests it): new entities in a ring
        # with chords (triangles), each also tied to an old ISOLATED
        # entity — so nothing any old seed reaches ever changes
        m = min(s["new_entities"], len(self.isolated))
        require(m >= 8, "too few isolated entities to attach the ingest to")
        self.n_new_entities = m
        self.n_new_atoms = 0
        self.new_e0 = None
        self.ingest_error = None

    # -- ingest ---------------------------------------------------------------
    def ingest(self) -> None:
        """The writer thread: 4 batches of new entities, then their links."""
        try:
            g, m = self.g, self.n_new_entities
            ne = self.s["serve_entities"]
            new_h = []
            for part in np.array_split(np.arange(m), 4):
                hs = g.bulk_import(values=(ne + part).tolist())
                new_h.append(np.arange(int(hs[0]), int(hs[0]) + len(part)))
            new_h = np.concatenate(new_h).astype(np.int64)
            i = np.arange(m)
            a = np.concatenate([new_h, new_h, new_h])
            b = np.concatenate([new_h[(i + 1) % m], new_h[(i + 2) % m],
                                self.isolated[:m]])
            vals = NEW_LINK_VALUE0 + np.arange(3 * m, dtype=np.int64)
            link_h = []
            for part in np.array_split(np.arange(3 * m), 4):
                hs = g.bulk_import(
                    values=vals[part].tolist(),
                    target_lists=np.stack([a[part], b[part]], axis=1)
                    .tolist(),
                )
                link_h.append(np.arange(int(hs[0]), int(hs[0]) + len(part)))
            self.new_entities = new_h
            self.link_h = np.concatenate([self.link_h] + link_h)
            self.link_a = np.concatenate([self.link_a, a])
            self.link_b = np.concatenate([self.link_b, b])
            self.link_val = np.concatenate([self.link_val, vals])
            self.n_new_atoms = 4 * m
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            self.ingest_error = e

    def describe(self) -> dict:
        return {"entities": self.s["serve_entities"],
                "links": self.s["serve_links"],
                "atoms": self.s["serve_entities"] + self.s["serve_links"],
                "load_s": round(self.load_s, 1),
                "atoms_per_s": round(
                    (self.s["serve_entities"] + self.s["serve_links"])
                    / self.load_s),
                "max_degree": int(self.deg.max()),
                "isolated_entities": int(len(self.isolated))}

    # -- requests -------------------------------------------------------------
    def requests(self, n: int, seed: int, where: str) -> list:
        """``n`` requests over every lane; ``where`` picks the population
        their anchors come from: 'old' (the loaded graph) or 'new' (the
        ingested component — answers that depend on the new atoms)."""
        mix = {"bfs": 0.45, "pattern": 0.22, "range": 0.11, "join": 0.11,
               "planned": 0.11}
        counts = {k: max(int(n * f), 1) for k, f in mix.items()}
        counts["bfs"] += n - sum(counts.values())
        return self.requests_of(counts, seed, where)

    def requests_of(self, counts: dict, seed: int,
                    where: str = "old") -> list:
        r = np.random.default_rng(seed)
        old = where == "old"
        n_links = self.n_old_links
        if old:
            # calm anchors: both ends of a link whose neighbourhoods hold
            # no wide row, so no lane is pushed to the host by a cap
            lk = r.permutation(n_links)[: 64 * sum(counts.values())]
            calm = lk[(self.nbr_deg[self.link_a[lk]] <= 16)
                      & (self.nbr_deg[self.link_b[lk]] <= 16)]
            require(len(calm) >= sum(counts.values()),
                    "too few calm links to draw anchors from")
            pick = iter(calm.tolist())
            val0 = LINK_VALUE0
            n_vals = n_links
        else:
            m = self.n_new_entities
            pick = iter((n_links + r.permutation(3 * m)).tolist())
            val0 = NEW_LINK_VALUE0
            n_vals = 3 * m
        out = []
        for i in range(counts.get("bfs", 0)):
            li = next(pick)
            seed_atom = int(self.link_b[li] if old else self.link_a[li])
            out.append({"kind": "bfs", "hops": 2 + (i % 2),
                        "atoms": [seed_atom]})
        for i in range(counts.get("pattern", 0)):
            li = next(pick)
            a, b = int(self.link_a[li]), int(self.link_b[li])
            typed = i % 3 == 0
            if i % 8 == 7:            # a pair no link joins
                b = int(self.link_b[next(pick)])
            out.append({"kind": "pattern", "atoms": [a, b],
                        "type": self.link_type if typed else None})
        for i in range(counts.get("range", 0)):
            lo = val0 + int(r.integers(0, max(n_vals - 100, 1)))
            width = int(r.integers(0, 60))
            out.append({"kind": "range", "lo": lo, "hi": lo + width,
                        "desc": i % 4 == 3, "atoms": [0]})
        for i in range(counts.get("join", 0)):
            li = next(pick)
            a = int(self.link_b[li] if old else self.link_a[li])
            out.append({"kind": "join", "atoms": [a]})
        for i in range(counts.get("planned", 0)):
            li = next(pick)
            a, b = int(self.link_a[li]), int(self.link_b[li])
            v = int(self.link_val[li])
            out.append({"kind": "planned", "atoms": [a, b],
                        "window": None if i % 2 else (v - 20, v + 20)})
        order = r.permutation(len(out))
        return [out[i] for i in order]

    @staticmethod
    def _path2(a: int) -> dict:
        """The join spec: two-step paths a — y — z."""
        from hypergraphdb_tpu.query import conditions as c
        from hypergraphdb_tpu.query.variables import var

        return {"y": c.CoIncident(a), "z": c.CoIncident(var("y"))}

    @staticmethod
    def _condition(q: dict):
        """A planned request's condition: links on both anchors, or links
        on the second anchor whose value lies in a window."""
        from hypergraphdb_tpu.query import conditions as c

        a, b = q["atoms"]
        if q["window"] is None:
            return c.And(c.Incident(a), c.Incident(b))
        lo, hi = q["window"]
        return c.And(c.AtomValue(lo, "gte"), c.AtomValue(hi, "lte"),
                     c.Incident(b))

    def submit(self, rt, q: dict):
        k = q["kind"]
        if k == "bfs":
            # explain: the answer carries the bucket it rode (needs the
            # runtime's tracer on; the sharded phase runs without one)
            return rt.submit_bfs(q["atoms"][0], max_hops=q["hops"],
                                 explain=rt.tracer.enabled)
        if k == "pattern":
            return rt.submit_pattern(q["atoms"], type_handle=q["type"])
        if k == "range":
            return rt.submit_range(q["lo"], q["hi"], desc=q["desc"])
        if k == "join":
            return rt.submit_join(self._path2(q["atoms"][0]))
        return rt.submit_planned(self._condition(q))

    # -- references -----------------------------------------------------------
    def prime_bfs_refs(self, reqs: list) -> None:
        """Bit-parallel: the references of up to 64 BFS requests per pass,
        memoized per (seed, hops, links so far)."""
        memo = self.__dict__.setdefault("_bfs_memo", {})
        L = len(self.link_h)
        todo: dict = {}
        for q in reqs:
            if q["kind"] == "bfs" and (q["atoms"][0], q["hops"], L) \
                    not in memo:
                todo.setdefault(q["hops"], set()).add(q["atoms"][0])
        if not todo:
            return
        n_ids = int(max(self.link_h.max(), self.link_a.max(),
                        self.link_b.max())) + 1
        flat = np.stack([self.link_a, self.link_b], axis=1).reshape(-1)
        link_of = np.repeat(np.arange(L, dtype=np.int64), 2)
        for hops, seeds in todo.items():
            seeds = sorted(seeds)
            for s0 in range(0, len(seeds), 64):
                part = np.asarray(seeds[s0: s0 + 64])
                vis = host_bfs_bits(n_ids, flat, link_of, L, part, hops)
                for k, sd in enumerate(part.tolist()):
                    memo[(sd, hops, L)] = bits_column(vis, k)

    def _on(self, atom: int) -> np.ndarray:
        return (self.link_a == atom) | (self.link_b == atom)

    def reference(self, q: dict):
        """The host answer of one request, from the generator's arrays:
        a sorted id array (value order for a range), or — for the join —
        sorted (y, z) tuples."""
        k = q["kind"]
        if k == "bfs":
            self.prime_bfs_refs([q])
            return self._bfs_memo[(q["atoms"][0], q["hops"],
                                   len(self.link_h))]
        if k == "pattern":
            hit = self._on(q["atoms"][0]) & self._on(q["atoms"][1])
            if q["type"] is not None and q["type"] != self.link_type:
                hit[:] = False
            return np.sort(self.link_h[hit])
        if k == "range":
            sel = np.flatnonzero((self.link_val >= q["lo"])
                                 & (self.link_val <= q["hi"]))
            sel = sel[np.argsort(self.link_val[sel], kind="stable")]
            return self.link_h[sel[::-1] if q["desc"] else sel]
        if k == "planned":
            hit = self._on(q["atoms"][1])
            if q["window"] is None:
                hit &= self._on(q["atoms"][0])
            else:
                lo, hi = q["window"]
                hit &= (self.link_val >= lo) & (self.link_val <= hi)
            return np.sort(self.link_h[hit])
        # join: a - y - z over co-incidence, y != a, z != y, z != a when
        # distinct (the lane's default); both ends of a link are neighbours
        a = q["atoms"][0]

        def nbrs(v):
            on = self._on(v)
            both = np.concatenate([self.link_a[on], self.link_b[on]])
            return np.unique(both[both != v])

        return sorted((int(y), int(z)) for y in nbrs(a)
                      for z in nbrs(y) if z != a)

    def check(self, q: dict, res, top_r: int, cross: bool = True) -> bool:
        """Hold one answer to its host reference — exact count, the exact
        prefix, an honest truncation flag — and, where the repo's own
        host engine answers the same question in milliseconds, hold the
        reference to ``find_all`` / ``host_join`` too. Returns whether
        that second comparison was made."""
        k = q["kind"]
        want = self.reference(q)
        if k == "join":
            got = [tuple(int(v) for v in row) for row in res.tuples]
            require(res.count == len(want), f"join count {res.count} != "
                    f"host {len(want)} for {q['atoms']}")
            require(got == want[: len(got)] and
                    len(got) == min(len(want), top_r),
                    f"join tuples differ from host for {q['atoms']}: "
                    f"{got[:4]} vs {want[:4]}")
            require(res.truncated == (res.count > len(got)),
                    "join truncation flag is not honest")
        else:
            got = np.asarray(res.matches, dtype=np.int64)
            require(res.count == len(want),
                    f"{k} count {res.count} != host {len(want)} for {q}")
            # a planned answer is re-served whole; a lane answers a window
            full = len(want) if k == "planned" else min(len(want), top_r)
            require(len(got) == full
                    and np.array_equal(got, want[: len(got)]),
                    f"{k} matches differ from host for {q}: "
                    f"{got[:6].tolist()} vs {want[:6].tolist()}")
            require(res.truncated == (res.count > len(got)),
                    f"{k} truncation flag is not honest for {q}")
        return cross and self._cross_check(q, want)

    def _cross_check(self, q: dict, want) -> bool:
        """reference == the repo's host engine, for the questions it
        answers fast (a value window is a seconds-long host scan at this
        scale, and a hub's neighbourhood a long traversal: skipped)."""
        from hypergraphdb_tpu import join
        from hypergraphdb_tpu.query import dsl

        k = q["kind"]
        if k == "bfs" and len(want) <= 2000:
            host = set(int(h) for h in self.g.find_all(
                dsl.bfs(q["atoms"][0], max_distance=q["hops"])))
            host.add(q["atoms"][0])     # find_all leaves the seed out
            require(sorted(host) == want.tolist(),
                    f"reference BFS != find_all for {q}")
        elif k == "pattern" and q["type"] in (None, self.link_type):
            host = sorted(int(h) for h in self.g.find_all(dsl.and_(
                dsl.incident(q["atoms"][0]), dsl.incident(q["atoms"][1]))))
            require(host == want.tolist(),
                    f"reference pattern != find_all for {q}")
        elif k == "planned" and q["window"] is None:
            host = sorted(int(h) for h in self.g.find_all(
                self._condition(q)))
            require(host == want.tolist(),
                    f"reference condition != find_all for {q}")
        elif k == "join":
            host = join.host_join(self.g, join.extract_pattern(
                self.g, self._path2(q["atoms"][0])))
            require([tuple(t) for t in host] == want,
                    f"reference join != host_join for {q}")
        else:
            return False
        return True


class _WarningTap(logging.Handler):
    """Collects WARNING+ records of the package's loggers for a phase: a
    swallowed failure that was at least logged shows on the phase line."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages: list = []

    def emit(self, record) -> None:
        self.messages.append(f"{record.name}: {record.getMessage()}"[:300])

    def install(self):
        logging.getLogger("hypergraphdb_tpu").addHandler(self)
        return self

    def remove(self) -> None:
        logging.getLogger("hypergraphdb_tpu").removeHandler(self)


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="every graph, request and probe is generated "
                         "from it")
    ap.add_argument("--scale", choices=sorted(SCALES), default="full",
                    help="'tiny' is the CPU rehearsal: refused unless the "
                         "caller set JAX_PLATFORMS=cpu")
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the mesh-sharded serving phase and what "
                         "it is compared with (needs four devices)")
    args = ap.parse_args(argv)
    rehearsal = args.scale == "tiny"
    if rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chip_smoke: --scale tiny is the CPU rehearsal; set "
              "JAX_PLATFORMS=cpu to ask for it", file=sys.stderr)
        return 2
    # the program itself, imported before anything is printed: a directory
    # that holds this script and nothing else fails here, with no result
    import hypergraphdb_tpu  # noqa: F401

    smoke = Smoke(args.scale, args.seed, rehearsal)
    phases = [("device", smoke.phase_device)]
    phases += ([("sharded", smoke.phase_sharded)] if args.four_chips else
               [("kernels", smoke.phase_kernels),
                ("serve", smoke.phase_serve)])
    t_all = time.perf_counter()
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            line = run()
        except NoAccelerator as e:
            # no accelerator: nothing on stdout, the reason on stderr
            print(f"chip_smoke: device phase failed: JAX found platform "
                  f"{e}, not a TPU", file=sys.stderr)
            return 1
        except PhaseFailed as e:
            emit({"phase": name, "ok": False, "error": str(e),
                  "seconds": round(time.perf_counter() - t0, 1)})
            return 1
        emit({"phase": name, "ok": True, **line})
    emit({"phase": "summary", "ok": True,
          "seconds": round(time.perf_counter() - t_all, 1),
          "compile_cache": smoke.compile_events})
    print(json.dumps({"ok": True, "device": smoke.device_json}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
