"""Whole traversals under a link predicate, one after another: the loop of
``back_to_back`` with ``ops.bfs_pull(..., link_types=<the run's family>)``.

The family is drawn once a run from ``--seed`` — ``family_types`` of the
type atoms the generator gave its links — and is the same for every
traversal. The reference filters the generator's entry arrays by the
generator's own link types (``harness/refs_typed.py``); the control is the
reference with the predicate dropped. The byte model's entries are the
ADMITTED ones, counted from the generator's arrays: the least an
implementation with an index by type must read.
"""

from __future__ import annotations

import numpy as np

from builders import columnar_snapshot
from drivers import back_to_back
from harness import bytes_model, refs, refs_typed


class Driver(back_to_back.Driver):
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        super().__init__(sut, cfg, traffic, seed, setup)
        # the links' types as generated, never as the program holds them
        self.type_of = columnar_snapshot.tables(cfg, seed)["type_of"]
        link_types = np.unique(self.type_of[sut.entities[1]:])
        self.family = np.sort(np.random.default_rng([seed, 5]).choice(
            link_types, cfg["family_types"], replace=False))
        admitted = int(np.count_nonzero(refs_typed.admitted_entries(
            self.type_of, sut.link_of, self.family)))
        # every admitted target entry is one incidence entry
        self.shapes = dict(sut.shapes, e_inc=admitted, e_tgt=admitted)
        setup["family"] = self.family.tolist()
        setup["admitted_entries"] = admitted

    def _traverse(self, seeds: np.ndarray):
        import jax

        from hypergraphdb_tpu.ops import bfs_pull

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.traverse"):
            res = bfs_pull(self.sut.snap, seeds, t["hops"], chunk=t["chunk"],
                           k_block=t["k_block"],
                           link_types=self.family.tolist())
            jax.block_until_ready(res.visited_t)
        with jax.profiler.TraceAnnotation("bench.counts_to_host"):
            counts = np.asarray(res.reach_counts)
        return res, counts

    def run(self, seconds: float) -> dict:
        window = super().run(seconds)
        window["bytes_per_traversal"] = bytes_model.traverse_bytes(
            seeds=self.traffic["seeds"], hops=self.traffic["hops"],
            **self.shapes)
        return window

    def reference(self, picks: list, n_last: int,
                  typed: bool = True) -> dict:
        """As ``back_to_back``'s, over the links the family admits;
        ``typed=False`` drops the predicate (the control)."""
        if not typed:
            return super().reference(picks, n_last)
        sut = self.sut
        seeds = np.asarray([self.runs[t]["seeds"][c] for t, c in picks])
        vis = refs_typed.host_bfs_bits(
            sut.n_atoms, sut.flat, sut.link_of, self.type_of, self.family,
            seeds, self.traffic["hops"])
        keep = np.uint64((1 << n_last) - 1)
        return {"picks": picks, "n_last": n_last, "bitmap": vis & keep,
                "counts": [len(c) for c in
                           refs.bits_columns(vis, len(picks))]}

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROL's answers: the reference in the
        program's place with the guarantee "under the link predicate"
        broken — every link followed, what a kernel that ignored link types
        would answer. It has to come out as not correct."""
        return self.check(self.reference(got["picks"], got["n_last"],
                                         typed=False))
