"""Whole path matches, one after another: the loop of ``back_to_back`` with
``ops.path_match(snap, seeds, [F1, F2, F3])`` — a link predicate PER STEP.

The families are drawn once a run from ``--seed`` — ``step_family_types[h]``
of the type atoms the generator gave its links for step h, the families
pairwise disjoint — and are the same for every match. The reference is
``harness/refs_path.py`` over the generator's entry arrays and the
generator's own link types; the control is the reference with the steps in
reverse order. The byte model's entries are the ADMITTED ones of each step,
counted from the generator's arrays (``harness/bytes_path.py``).
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from builders import columnar_snapshot
from drivers import back_to_back
from harness import bytes_path, refs, refs_path


def _evictions() -> int:
    """The program's counter of restricted families let go; 0 under a
    program that has none."""
    from hypergraphdb_tpu.obs import default_registry

    counter = default_registry().get("bfs.restrict.evictions")
    return 0 if counter is None else int(counter.value)


class Driver(back_to_back.Driver):
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        super().__init__(sut, cfg, traffic, seed, setup)
        sizes = traffic["step_family_types"]
        if len(sizes) != traffic["hops"]:
            raise ValueError("step_family_types names a family a hop")
        # the links' types as generated, never as the program holds them
        self.type_of = columnar_snapshot.tables(cfg, seed)["type_of"]
        link_types = np.unique(self.type_of[sut.entities[1]:])
        drawn = np.random.default_rng([seed, 5]).permutation(link_types)
        if sum(sizes) > len(drawn):
            raise ValueError("the steps' families cannot be disjoint")
        cuts = np.cumsum(sizes)
        self.families = [np.sort(f) for f in np.split(drawn[:cuts[-1]],
                                                      cuts[:-1])]
        # admitted entries a step: the entries of each type, once
        by_type = np.bincount(self.type_of[sut.link_of])
        self.step_entries = [int(by_type[f].sum()) for f in self.families]
        setup["families"] = [f.tolist() for f in self.families]
        setup["admitted_entries_by_step"] = self.step_entries

    def _traverse(self, seeds: np.ndarray):
        import jax

        from hypergraphdb_tpu.ops import path_match

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.traverse"):
            res = path_match(self.sut.snap, seeds,
                             [f.tolist() for f in self.families],
                             chunk=t["chunk"], k_block=t["k_block"])
            jax.block_until_ready(res.frontier_t)
        with jax.profiler.TraceAnnotation("bench.counts_to_host"):
            counts = np.asarray(res.match_counts)
        # the base class reads the last bitmap under a traversal's name
        return SimpleNamespace(visited_t=res.frontier_t), counts

    def run(self, seconds: float) -> dict:
        evicted = _evictions()
        window = super().run(seconds)
        n_rows, seeds = self.sut.shapes["n_rows"], self.traffic["seeds"]
        window["bytes_per_traversal"] = bytes_path.match_bytes(
            n_rows, self.step_entries, seeds)
        window["frontier_write_bytes"] = bytes_path.frontier_write_bytes(
            n_rows, seeds, len(self.families))
        window["counters"] = {
            "restrict_evictions_in_window": _evictions() - evicted}
        return window

    def reference(self, picks: list, n_last: int,
                  reverse: bool = False) -> dict:
        """The sampled seeds' end-point counts and, for the first
        ``n_last`` (the last match's), their end-point columns, by the
        numpy match; ``reverse=True`` takes the steps in reverse order
        (the control)."""
        sut = self.sut
        seeds = np.asarray([self.runs[t]["seeds"][c] for t, c in picks])
        steps = self.families[::-1] if reverse else self.families
        ends = refs_path.host_match_bits(
            sut.n_atoms, sut.flat, sut.link_of, self.type_of, steps, seeds)
        keep = np.uint64((1 << n_last) - 1)
        return {"picks": picks, "n_last": n_last, "bitmap": ends & keep,
                "counts": [len(c) for c in
                           refs.bits_columns(ends, len(picks))]}

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROL's answers: the reference in the
        program's place with the guarantee "under the predicate of every
        step" broken — the steps' families in reverse order, what a program
        that took the wrong plan for a hop would answer. It has to come out
        as not correct."""
        return self.check(self.reference(got["picks"], got["n_last"],
                                         reverse=True))
