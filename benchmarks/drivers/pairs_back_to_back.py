"""Whole pair-distance batches, one after another: the loop of
``typed_back_to_back`` with ``ops.pair_distances(snap, sources, targets,
max_hops, link_types=<the run's family>)`` — how far apart, a LENGTH per
pair.

A "traversal" is a batch answered: all its lengths on the host (the
operator hands back a host array; its two bitmaps are gone when it
returns). ``traverse_time_s`` = window ÷ batches. Sources and targets are
fresh per batch, independent, uniform over the entities; the family is the
sibling's, drawn once a run from ``--seed``. The reference is
``harness/refs_pairs.py`` — one ball grown forward from the sources, over
the generator's entry arrays and the generator's own link types, run to
``reference_depth`` so that the line can say how many sampled pairs the cap
cut; the control is the reference that rounds an odd length up to the next
even one. The byte model is ``harness/bytes_pairs.py``.
"""

from __future__ import annotations

import numpy as np

from drivers import typed_back_to_back
from harness import bytes_pairs, refs_pairs


def _counter(name: str) -> int:
    """A counter of the program's default registry; 0 where it has none."""
    from hypergraphdb_tpu.obs import default_registry

    counter = default_registry().get(name)
    return 0 if counter is None else int(counter.value)


class Driver(typed_back_to_back.Driver):
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        super().__init__(sut, cfg, traffic, seed, setup)
        # the loop and the sampler of ``back_to_back`` read the depth of a
        # traversal under ``hops``: a batch's is its cap
        self.traffic = dict(traffic, hops=traffic["max_hops"])
        self.expansions: list = []
        self._lengths: dict = {}

    def _seeds(self) -> np.ndarray:
        """(2, pairs): a batch's sources and, drawn after them, its
        targets."""
        return np.stack([super(Driver, self)._seeds() for _ in range(2)])

    def _traverse(self, ends: np.ndarray):
        import jax

        from hypergraphdb_tpu.ops import pair_distances

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.traverse"):
            res = pair_distances(self.sut.snap, ends[0], ends[1],
                                 t["max_hops"], chunk=t["chunk"],
                                 k_block=t["k_block"],
                                 link_types=self.family.tolist())
        self.expansions.append(int(res.expansions))
        # the lengths ARE the batch's answer; nothing stays on the device
        return None, np.asarray(res.dist)

    def run(self, seconds: float) -> dict:
        early, self.expansions = _counter("bfs.pairs.early_exits"), []
        window = super().run(seconds)
        n_rows, t = self.sut.shapes["n_rows"], self.traffic
        window["bytes_per_traversal"] = bytes_pairs.pair_bytes(
            n_rows, self.shapes["e_tgt"], t["seeds"], t["max_hops"])
        window["meet_bytes"] = bytes_pairs.meet_bytes(
            n_rows, t["seeds"], t["max_hops"])
        depths, counts = np.unique(self.runs[-1]["counts"],
                                   return_counts=True)
        window["counters"] = {
            "early_exits_in_window":
                _counter("bfs.pairs.early_exits") - early,
            "expansions_a_batch": [min(self.expansions),
                                   max(self.expansions)],
            "depth_histogram_last_batch":
                {str(int(d)): int(n) for d, n in zip(depths, counts)},
        }
        return window

    def collect(self) -> dict:
        """The sampled pairs' lengths as the timed batches returned them;
        then the program's state goes."""
        picks = self.sample()
        got = {"picks": picks,
               "dist": [int(self.runs[b]["counts"][c]) for b, c in picks]}
        self.last = None
        self.sut.snap = None
        return got

    def lengths(self, picks: list) -> np.ndarray:
        """The sampled pairs' lengths by the numpy search, uncapped up to
        ``reference_depth`` (kept: the control asks for the same picks)."""
        key = tuple(picks)
        if key not in self._lengths:
            sut = self.sut
            ends = np.asarray([self.runs[b]["seeds"][:, c]
                               for b, c in picks])
            self._lengths[key] = refs_pairs.host_pair_dist(
                sut.n_atoms, sut.flat, sut.link_of, self.type_of,
                self.family, ends[:, 0], ends[:, 1],
                max(self.traffic["reference_depth"],
                    self.traffic["max_hops"]))
        return self._lengths[key]

    def check(self, got: dict) -> dict:
        """Every number compared, beside its limit: exact. ``cut_by_cap``
        (no limit: it says what the cap hides, not whether the run is
        right) counts the sampled pairs whose length the reference finds
        past ``max_hops``."""
        raw = self.lengths(got["picks"])
        want = refs_pairs.capped(raw, self.traffic["max_hops"])
        return {"dist_differ": (int(np.count_nonzero(
                    np.asarray(got["dist"]) != want)), 0),
                "pairs_compared": (len(got["picks"]), None),
                "cut_by_cap": (int(np.count_nonzero(
                    raw > self.traffic["max_hops"])), None)}

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROL's answers: the reference in the
        program's place with the guarantee "the exact length" broken — an
        odd length rounded up to the next even one, what a program that
        tested only after both sides had expanded would answer. It has to
        come out as not correct."""
        wrong = refs_pairs.capped(
            refs_pairs.tested_on_even_depths_only(
                self.lengths(got["picks"])), self.traffic["max_hops"])
        return self.check({"picks": got["picks"], "dist": wrong.tolist()})
