"""Whole-graph PageRank runs, one after another: the loop of
``back_to_back`` with ``ops.pagerank(snap, damping, iterations)`` — how
important each atom is, a float32 rank per atom.

A "traversal" is a run answered: its ranks ready on the device and their
sum (``mass``) on the host; the previous run's ranks are dropped before the
next starts. ``traverse_time_s`` = window ÷ runs. No seeds and no link
predicate: every run asks the same whole-graph question of one resident
graph, as Graphalytics repeats a run. The reference is
``harness/refs_pagerank.py`` — numpy float64 over the generator's entry
arrays; the three controls are that reference with one guarantee broken
each. The byte model is ``harness/bytes_pagerank.py``, over the traffic's
iterations.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import back_to_back
from harness import bytes_pagerank, refs_pagerank
# the operator, as the driver is loaded: a program without it fails here,
# right after the build, with no run started
from hypergraphdb_tpu.ops import pagerank

#: the controls, each the reference with one guarantee broken
CONTROLS = {"a_unweighted": {"weighted": False},
            "b_no_dangling": {"dangling": False},
            "c_bf16": {"bf16": True}}


def _counter(name: str) -> int:
    """A counter of the program's default registry; 0 where it has none."""
    from hypergraphdb_tpu.obs import default_registry

    counter = default_registry().get(name)
    return 0 if counter is None else int(counter.value)


class Driver(back_to_back.Driver):
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        super().__init__(sut, cfg, traffic, seed, setup)
        self.tolerance = cfg["tolerance"]
        self._walk = None
        self._ref = None

    def _pagerank(self):
        import jax

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.traverse"):
            res = pagerank(self.sut.snap, damping=t["damping"],
                           iterations=t["iterations"], chunk=t["chunk"])
            jax.block_until_ready(res.ranks)
        return res

    def warm(self) -> None:
        """One run at the window's own shapes; its ranks are dropped."""
        self._pagerank()

    def run(self, seconds: float) -> dict:
        runs = []
        folded0 = _counter("pr.rows_folded")
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            # free the ranks before the next run: no other name holds them
            self.last = None
            self.last = self._pagerank()
            runs.append({"mass": self.last.mass,
                         "iterations": self.last.iterations,
                         "t_done": time.perf_counter() - t0})
        window_s = time.perf_counter() - t0
        self.runs = runs
        masses = [r["mass"] for r in runs]
        self.window = {
            "window_s": window_s, "attempted": len(runs), "failed": 0,
            "end_to_end": {"traverse_time_s": window_s / len(runs)},
            "traversals": len(runs),
            "pr_bytes_per_run": bytes_pagerank.pr_bytes(
                self.sut.n_atoms, self.sut.shapes["e_tgt"],
                self.traffic["iterations"]),
            "counters": {
                "iterations_last_run": runs[-1]["iterations"],
                "mass_a_run": [min(masses), max(masses)],
                "rows_folded_in_window":
                    _counter("pr.rows_folded") - folded0,
            },
        }
        return self.window

    def collect(self) -> dict:
        """Every rank of the last run, read from the device (40 MB), and
        every run's mass; then the program's state goes."""
        ranks = np.asarray(self.last.ranks)
        got = {"ranks": ranks[: self.sut.n_atoms].astype(np.float64),
               "mass": [r["mass"] for r in self.runs]}
        self.last = None
        self.sut.snap = None
        return got

    def _pagerank_ref(self, **broken) -> np.ndarray:
        sut, t = self.sut, self.traffic
        if self._walk is None:
            self._walk = refs_pagerank.walk(sut.n_atoms, sut.flat,
                                            sut.link_of)
        return refs_pagerank.pagerank(
            sut.n_atoms, sut.flat, sut.link_of, damping=t["damping"],
            iterations=t["iterations"], weights=self._walk, **broken)

    def reference(self) -> np.ndarray:
        """``refs_pagerank.pagerank`` over the whole graph, once (the check
        and the controls both ask for it)."""
        if self._ref is None:
            self._ref = self._pagerank_ref()
        return self._ref

    def check(self, got: dict) -> dict:
        """Every number compared, beside its limit. The ranks of the last
        run, every row, each within the relative tolerance of the
        reference's; every run's mass within its tolerance of 1. Without a
        limit: the largest errors of both, and how much was compared."""
        want = self.reference()
        rel = np.abs(np.asarray(got["ranks"]) - want) / want
        tol = self.tolerance
        return {
            "ranks_differ": (int(np.count_nonzero(~(rel <= tol["rank_rel"]))),
                             0),
            "mass_differ": (sum(not abs(m - 1.0) <= tol["mass_abs"]
                                for m in got["mass"]), 0),
            "max_rel_err": (float(np.max(rel)), None),
            "max_mass_err": (max(abs(m - 1.0) for m in got["mass"]), None),
            "rows_compared": (len(want), None),
            "runs_compared": (len(got["mass"]), None),
        }

    def controls(self, got: dict) -> dict:
        """Each control's comparison, by name: the reference with one
        guarantee broken in the program's place, for every run."""
        out = {}
        for name, broken in CONTROLS.items():
            ranks = self._pagerank_ref(**broken)
            out[name] = self.check({"ranks": ranks,
                                    "mass": [float(ranks.sum())]
                                    * len(got["mass"])})
        return out

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROLS' answers, for ``control.py``:
        ``controls_caught`` — how many of the three came out not correct —
        against a limit of two, so the controls as one are "correct" unless
        EVERY one of them is caught; and each control's numbers, without a
        limit."""
        caught, out = 0, {}
        for name, compared in self.controls(got).items():
            caught += not all(v <= lim for v, lim in compared.values()
                              if lim is not None)
            out.update({f"{name}.{k}": (v, None)
                        for k, (v, _) in compared.items()})
        return {"controls_caught": (caught, len(CONTROLS) - 1), **out}
