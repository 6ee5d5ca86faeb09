"""Whole-graph connected-components runs, one after another: the family of
``typed_back_to_back`` with ``ops.connected_components(snap,
link_types=<the run's family>)`` — which atoms hang together, a label per
atom.

A "traversal" is a run answered: its labels ready on the device and its
component count on the host; the previous run's labels are dropped before
the next starts. ``traverse_time_s`` = window ÷ runs. No seeds: every run
asks the same whole-graph question of one resident graph, as Graphalytics
repeats a run; the family is the typed siblings', drawn once a run from
``--seed``. The reference is ``harness/refs_wcc.py`` — synchronous min-label
propagation in numpy over the generator's entry arrays and its own link
types; the control is the reference cut one lowering round short. The byte
model is ``harness/bytes_wcc.py``, over the reference's rounds.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import typed_back_to_back
from harness import bytes_wcc, refs_wcc
# the operator, as the driver is loaded: a program without it fails here,
# right after the build, with no run started
from hypergraphdb_tpu.ops import connected_components


def _counter(name: str) -> int:
    """A counter of the program's default registry; 0 where it has none."""
    from hypergraphdb_tpu.obs import default_registry

    counter = default_registry().get(name)
    return 0 if counter is None else int(counter.value)


class Driver(typed_back_to_back.Driver):
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        super().__init__(sut, cfg, traffic, seed, setup)
        self._ref = None

    def _components(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.traverse"):
            res = connected_components(self.sut.snap, self.family.tolist(),
                                       chunk=self.traffic["chunk"])
            jax.block_until_ready(res.labels)
        return res

    def warm(self) -> None:
        """One run at the window's own shapes; its labels are dropped."""
        self._components()

    def run(self, seconds: float) -> dict:
        runs = []
        lowered0 = _counter("wcc.rows_lowered")
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            # free the labels before the next run: no other name holds them
            self.last = None
            before = _counter("wcc.rows_lowered")
            self.last = self._components()
            runs.append({"n_components": self.last.n_components,
                         "rounds": self.last.rounds,
                         "rows_lowered": _counter("wcc.rows_lowered")
                         - before,
                         "t_done": time.perf_counter() - t0})
        window_s = time.perf_counter() - t0
        self.runs = runs
        last = runs[-1]
        self.window = {
            "window_s": window_s, "attempted": len(runs), "failed": 0,
            "end_to_end": {"traverse_time_s": window_s / len(runs)},
            "traversals": len(runs),
            "counters": {
                "rounds_last_run": last["rounds"],
                "n_components_last_run": last["n_components"],
                "rows_lowered_last_run": last["rows_lowered"],
                "rows_lowered_in_window":
                    _counter("wcc.rows_lowered") - lowered0,
                "rounds_a_run": [min(r["rounds"] for r in runs),
                                 max(r["rounds"] for r in runs)],
            },
        }
        return self.window

    def collect(self) -> dict:
        """Every label of the last run, read from the device (40 MB), and
        every run's count and rounds; then the program's state goes."""
        labels = np.asarray(self.last.labels)
        got = {"labels": labels[: self.sut.n_atoms],
               "n_components": [r["n_components"] for r in self.runs],
               "rounds": [r["rounds"] for r in self.runs]}
        self.last = None
        self.sut.snap = None
        return got

    def reference(self) -> tuple:
        """``refs_wcc.min_label_rounds`` over the run's family, once (the
        check and the control both ask for it)."""
        if self._ref is None:
            self._ref = self._propagate(None)
        return self._ref

    def _propagate(self, max_rounds: int | None) -> tuple:
        sut = self.sut
        return refs_wcc.min_label_rounds(sut.n_atoms, sut.flat, sut.link_of,
                                         self.type_of, self.family,
                                         max_rounds)

    def check(self, got: dict) -> dict:
        """Every number compared, beside its limit: exact. The labels over
        every row of the last run; the component count of every run of the
        window. Without a limit: the rounds (an implementation's own number,
        equal to the reference's for synchronous rounds), the last run's
        lowered rows and the entities' components, for the line."""
        labels, rounds_ref, lowered = self.reference()
        n_ref = refs_wcc.components(labels)
        e0, e1 = self.sut.entities
        own = labels[e0:e1] == np.arange(e0, e1)
        # the byte model's rounds are the reference's (read after check)
        self.window["wcc_bytes_per_run"] = bytes_wcc.wcc_bytes(
            self.sut.n_atoms, self.shapes["e_tgt"], rounds_ref)
        self.window["counters"].update(
            rounds_ref=rounds_ref, lowered_by_round_ref=lowered,
            entity_components=int(np.count_nonzero(own)))
        return {
            "labels_differ": (int(np.count_nonzero(
                np.asarray(got["labels"]) != labels)), 0),
            "n_components_differ": (sum(
                n != n_ref for n in got["n_components"]), 0),
            "rows_compared": (len(labels), None),
            "runs_compared": (len(got["n_components"]), None),
            "rounds_differ": (sum(r != rounds_ref for r in got["rounds"]),
                              None),
        }

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROL's answers: the reference in the
        program's place with the guarantee "exactly the least id of the
        component" broken — propagation stopped one lowering round short,
        what a program that stopped at a threshold instead of at zero would
        answer. It has to come out as not correct."""
        rounds_ref = self.reference()[1]
        labels, rounds, _ = self._propagate(max(rounds_ref - 2, 0))
        return self.check({"labels": labels,
                           "n_components": [refs_wcc.components(labels)]
                           * len(got["n_components"]),
                           "rounds": [rounds] * len(got["rounds"])})
