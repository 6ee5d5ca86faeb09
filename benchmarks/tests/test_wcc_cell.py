"""The connected-components cell's yardstick at rehearsal size
(``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests``; the other cells'
files keep theirs).

- the run is correct and its control — the reference cut one lowering round
  short — is not, through ``control.py`` unedited;
- with the timed path broken underneath, a whole run reports ``correct``
  false: rounds that stop after three, and a ``connected_components`` that
  ignores ``link_types``;
- the reference (numpy over the generator's arrays) gives the labels of the
  program's host oracle ``algorithms/traversals.connected_components``; the
  bytes follow the admitted entries and the reference's rounds;
- a traced run reads the cell's per-layer metrics it can read on the CPU.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from tests import control  # noqa: E402
from tests.test_yardstick import argv_of, result_of  # noqa: E402

CELL = "wcc10m.family16"

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="the rehearsal is asked for with JAX_PLATFORMS=cpu")


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_run_is_correct_and_control_is_not(seed, capsys):
    assert control.main(argv_of(CELL, seed)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and not line["control_correct"]
    assert line["compared"]["labels_differ"] == {"value": 0, "limit": 0}
    assert line["compared"]["n_components_differ"] == {"value": 0,
                                                       "limit": 0}
    assert line["control_compared"]["labels_differ"]["value"] > 0
    assert line["checked"]["rows_compared"] == 3909
    assert line["checked"]["rounds_differ"] == 0


def test_the_line_says_what_the_rounds_did():
    out = result_of(argv_of(CELL, 4))
    assert out["correct"] and out["compiles_in_window"] == 0
    c = out["counters"]
    assert c["rounds_a_run"] == [c["rounds_ref"]] * 2 == \
        [c["rounds_last_run"]] * 2
    assert c["lowered_by_round_ref"][-1] == 0
    assert len(c["lowered_by_round_ref"]) == c["rounds_ref"]
    assert c["rows_lowered_last_run"] == sum(c["lowered_by_round_ref"])
    assert c["rows_lowered_in_window"] == \
        out["attempted"] * c["rows_lowered_last_run"]
    assert 0 < c["entity_components"] < c["n_components_last_run"]


def test_rounds_that_stop_after_three_fail_the_cell(monkeypatch):
    """A round loop that reads "nothing lowered" at its third round: what a
    program with a fixed round budget would answer."""
    from hypergraphdb_tpu.ops import ellbfs

    real_init, real_round, ran = ellbfs._wcc_init, ellbfs._wcc_round, [0]

    def init(*a, **kw):
        ran[0] = 0
        return real_init(*a, **kw)

    def round_(*a, **kw):
        ran[0] += 1
        labels, lowered = real_round(*a, **kw)
        return labels, (0 if ran[0] == 3 else lowered)

    monkeypatch.setattr(ellbfs, "_wcc_init", init)
    monkeypatch.setattr(ellbfs, "_wcc_round", round_)
    out = result_of(argv_of(CELL, 5))
    assert out["counters"]["rounds_a_run"] == [3, 3]
    assert not out["correct"]
    assert out["compared"]["labels_differ"]["value"] > 0
    assert out["checked"]["rounds_differ"] == out["attempted"]


def test_ignored_link_types_fail_the_cell(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real = ops.connected_components
    families = []

    def forgetful(snap, link_types=None, **kw):
        families.append(link_types)
        return real(snap, **kw)

    monkeypatch.setattr(ops, "connected_components", forgetful)
    out = result_of(argv_of(CELL, 6))
    assert families and all(f == out["setup"]["family"] for f in families)
    assert not out["correct"]
    assert out["compared"]["labels_differ"]["value"] > 0
    assert out["compared"]["n_components_differ"]["value"] > 0


def _built(seed: int):
    spec = run.load_cell(CELL, rehearse=True)
    cfg, traffic = spec["config"], spec["traffic"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, seed, {})
    setup: dict = {}
    driver = run.load_module("drivers", traffic["driver"]).Driver(
        sut, cfg, traffic, seed, setup)
    return sut, cfg, traffic, driver, setup


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_is_the_programs_host_oracle_and_the_control_is_not(
        seed):
    """``refs_wcc.min_label_rounds`` (numpy, the generator's arrays) against
    ``algorithms/traversals.connected_components`` over the program's
    snapshot, atom by atom; cut one lowering round short it differs by the
    last lowering round's rows."""
    from harness import refs_wcc
    from hypergraphdb_tpu.algorithms.traversals import (
        DefaultALGenerator,
        connected_components,
    )

    sut, cfg, traffic, driver, _ = _built(seed)
    snap, family = sut.snap, set(driver.family.tolist())

    class Graph:
        def atoms(self):
            return range(snap.num_atoms)

        def get_incidence_set(self, atom):
            return snap.incidence_row(int(atom)).tolist()

        def get_targets(self, link):
            return snap.targets_row(int(link)).tolist()

    gen = DefaultALGenerator(
        Graph(), link_predicate=lambda g, link:
        int(snap.type_of[int(link)]) in family)
    labels, rounds, lowered = refs_wcc.min_label_rounds(
        sut.n_atoms, sut.flat, sut.link_of, driver.type_of, driver.family)
    want = connected_components(Graph(), gen)
    assert labels.tolist() == [want[a] for a in range(sut.n_atoms)]
    assert rounds == len(lowered) >= 3 and lowered[-1] == 0
    short, r_short, _ = refs_wcc.min_label_rounds(
        sut.n_atoms, sut.flat, sut.link_of, driver.type_of, driver.family,
        max_rounds=rounds - 2)
    assert r_short == rounds - 2
    assert int(np.count_nonzero(short != labels)) == lowered[-2]
    # no admitted entry: no round, every atom its own label
    none, r_none, _ = refs_wcc.min_label_rounds(
        sut.n_atoms, sut.flat, sut.link_of, driver.type_of,
        np.asarray([], np.int32))
    assert r_none == 0 and (none == np.arange(sut.n_atoms)).all()


def test_bytes_follow_the_admitted_entries_and_the_reference_rounds():
    from harness import bytes_model, bytes_wcc

    sut, cfg, traffic, driver, setup = _built(9)
    entries = setup["admitted_entries"]
    assert 0 < entries < sut.shapes["e_tgt"]
    window = driver.run(0.05)  # at least one whole run
    got = driver.collect()
    compared = driver.check(got)
    assert compared["labels_differ"] == (0, 0)
    rounds = window["counters"]["rounds_ref"]
    n = sut.n_atoms
    assert window["wcc_bytes_per_run"] == bytes_wcc.wcc_bytes(
        n, entries, rounds) == rounds * (
            bytes_model.relation_bytes(n, entries, entries) + 8 * n)
    assert window["traversals"] >= 1
    assert window["end_to_end"]["traverse_time_s"] > 0


def test_a_traced_run_reads_what_the_cpu_can():
    """The counters' readers and the set-up's on the line; the device-trace
    readers are None without a TPU plane and leave the line. The counters
    are the process's: this test starts them afresh, as a run's process
    does."""
    from hypergraphdb_tpu.obs import default_registry

    for name in ("wcc.runs", "wcc.rounds", "wcc.rows_lowered",
                 "wcc.rows_folded"):
        default_registry().counter(name).reset()
    out = result_of(["--workload", CELL, "--seed", "8", "--seconds", "1",
                     "--trace", "1", "--rehearse"])
    read = {k: v["value"] for k, v in out["rehearsal_values"].items()}
    assert out["correct"]
    assert read["wcc_rounds_per_run"] == out["counters"]["rounds_ref"]
    assert 0 < read["wcc_lowered_share"] < 100
    assert {"plan_build_s", "snapshot_build_s", "typed_restrict_s",
            "warm_s"} <= set(read)
    assert not {"traverse_dev_s.wcc_stages", "traverse_dev_s.wcc_fold",
                "wcc_roofline"} & set(read)
