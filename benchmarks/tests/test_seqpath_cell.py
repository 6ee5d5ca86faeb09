"""The path-match cell's yardstick at rehearsal size (``JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests``; ``test_yardstick.py`` and
``test_typed_cell.py`` keep the benchmark's other cells).

- the run is correct and its control — the reference with the steps in
  reverse order — is not;
- with the timed path broken underneath, a whole run reports ``correct``
  false: a ``path_match`` that uses step 1's family in every hop, and one
  whose step ORs the new frontier into the old (keeps a visited set);
- the bytes follow the entries each step's family admits;
- the self-check still passes with this cell's files beside the others.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from tests import control  # noqa: E402
from tests.test_yardstick import argv_of, result_of  # noqa: E402

CELL = "seqpath10m.match3"

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="the rehearsal is asked for with JAX_PLATFORMS=cpu")


def test_selfcheck():
    assert run.main(["--selfcheck"]) == 0


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_run_is_correct_and_control_is_not(seed, capsys):
    assert control.main(argv_of(CELL, seed)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and not line["control_correct"]
    assert line["control_compared"]["counts_differ"]["value"] > 0
    assert line["control_compared"]["bitmap_rows_differ"]["value"] > 0


def _assert_not_correct(out: dict) -> None:
    assert not out["correct"]
    assert out["compared"]["counts_differ"]["value"] > 0
    assert out["compared"]["bitmap_rows_differ"]["value"] > 0


def test_one_family_for_every_hop_fails_the_cell(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real = ops.path_match
    asked = []

    def one_plan(snap, seeds, steps, **kw):
        asked.append(steps)
        return real(snap, seeds, [steps[0]] * len(steps), **kw)

    monkeypatch.setattr(ops, "path_match", one_plan)
    out = result_of(argv_of(CELL, 5))
    assert asked and all(s == out["setup"]["families"] for s in asked)
    _assert_not_correct(out)


def test_a_step_that_keeps_a_visited_set_fails_the_cell(monkeypatch):
    from hypergraphdb_tpu.ops import ellbfs

    ored = ellbfs._visited_update
    monkeypatch.setattr(
        ellbfs, "_frontier_replace",
        lambda frontier, reach, out_map, n: ored(frontier, reach, out_map, n))
    _assert_not_correct(result_of(argv_of(CELL, 6)))


def test_bytes_follow_each_steps_admitted_entries():
    """Three disjoint families of the traffic file's sizes; the roofline's
    bytes are one hop a step over that step's admitted entries as
    generated, and the program's restricted snapshots agree with the
    generator's counts."""
    from harness import bytes_model

    spec = run.load_cell(CELL, rehearse=True)
    cfg, traffic = spec["config"], spec["traffic"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, 9, {})
    setup: dict = {}
    driver = run.load_module("drivers", traffic["driver"]).Driver(
        sut, cfg, traffic, 9, setup)
    families, entries = setup["families"], setup["admitted_entries_by_step"]
    assert [len(f) for f in families] == traffic["step_family_types"]
    assert len({t for f in families for t in f}) == \
        sum(traffic["step_family_types"])
    assert all(0 < e < sut.shapes["e_tgt"] for e in entries)
    from hypergraphdb_tpu.ops.ellbfs import restricted_for

    assert [restricted_for(sut.snap, f).n_edges_tgt for f in families] \
        == entries
    window = driver.run(0.05)  # at least one whole match
    assert window["bytes_per_traversal"] == sum(
        bytes_model.traverse_bytes(sut.shapes["n_rows"], e, e,
                                   traffic["seeds"], hops=1)
        for e in entries)
    assert window["frontier_write_bytes"] == \
        3 * sut.shapes["n_rows"] * traffic["seeds"] // 8
    assert window["counters"] == {"restrict_evictions_in_window": 0}
