"""The typed-path cell's yardstick at rehearsal size (``JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests``; ``test_yardstick.py`` keeps the list of
the benchmark's other cells).

- the run is correct and its control — the reference with the link
  predicate dropped — is not;
- with the predicate dropped underneath the timed path (a ``bfs_pull`` that
  forgets ``link_types``), a whole run reports ``correct`` false;
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

CELL = "typedpath10m.traverse3"

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


def test_forgotten_predicate_fails_the_cell(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real = ops.bfs_pull
    families = []

    def forgetful(*a, link_types=None, **kw):
        families.append(link_types)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "bfs_pull", forgetful)
    out = result_of(argv_of(CELL, 5))
    assert families and all(f == out["setup"]["family"] for f in families)
    assert not out["correct"]
    assert out["compared"]["counts_differ"]["value"] > 0
    assert out["compared"]["bitmap_rows_differ"]["value"] > 0


def test_bytes_follow_the_admitted_entries():
    """The roofline's bytes come from the admitted entries as generated,
    fewer than the graph's, and the family is the configuration's size."""
    spec = run.load_cell(CELL, rehearse=True)
    cfg, traffic = spec["config"], spec["traffic"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, 9, {})
    setup: dict = {}
    driver = run.load_module("drivers", traffic["driver"]).Driver(
        sut, cfg, traffic, 9, setup)
    assert len(setup["family"]) == cfg["family_types"]
    assert 0 < setup["admitted_entries"] < sut.shapes["e_tgt"]
    assert driver.shapes["e_inc"] == driver.shapes["e_tgt"] \
        == setup["admitted_entries"]
    # the program's restricted snapshot agrees with the generator's count
    from hypergraphdb_tpu.ops.ellbfs import restricted_for

    sub = restricted_for(sut.snap, setup["family"])
    assert sub.n_edges_tgt == setup["admitted_entries"]
