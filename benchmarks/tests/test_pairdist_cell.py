"""The pair-distance cell's yardstick at rehearsal size (``JAX_PLATFORMS=cpu
python -m pytest benchmarks/tests``; ``test_yardstick.py``,
``test_typed_cell.py`` and ``test_seqpath_cell.py`` keep the benchmark's
other cells).

- the run is correct and its control — the reference that rounds an odd
  length up to the next even one — is not;
- with the timed path broken underneath, a whole run reports ``correct``
  false: a ``pair_distances`` that ignores ``link_types``, and a search that
  skips the meet test between a forward and a backward expansion;
- the reference finds a length from ONE ball and agrees with the program's
  host oracle; the bytes follow the admitted entries and the cap;
- a traffic file no cell names (the cap of 8) runs through
  ``other_traffic.py``; the self-check still passes with this cell's files
  beside the others.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from tests import control, other_traffic  # noqa: E402
from tests.test_yardstick import argv_of, result_of  # noqa: E402

CELL = "pairdist10m.sp4"

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
    assert line["compared"]["dist_differ"] == {"value": 0, "limit": 0}
    assert line["control_compared"]["dist_differ"]["value"] > 0
    assert line["checked"]["pairs_compared"] == 64


def test_the_line_says_what_a_batch_ran_and_what_the_cap_cut():
    out = result_of(argv_of(CELL, 4))
    assert out["correct"] and out["compiles_in_window"] == 0
    counters = out["counters"]
    lo, hi = counters["expansions_a_batch"]
    assert 1 <= lo <= hi <= 4
    # a batch that ended before the cap is an early exit, and no other
    assert (counters["early_exits_in_window"] == 0) == (lo == 4)
    hist = counters["depth_histogram_last_batch"]
    assert sum(hist.values()) == 64 and set(hist) <= {"-1", "0", "1", "2",
                                                      "3", "4"}
    assert 0 <= out["checked"]["cut_by_cap"] <= 64
    assert out["setup"]["admitted_entries"] > 0


def test_ignored_link_types_fail_the_cell(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real = ops.pair_distances
    families = []

    def forgetful(*a, link_types=None, **kw):
        families.append(link_types)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "pair_distances", forgetful)
    out = result_of(argv_of(CELL, 5))
    assert families and all(f == out["setup"]["family"] for f in families)
    assert not out["correct"]
    assert out["compared"]["dist_differ"]["value"] > 0


def test_a_skipped_test_after_the_forward_expansion_fails_the_cell(
        monkeypatch):
    """The meet test after every forward expansion answers "no column
    met": an odd length is then first seen one expansion late."""
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs

    real_block, real_meet, tests = ellbfs._pair_block, ellbfs._meet, [0]

    def block(*a, **kw):
        tests[0] = 0
        return real_block(*a, **kw)

    def meet(fwd, bwd):
        tests[0] += 1
        words = real_meet(fwd, bwd)
        return jnp.zeros_like(words) if tests[0] % 2 else words

    monkeypatch.setattr(ellbfs, "_pair_block", block)
    monkeypatch.setattr(ellbfs, "_meet", meet)
    out = result_of(argv_of(CELL, 6))
    assert not out["correct"]
    assert out["compared"]["dist_differ"]["value"] > 0


def _built(seed: int):
    spec = run.load_cell(CELL, rehearse=True)
    cfg, traffic = spec["config"], spec["traffic"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, seed, {})
    setup: dict = {}
    driver = run.load_module("drivers", traffic["driver"]).Driver(
        sut, cfg, traffic, seed, setup)
    return sut, cfg, traffic, driver, setup


def test_reference_is_the_programs_host_oracle_and_the_control_is_not():
    """``refs_pairs.host_pair_dist`` (numpy, the generator's arrays) against
    ``algorithms/traversals.shortest_path_length`` over the program's
    snapshot, pair by pair; the control's rounding moves exactly the odd
    lengths."""
    from harness import refs_pairs
    from hypergraphdb_tpu.algorithms.traversals import (
        DefaultALGenerator,
        shortest_path_length,
    )

    sut, cfg, traffic, driver, _ = _built(11)
    snap, family = sut.snap, set(driver.family.tolist())

    class Graph:
        def get_incidence_set(self, atom):
            return snap.incidence_row(int(atom)).tolist()

        def get_targets(self, link):
            return snap.targets_row(int(link)).tolist()

    gen = DefaultALGenerator(
        Graph(), link_predicate=lambda g, link:
        int(snap.type_of[int(link)]) in family)
    ends = driver._seeds()[:, :64]
    ends[1, :2] = ends[0, :2]  # s == t
    raw = refs_pairs.host_pair_dist(sut.n_atoms, sut.flat, sut.link_of,
                                    driver.type_of, driver.family, ends[0],
                                    ends[1], 8)
    want = [shortest_path_length(Graph(), int(s), int(t), gen, 8)
            for s, t in ends.T]
    assert raw.tolist() == want
    assert {0, -1} < set(want) and any(d % 2 == 1 for d in want)
    capped = refs_pairs.capped(raw, 4)
    assert all(c == (d if d <= 4 else -1) for c, d in zip(capped, raw))
    wrong = refs_pairs.tested_on_even_depths_only(raw)
    assert all(w == (d + 1 if d > 0 and d % 2 else d)
               for w, d in zip(wrong, raw))


def test_bytes_follow_the_admitted_entries_and_the_cap():
    from harness import bytes_model, bytes_pairs

    sut, cfg, traffic, driver, setup = _built(9)
    assert len(setup["family"]) == cfg["family_types"]
    entries = setup["admitted_entries"]
    assert 0 < entries < sut.shapes["e_tgt"]
    window = driver.run(0.05)  # at least one whole batch
    n_rows, pairs, cap = sut.shapes["n_rows"], traffic["seeds"], 4
    assert traffic["max_hops"] == cap
    assert window["meet_bytes"] == cap * 2 * (n_rows * pairs // 8) \
        == bytes_pairs.meet_bytes(n_rows, pairs, cap)
    assert window["bytes_per_traversal"] == window["meet_bytes"] + \
        bytes_model.traverse_bytes(n_rows, entries, entries, pairs, hops=cap)
    assert window["traversals"] == len(driver.expansions) >= 1
    assert window["end_to_end"]["traverse_time_s"] > 0


def test_the_cap_of_eight_runs_under_a_traffic_file_no_cell_names():
    """``traffic/pairdist8-4096.json`` through ``other_traffic.py``: the
    batch ends when its last pair is met or exhausted, so its expansions
    follow the data and nothing is cut by the cap."""
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert other_traffic.main(["--traffic", "pairdist8-4096",
                                   *argv_of(CELL, 3)]) == 0
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert out["correct"] and out["checked"]["cut_by_cap"] == 0
    lo, hi = out["counters"]["expansions_a_batch"]
    assert 4 <= lo <= hi <= 8
    assert np.all(np.asarray(list(map(
        int, out["counters"]["depth_histogram_last_batch"]))) <= 8)
