"""Tests of the yardstick itself, at a size a test run can hold (the CPU
rehearsal). Run them with ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests`` (they are not part of the repo's tier-1 tests).

- the self-check (trace reducer, byte functions);
- each cell's control comes out as not correct, while the run is correct;
- with the timed path broken underneath — an answer altered where it is
  produced — a whole run (the harness's look for a chip skipped: the
  rehearsal) reports ``correct`` false.
"""

import io
import json
import os
import sys
from concurrent.futures import Future
from contextlib import redirect_stdout

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from tests import control  # noqa: E402

CELLS = ["embedded10m.traverse3", "served3m.mixed256"]

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="the rehearsal is asked for with JAX_PLATFORMS=cpu")


def result_of(argv) -> dict:
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run.main(argv) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def argv_of(cell: str, seed: int) -> list:
    return ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse"]


def test_selfcheck():
    assert run.main(["--selfcheck"]) == 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_run_is_correct_and_control_is_not(cell, seed, capsys):
    assert control.main(argv_of(cell, seed)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and not line["control_correct"]


def test_altered_count_fails_the_traversal_cell(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real = ops.bfs_pull

    def altered(*a, **kw):
        res = real(*a, **kw)
        return res._replace(reach_counts=res.reach_counts + 1)

    monkeypatch.setattr(ops, "bfs_pull", altered)
    out = result_of(argv_of(CELLS[0], 5))
    assert not out["correct"]
    assert out["compared"]["counts_differ"]["value"] > 0
    assert out["compared"]["bitmap_rows_differ"]["value"] == 0


def test_altered_bitmap_fails_the_traversal_cell(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real = ops.bfs_pull

    def altered(*a, **kw):
        res = real(*a, **kw)
        # one atom dropped from every seed's visited set
        return res._replace(visited_t=res.visited_t.at[70].set(0))

    monkeypatch.setattr(ops, "bfs_pull", altered)
    out = result_of(argv_of(CELLS[0], 6))
    assert not out["correct"]
    assert out["compared"]["bitmap_rows_differ"]["value"] > 0


@pytest.mark.parametrize("fault", ["dropped_row", "never_answered"])
def test_altered_answer_fails_the_served_cell(monkeypatch, fault):
    import dataclasses

    from hypergraphdb_tpu.serve import ServeRuntime

    real = ServeRuntime.submit
    seen = [0]
    in_window = [False]
    real_load_module = run.load_module

    def load_module(kind, name):
        """The fault starts with the window (set-up waits for its answers)."""
        mod = real_load_module(kind, name)
        if kind == "drivers":
            real_run = mod.Driver.run

            def run_window(self, seconds):
                in_window[0] = True
                return real_run(self, seconds)

            mod.Driver.run = run_window
        return mod

    monkeypatch.setattr(run, "load_module", load_module)

    def altered(self, request, *a, **kw):
        inner = real(self, request, *a, **kw)
        seen[0] += in_window[0]
        if seen[0] % 7 or not in_window[0]:
            return inner
        outer: Future = Future()
        if fault == "dropped_row":
            def done(f):
                res = f.result()
                rows = "tuples" if hasattr(res, "tuples") else "matches"
                outer.set_result(dataclasses.replace(
                    res, **{rows: getattr(res, rows)[:-1]}))
            inner.add_done_callback(done)
        return outer

    monkeypatch.setattr(ServeRuntime, "submit", altered)
    if fault == "never_answered":
        # (the window waits for stragglers: keep the wait short here)
        real_load = run.load_cell

        def load(workload, rehearse):
            spec = real_load(workload, rehearse)
            spec["traffic"]["straggler_timeout_s"] = 2
            return spec

        monkeypatch.setattr(run, "load_cell", load)
    out = result_of(argv_of(CELLS[1], 7))
    assert not out["correct"]
    name = "answers_wrong" if fault == "dropped_row" else "answers_missing"
    assert out["compared"][name]["value"] > 0
    if fault == "never_answered":
        assert out["failed"] > 0
