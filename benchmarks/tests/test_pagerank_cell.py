"""The PageRank cell's yardstick at rehearsal size
(``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests``; the other cells'
files keep theirs).

- the run is correct and each of its three controls — ``w_e`` dropped, the
  dangling mass dropped, the sums rounded through bfloat16 — is not,
  through ``control.py`` unedited and through ``pagerank_controls.py``;
- with the timed path broken underneath, a whole run reports ``correct``
  false: a ``pagerank`` that ignores ``w_e``, and one that stops an
  iteration early;
- the reference (numpy over the generator's arrays) gives the ranks of the
  program's host oracle ``algorithms/traversals.pagerank``; the bytes follow
  the shapes and the traffic's iterations;
- a traced run reads the cell's per-layer metrics it can read on the CPU.
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
from tests import control, pagerank_controls  # noqa: E402
from tests.test_yardstick import argv_of, result_of  # noqa: E402

CELL = "pagerank10m.iter10"

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="the rehearsal is asked for with JAX_PLATFORMS=cpu")


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_run_is_correct_and_every_control_is_not(seed, capsys):
    assert control.main(argv_of(CELL, seed)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] and not line["control_correct"]
    assert line["compared"]["ranks_differ"] == {"value": 0, "limit": 0}
    assert line["compared"]["mass_differ"] == {"value": 0, "limit": 0}
    assert line["control_compared"]["controls_caught"] == {"value": 3,
                                                           "limit": 2}
    assert line["checked"]["rows_compared"] == 3909
    assert line["checked"]["max_rel_err"] < 1e-5


def test_each_control_fails_by_its_own_numbers():
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = pagerank_controls.main(argv_of(CELL, 4))
    whole, verdict = (json.loads(x) for x in
                      buf.getvalue().strip().splitlines()[-2:])
    assert rc == 0 and verdict["correct"]
    assert verdict["controls_correct"] == {"a_unweighted": False,
                                           "b_no_dangling": False,
                                           "c_bf16": False}
    tol = 1e-4
    got = {k: {m: v for m, (v, _) in c.items()}
           for k, c in whole["also"].items()}
    # no dangling mass: every row low; bfloat16: beyond 1e-4, not beyond 10%
    assert got["b_no_dangling"]["ranks_differ"] == 3909
    assert tol < got["c_bf16"]["max_rel_err"] < 0.1
    assert got["c_bf16"]["ranks_differ"] > 1000
    assert got["a_unweighted"]["max_mass_err"] > 1.0


def test_a_pagerank_that_ignores_the_link_weights_fails(monkeypatch):
    """``w_e`` taken as 1 for every link that steps, ``c_v`` as the slots:
    the unnormalised walk of control (a), from the program."""
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs

    real = ellbfs._pr_weights

    def unweighted(snap, plans):
        pw = real(snap, plans)
        slots = jnp.where(pw.inv_d > 0, 1.0 / jnp.where(pw.inv_d > 0,
                                                         pw.inv_d, 1.0), 0.0)
        return ellbfs._PRWeights(pw.inv_d, (pw.w > 0).astype(jnp.float32),
                                 slots.astype(jnp.float32))

    monkeypatch.setattr(ellbfs, "_pr_weights", unweighted)
    out = result_of(argv_of(CELL, 5))
    assert not out["correct"]
    assert out["compared"]["ranks_differ"]["value"] > 1000
    assert out["compared"]["mass_differ"]["value"] == out["attempted"]


def test_a_pagerank_that_stops_an_iteration_early_fails(monkeypatch):
    import hypergraphdb_tpu.ops as ops

    real, asked = ops.pagerank, []

    def short(snap, link_types=None, **kw):
        asked.append(kw["iterations"])
        return real(snap, link_types, **{**kw,
                                         "iterations": kw["iterations"] - 1})

    monkeypatch.setattr(ops, "pagerank", short)
    out = result_of(argv_of(CELL, 6))
    assert asked and set(asked) == {10}
    assert not out["correct"]
    assert out["compared"]["ranks_differ"]["value"] > 0
    assert out["counters"]["iterations_last_run"] == 9


def _built(seed: int):
    spec = run.load_cell(CELL, rehearse=True)
    cfg, traffic = spec["config"], spec["traffic"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, seed, {})
    setup: dict = {}
    driver = run.load_module("drivers", traffic["driver"]).Driver(
        sut, cfg, traffic, seed, setup)
    return sut, cfg, traffic, driver, setup


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_is_the_programs_host_oracle(seed):
    """``refs_pagerank.pagerank`` (numpy, the generator's arrays) against
    ``algorithms/traversals.pagerank`` over the program's snapshot, atom by
    atom, after every count of iterations; the graph has hubs, duplicate
    targets and dangling atoms."""
    from harness import refs_pagerank
    from hypergraphdb_tpu.algorithms import traversals

    sut, cfg, traffic, driver, _ = _built(seed)
    walk = refs_pagerank.walk(sut.n_atoms, sut.flat, sut.link_of)
    assert (walk["d"] == 0).sum() > sut.n_atoms - sut.entities[1]
    for k in (0, 1, 10):
        want = traversals.pagerank(sut.snap, iterations=k)
        got = refs_pagerank.pagerank(sut.n_atoms, sut.flat, sut.link_of,
                                     damping=0.85, iterations=k,
                                     weights=walk)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        assert abs(got.sum() - 1.0) < 1e-12


def test_bytes_follow_the_shapes_and_the_traffics_iterations():
    from harness import bytes_model, bytes_pagerank

    sut, cfg, traffic, driver, setup = _built(9)
    window = driver.run(0.05)  # at least one whole run
    got = driver.collect()
    compared = driver.check(got)
    assert compared["ranks_differ"] == (0, 0)
    n, e = sut.n_atoms, sut.shapes["e_tgt"]
    assert window["pr_bytes_per_run"] == bytes_pagerank.pr_bytes(
        n, e, 10) == 10 * (bytes_model.relation_bytes(n, e, e) + 8 * n)
    assert window["traversals"] >= 1
    assert window["end_to_end"]["traverse_time_s"] > 0


def test_a_traced_run_reads_what_the_cpu_can():
    """The counters' readers and the set-up's on the line; the device-trace
    readers are None without a TPU plane and leave the line. The counters
    are the process's: this test starts them afresh, as a run's process
    does."""
    from hypergraphdb_tpu.obs import default_registry

    for name in ("pr.runs", "pr.iterations", "pr.rows_folded"):
        default_registry().counter(name).reset()
    out = result_of(["--workload", CELL, "--seed", "8", "--seconds", "1",
                     "--trace", "1", "--rehearse"])
    read = {k: v["value"] for k, v in out["rehearsal_values"].items()}
    assert out["correct"]
    assert read["pr_iterations_per_run"] == 10
    assert {"plan_build_s", "snapshot_build_s", "plan_upload_s",
            "traverse_plan_upper_share", "warm_s"} <= set(read)
    assert not {"traverse_dev_s.pr_stages", "traverse_dev_s.pr_update",
                "pr_roofline"} & set(read)
