"""The host's seconds from inside, read through the benchmark: a whole
traced run of each admitted cell at rehearsal size (``JAX_PLATFORMS=cpu
python3 -m pytest benchmarks/tests/test_host_accounting.py -q``).

- every reader PR 36 added (``traverse_host_s.*``, ``traverse_stall_s``,
  ``warm_*_s``) returns a number on its cell's line;
- ``warm_trace_s + warm_lower_s + warm_compile_s + warm_cache_load_s +
  warm_other_s`` is ``warm_s``;
- an operation's self time and its direct children's seconds are its wall;
- the window is the last ``attempted`` operations of the ring and the
  warm-up the one before them; on an emptied ring every reader gives None.

``tests/test_obs_phase.py`` (tier-1) holds the records themselves.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from harness import phase_log  # noqa: E402
from tests.test_yardstick import result_of  # noqa: E402

CELLS = {"embedded10m.traverse3": "hg.bfs.pull",
         "typedpath10m.traverse3": "hg.bfs.pull",
         "seqpath10m.match3": "hg.bfs.match",
         "pairdist10m.sp4": "hg.bfs.pairs"}
HOST = ("traverse_host_s.unwaited", "traverse_host_s.sparse_expand",
        "traverse_host_s.self", "traverse_stall_s")
WARM = ("warm_trace_s", "warm_lower_s", "warm_compile_s",
        "warm_cache_load_s", "warm_other_s")

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="the rehearsal is asked for with JAX_PLATFORMS=cpu")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_traced_run_reports_the_nine_and_they_add_up(cell):
    from hypergraphdb_tpu.obs import phase_log as ring

    ring().reset()
    out = result_of(["--workload", cell, "--seed", "7", "--seconds", "1",
                     "--trace", "1", "--rehearse"])
    assert out["correct"] and out["compiles_in_window"] == 0
    read = {k: v["value"] for k, v in out["rehearsal_values"].items()}
    assert set(HOST + WARM) <= set(read)
    assert all(read[k] >= 0.0 for k in HOST + WARM)
    assert read["traverse_stall_s"] == 0.0
    assert sum(read[k] for k in WARM) == pytest.approx(read["warm_s"],
                                                       abs=1e-9)
    # the ring as the run left it: the window is its last operations
    ctx = {"window": {"attempted": out["attempted"]},
           "setup": {"warm_s": read["warm_s"]}}
    window, warm = phase_log.window_of(ctx), phase_log.warm_of(ctx)
    assert len(window.ops) == out["attempted"] and len(warm.ops) == 1
    assert {r["name"] for r in window.ops + warm.ops} == {CELLS[cell]}
    assert warm.ops[0]["t1"] <= window.ops[0]["t0"]
    walls = sum(map(phase_log.wall, window.ops))
    assert walls <= out["window_s"]
    assert read["traverse_host_s.self"] * len(window.ops) + sum(
        map(phase_log.wall, window.children())) == pytest.approx(
            walls, abs=1e-9)
    assert read["traverse_host_s.sparse_expand"] \
        <= read["traverse_host_s.unwaited"] <= walls / len(window.ops)
    # every phase below an operation carries its id
    assert {r["op"] for r in window.below} == {r["id"] for r in window.ops}
    ring().reset()
    assert phase_log.window_of(ctx) is None and phase_log.warm_of(ctx) is None
    for name in HOST + WARM:
        assert run.load_module("layer_metrics", name).read(ctx) is None
