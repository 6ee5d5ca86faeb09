"""One run of the PageRank cell and, in the same process, each of its three
controls, every number of each.

    python benchmarks/tests/pagerank_controls.py --workload pagerank10m.iter10 --seed <n> --seconds <s> --trace <0|1>

``control.py`` runs a cell's one control and prints only what it compares
beside a limit; the PageRank driver has three (``Driver.controls``: the
reference with ``w_e`` dropped, with the dangling mass dropped, with its sums
rounded through bfloat16), and what says how far each falls outside the
tolerance — ``max_rel_err``, ``max_mass_err`` — has no limit. This prints
the run's whole result line with each control's comparison under ``also``,
then one line: whether the run is correct and which controls are. The exit
code is 0 only if the run is correct and EVERY control is not.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main(argv=None) -> int:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(argv, also=lambda driver, got: driver.controls(got))
    if rc:
        return rc
    line = buf.getvalue().strip().splitlines()[-1]
    out = json.loads(line)
    print(line, flush=True)
    correct = {name: all(v <= lim for v, lim in compared.values()
                         if lim is not None)
               for name, compared in out["also"].items()}
    print(json.dumps({"workload": out["workload"], "seed": out["seed"],
                      "correct": out["correct"],
                      "controls_correct": correct}), flush=True)
    return 0 if out["correct"] and not any(correct.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
