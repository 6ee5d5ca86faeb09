"""One run of a cell and, in the same process, its control.

    python benchmarks/tests/control.py --workload <cell> --seed <n> --seconds <s>

The run is an ordinary one (the arguments are ``run.py``'s); once its
answers are compared, the control's answers to the same questions are
compared the same way (``Driver.control``): the plain reference in the
program's place with one guarantee of the configuration broken. The
result's ``also`` holds the control's numbers; the exit code is 0 only if
the run is correct and the control is not.
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
        rc = run.main(argv, also=lambda driver, got: driver.control(got))
    if rc:
        return rc
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    control = {k: {"value": v, "limit": lim}
               for k, (v, lim) in out["also"].items() if lim is not None}
    control_correct = all(c["value"] <= c["limit"] for c in control.values())
    print(json.dumps({"workload": out["workload"], "seed": out["seed"],
                      "device": out["device"], "metrics": out["metrics"],
                      "breakdown": out.get("breakdown"),
                      "check_s": out["check_s"], "correct": out["correct"],
                      "compared": out["compared"], "checked": out["checked"],
                      "control_correct": control_correct,
                      "control_compared": control}), flush=True)
    return 0 if out["correct"] and not control_correct else 1


if __name__ == "__main__":
    sys.exit(main())
