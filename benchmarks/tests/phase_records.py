"""One ordinary run of a cell and, under ``also`` on its result line, what
the program's own records say of the window's operations.

    python benchmarks/tests/phase_records.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The arguments are ``run.py``'s and the run is ``run.main``'s, so a plain run
here is a plain run (the tool for hunting a stall: the line names it). The
ring itself goes to ``chiprun_out/phase_log/<cell>.<seed>.t<trace>.jsonl``
(``FlightRecorder.to_jsonl``). ``also`` holds:

- ``op_wall_s``: every window operation's wall, in order;
- ``per_op_s``: seconds an operation by ``<phase>`` and ``<phase>.<step>``
  (the mean over the window), beside ``self`` — they add up to the mean
  operation;
- ``stalls``: the records the program flagged, with the operation's index
  in the window;
- ``slow``: every instance over three times its name's median in the
  window, flagged or not (a stall the program's rule is too coarse for).

Null under a program that keeps no such records.
"""

import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from harness import phase_log  # noqa: E402

SLOW = 3.0


def summary(attempted: int) -> dict | None:
    window = phase_log.window_of({"window": {"attempted": attempted}})
    if window is None:
        return None
    n, index = len(window.ops), {op["id"]: i
                                 for i, op in enumerate(window.ops)}
    per_op = {"self": (sum(map(phase_log.wall, window.ops))
                       - sum(map(phase_log.wall, window.children()))) / n}
    walls: dict = {}
    for rec in window.below:
        walls.setdefault(rec["name"], []).append(phase_log.wall(rec))
        per_op[rec["name"]] = per_op.get(rec["name"], 0.0) \
            + phase_log.wall(rec) / n
        for key, secs in rec.items():
            if key.startswith("step."):
                name = f"{rec['name']}.{key[5:]}"
                per_op[name] = per_op.get(name, 0.0) + secs / n
    median = {name: statistics.median(w) for name, w in walls.items()}

    def told(rec: dict) -> dict:
        return {**{k: v for k, v in rec.items() if k not in ("t0", "t1")},
                "wall_s": phase_log.wall(rec),
                "median_s": median[rec["name"]],
                "op_index": index[rec["op"]]}

    return {"op_wall_s": [phase_log.wall(op) for op in window.ops],
            "per_op_s": per_op,
            "stalls": [told(r) for r in window.below if r.get("stall")],
            "slow": [told(r) for r in window.below
                     if phase_log.wall(r) > SLOW * median[r["name"]]
                     and phase_log.wall(r) > 0.01]}


def main(argv=None) -> int:
    def also(driver, got):
        try:
            from hypergraphdb_tpu.obs import phase_log as ring
        except ImportError:  # a program without the ring: a plain run
            return None
        args = dict(zip(argv[::2], argv[1::2]))
        out = os.path.join(run.ROOT, "chiprun_out", "phase_log")
        os.makedirs(out, exist_ok=True)
        ring().dump(os.path.join(
            out, f"{args['--workload']}.{args.get('--seed', 0)}"
                 f".t{args.get('--trace', 0)}.jsonl"))
        return summary(len(driver.runs))

    argv = list(sys.argv[1:] if argv is None else argv)
    return run.main(argv, also=also)


if __name__ == "__main__":
    sys.exit(main())
