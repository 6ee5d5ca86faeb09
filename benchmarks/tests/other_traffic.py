"""One run of a cell under ANOTHER traffic file than the one
``BENCHMARK.json`` gives it: for a reading that no cell holds (``PERF.md``
section 7's candidate rows).

    python benchmarks/tests/other_traffic.py --traffic <file name> --workload <cell> ...

Every other argument is ``run.py``'s, and so is the result line; the traffic
file is found by name under ``traffic/`` and has to name the cell's driver.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traffic", required=True)
    args, rest = ap.parse_known_args(argv)
    load_cell = run.load_cell

    def with_other_traffic(workload: str, rehearse: bool) -> dict:
        spec = load_cell(workload, rehearse)
        with open(os.path.join(run.HERE, "traffic",
                               f"{args.traffic}.json")) as f:
            traffic = json.load(f)
        if traffic["driver"] != spec["traffic"]["driver"]:
            raise SystemExit(f"{args.traffic} is not a mix for the cell's "
                             f"driver {spec['traffic']['driver']!r}")
        if rehearse:
            traffic.update(traffic.get("rehearse", {}))
        spec["traffic"] = traffic
        return spec

    run.load_cell = with_other_traffic
    try:
        return run.main(rest)
    finally:
        run.load_cell = load_cell


if __name__ == "__main__":
    sys.exit(main())
