"""Print what a trace holds: planes, lines, event counts, the commonest
names. Look at one trace by hand before trusting the reducer on it.

    python benchmarks/tests/trace_summary.py <file.xplane.pb>
"""

import collections
import sys

from jax.profiler import ProfileData


def main(path: str) -> None:
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = collections.Counter()
            total = 0
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                total += ev.duration_ns
                n += 1
            top = ", ".join(f"{k[:60]} x{v}" for k, v in names.most_common(6))
            print(f"  line {line.name!r}: {n} events, {total / 1e9:.4f} s; {top}")


if __name__ == "__main__":
    main(sys.argv[1])
