#!/usr/bin/env python3
"""What a 4-byte scalar costs the kernel and the XLA gather, outside the cells.

    python3 benchmarks/tests/scalar_gather_probe.py --seed <n> [--plan-seed <n>]

One process, one JSON line per part (``PERF.md`` section 6 holds the
readings; the ``ops/pallas_gather.py`` docstring their table).

Price (on the chip): one class of a whole-graph pyramid alone — a flat
table of ``--rows`` values, float32 summed and int32 minned, ``--indices``
indices a pass at each of ``--widths`` (default: every class width of
``ellbfs.CLASS_WIDTHS``), indices uniform over the table — through the
scalar form of the row-gather kernel (``pallas_gather.gather_reduce``: a
128-lane row fetched an index, its one lane kept) and through today's XLA
route (``ellbfs._reduce_classes`` without the kernel: ``values[idx]`` and
``_fold`` in scan blocks). Seconds a pass (the least of ``--reps``), ns an
index, the kernel's gain, and 4096 sampled chunks of each against numpy
(float64 sums, ``--rtol``; exact minima). The kernel reads the table padded
to whole (8, 128) tiles once, outside the timed call, as the pyramid does.
Then (``--plan-seed``) the untyped plan of ``pagerank10m.iter10``, built as
the cell builds it: the level-0 indices of each class, both stages — which
widths carry the iteration's indices.

CPU rehearsal (the interpreter stands in for the kernel):
``JAX_PLATFORMS=cpu ... --rows 100072 --indices 16384 --widths 2,20
--interpret --reps 1``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
ROWS, INDICES = 10_000_072, 8_388_608


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def _best(fn, args, reps: int) -> tuple[float, object]:
    out = fn(*args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best, out


def price(args) -> None:
    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops import pallas_gather as pg

    if args.interpret:  # the interpreter stands in for the chip's kernel
        real = pg.gather_reduce
        pg.gather_reduce = lambda v, i, w, op: real(v, i, w, op,
                                                    interpret=True)
    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=dev.device_kind)
    r = np.random.default_rng([args.seed, 41])
    widths = ([int(x) for x in args.widths.split(",")] if args.widths
              else list(eb.CLASS_WIDTHS))
    for op, dtype in (("sum", np.float32), ("min", np.int32)):
        vals = (r.random(args.rows, dtype=np.float32) if op == "sum"
                else r.integers(0, args.rows, size=args.rows)
                .astype(np.int32))
        values = jnp.asarray(vals)
        ident = pg.scalar_identity(op, dtype)
        table = jax.jit(lambda v: pg.scalar_table(v, ident))(values)
        for w in widths:
            n = args.indices // w * w
            idx = r.integers(0, args.rows, size=n).astype(np.int32)
            ids = jnp.asarray(idx)

            def route(kernel, w=w):
                return jax.jit(lambda v, i: eb._reduce_classes(
                    jnp.full((i.shape[0] // w + 1,), ident, v.dtype), v,
                    (i,), (w,), args.chunk, kernel))

            kern, xla = route(True), route(False)
            k_s, k_out = _best(kern, (table, ids), args.reps)
            x_s, x_out = _best(xla, (values, ids), args.reps)
            pick = r.integers(0, n // w, size=min(4096, n // w))
            g = vals[idx.reshape(-1, w)[pick]]
            if op == "sum":
                want = g.astype(np.float64).sum(axis=1)

                def differ(got):
                    return int(np.count_nonzero(~np.isclose(
                        got[pick], want, rtol=args.rtol, atol=0)))
            else:
                want = g.min(axis=1)

                def differ(got):
                    return int(np.count_nonzero(got[pick] != want))
            say("price", op=op, width=w, indices=n,
                kernel_s=k_s, kernel_ns=1e9 * k_s / n,
                xla_s=x_s, xla_ns=1e9 * x_s / n,
                gain_pct=100.0 * (1.0 - k_s / x_s),
                kernel_differ=differ(np.asarray(k_out)[: n // w]),
                xla_differ=differ(np.asarray(x_out)[: n // w]))


def plan_shapes(seed: int) -> None:
    """The untyped plan of ``pagerank10m.iter10`` at its full size."""
    import run

    from hypergraphdb_tpu.ops import ellbfs as eb

    spec = run.load_cell("pagerank10m.iter10", rehearse=False)
    cfg = spec["config"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, seed, {})
    plans = eb.plans_for(sut.snap)
    s1 = plans.stage1
    for stage, levels, widths, n in (
            ("stage1", s1.levels, s1.widths, s1.n_lvl0),
            ("stage2", plans.stage2_levels, plans.stage2_widths,
             plans.stage2_n_lvl0)):
        say("plan", seed=seed, stage=stage,
            classes={str(w): len(l) for l, w in zip(levels[:n], widths[:n])},
            upper_indices=int(sum(len(l) for l in levels[n:])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--indices", type=int, default=INDICES)
    ap.add_argument("--widths", default="",
                    help="comma-separated; default every class width")
    ap.add_argument("--chunk", type=int, default=1 << 16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rtol", type=float, default=1e-5)
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel in the Pallas interpreter (CPU)")
    ap.add_argument("--plan-seed", type=int, default=None,
                    help="also build the PageRank cell's plan at this seed "
                         "and print its level-0 classes")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, ROOT)
    price(args)
    if args.plan_seed is not None:
        plan_shapes(args.plan_seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
