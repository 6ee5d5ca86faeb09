#!/usr/bin/env python3
"""What a row costs the whole-graph folds, by the route of their fetch,
outside a cell.

    python3 benchmarks/tests/scalar_fold_probe.py --seed <n> [--plan-seed <n>]

One process on the chip, one JSON line per part. A WCC round and a PageRank
iteration end in ``ellbfs._fold_rows`` over a flat state: ``state[v] =
combine(state[v], buf[out_map[v]])`` over the plan's listed row blocks of
``ellbfs.UPDATE_ROWS`` rows. Its fetch ``buf[out_map[block]]`` is either the
XLA gather (``use_pallas=False``) or the scalar form of the row-gather
kernel at width 1 (``pallas_gather.gather_reduce(buf, idx, 1, op)``, where
``_update_on_kernel`` admits the state and its table). Here the fold runs
alone at the cells' shapes — a state of 10,000,072 rows, a stage-2 buffer
of 2,108,462 values padded to whole (8, 128) tiles as the kernel route's
pyramid leaves it, an ``out_map`` that sends the rows ``--reach`` into the
buffer class by class and every other row to its zero row; or, with
``--plan-seed``, the untyped plan of ``pagerank10m.iter10`` built as the
cell builds it: its ``out_map``, its listed blocks, its stage-2 buffer's
length. Two folds, as the operators run them: the int32 min with
``_LOWERED``'s count (WCC) and the float32 replacement into zeros
(PageRank). Parts:

- ``fold``: seconds a pass (the least of ``--reps``, the state donated as
  the programs donate it), ns a row visited, by route, and the rows of
  4096 sampled that differ from numpy (and the count's error);
- ``xla_buffer``: the XLA route over a buffer of the plan's exact length
  beside the padded one (whether the pad moves the XLA fold);
- ``fetch``: the fetch alone over the listed rows' ``out_map``, one call a
  block in a loop as the fold makes them, ns an index, ``buf[idx]`` beside
  the kernel, and the rows in which the two differ.

CPU rehearsal (the kernel in Pallas's interpreter, a small graph):
``JAX_PLATFORMS=cpu ... --rows 200072 --buffer-rows 20001 --reach 65:70065
--reps 1 --interpret``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
ROWS, BUFFER_ROWS = 10_000_072, 2_108_462


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def synthetic(args, rng):
    """``(n_pad, n_atoms, buffer rows, out_map, listed blocks)`` of a plan
    shaped as the cells' are: rows in order within a width class, the
    classes one after another in the buffer; the rest on its zero row."""
    n_pad, n_buf = args.rows, args.buffer_rows
    first, last = (int(x) for x in args.reach.split(":"))
    out_map = np.full(n_pad, n_buf - 1, dtype=np.int32)
    cls = rng.integers(0, 9, size=last - first)
    out_map[first + np.argsort(cls, kind="stable")] = \
        np.arange(last - first) % (n_buf - 1)
    ub = min(args.block_rows, n_pad)
    blocks = np.zeros(-(-n_pad // ub), dtype=bool)
    blocks[first // ub: (last - 1) // ub + 1] = True
    return n_pad, n_pad - 7, n_buf, out_map, blocks


def from_plan(seed: int):
    """The same five of the untyped plan of ``pagerank10m.iter10``."""
    import run

    from hypergraphdb_tpu.ops import ellbfs as eb

    spec = run.load_cell("pagerank10m.iter10", rehearse=False)
    cfg = spec["config"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, seed, {})
    plans = eb.plans_for(sut.snap)
    n_buf = sum(len(l) // w for l, w in zip(plans.stage2_levels,
                                            plans.stage2_widths)) + 1
    return (plans.n_pad, plans.n_atoms, n_buf, plans.out_map,
            eb._active_blocks(plans))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-seed", type=int, default=None,
                    help="fold over the PageRank cell's plan at this seed")
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--buffer-rows", type=int, default=BUFFER_ROWS)
    ap.add_argument("--reach", default="65:2000065",
                    help="first:past-last row that the buffer can reach")
    ap.add_argument("--block-rows", type=int, default=1 << 16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel in Pallas's interpreter (CPU)")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops import pallas_gather as pg

    if args.interpret:
        real = pg.gather_reduce
        pg.gather_reduce = lambda v, i, w, op: real(v, i, w, op,
                                                    interpret=True)
    rng = np.random.default_rng([args.seed, 42])
    n_pad, n_atoms, n_buf, out_map, blocks = (
        synthetic(args, rng) if args.plan_seed is None
        else from_plan(args.plan_seed))
    ub = eb._block_rows(n_pad, args.block_rows)
    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=dev.device_kind, rows=n_pad,
        atoms=n_atoms, buffer_rows=n_buf, block_rows=ub,
        blocks_listed=int(blocks.sum()), blocks=len(blocks),
        plan_seed=args.plan_seed,
        kernel_route={dt: eb._update_on_kernel(
            jax.ShapeDtypeStruct((n_pad,), dt), True, args.block_rows,
            table=jax.ShapeDtypeStruct((n_buf,), dt))
            for dt in ("int32", "float32")})

    folded = np.zeros(n_pad, dtype=bool)
    for b in np.flatnonzero(blocks):
        start = min(b * ub, n_pad - ub)
        folded[start: start + ub] = True
    visited = int(blocks.sum()) * ub
    rows_arg = eb._listed(jnp.asarray(out_map), blocks, args.block_rows)
    sample = np.unique(np.concatenate([
        rng.integers(0, n_pad, size=2048),
        rng.choice(np.flatnonzero(folded), size=2042),
        [0, n_atoms, n_pad - 1]])).astype(np.int32)
    sample_dev = jnp.asarray(sample)

    folds = {  # op: (dtype, combine, gain, the state a pass starts from)
        "min": (np.int32, jnp.minimum, eb._LOWERED,
                lambda: jnp.where(jnp.arange(n_pad, dtype=jnp.int32)
                                  < n_atoms,
                                  jnp.arange(n_pad, dtype=jnp.int32),
                                  eb.INT32_MAX)),
        "sum": (np.float32, lambda cur, reached: reached, None,
                lambda: jnp.zeros((n_pad,), jnp.float32)),
    }

    def buffer(op, dtype, length):
        ident = pg.scalar_identity(op, dtype)
        vals = (rng.integers(0, n_atoms, size=n_buf).astype(dtype)
                if op == "min" else rng.random(n_buf, dtype=np.float32))
        vals[n_buf - 1] = ident
        full = np.full(length, ident, dtype=dtype)
        full[:n_buf] = vals
        return full

    def read(fn, fresh, reach_dev):
        """Least seconds of ``--reps`` passes, each over a fresh donated
        state, and the first pass's output."""
        out = fn(fresh(), reach_dev, rows_arg, jnp.int32(n_atoms))
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.reps):
            state = fresh()
            state.block_until_ready()
            t0 = time.perf_counter()
            jax.block_until_ready(fn(state, reach_dev, rows_arg,
                                     jnp.int32(n_atoms)))
            best = min(best, time.perf_counter() - t0)
        return best, out

    padded = -(-n_buf // pg.G_SCALAR) * pg.G_SCALAR
    for op, (dtype, combine, gain, fresh) in folds.items():
        reach = buffer(op, dtype, padded)
        reach_dev = jnp.asarray(reach)
        before = np.asarray(fresh()[sample_dev])
        fetched = reach[out_map[sample]]
        want = np.where(folded[sample], np.minimum(before, fetched)
                        if gain else fetched, before)
        want[sample == n_atoms] = reach[n_buf - 1]
        if gain:
            state0 = np.asarray(fresh())
            lowered = int(np.count_nonzero(
                folded & (reach[out_map] < state0)))
            del state0

        def fold_fn(kernel):
            return jax.jit(partial(eb._fold_rows, combine=combine,
                                   block_rows=args.block_rows, gain=gain,
                                   use_pallas=kernel), donate_argnums=(0,))

        for route in ("xla", "kernel"):
            s, out = read(fold_fn(route == "kernel"), fresh, reach_dev)
            got = out[0] if gain else out
            fields = {}
            if gain:
                fields["count_error"] = int(out[1]) - lowered
            say("fold", op=op, route=route, rows_visited=visited, seconds=s, ns_per_row=1e9 * s / visited,
                rows_differ=int(np.count_nonzero(
                    np.asarray(got[sample_dev]) != want)), **fields)
            del out, got

        # the XLA route over the plan's exact buffer beside the padded one
        exact = jnp.asarray(reach[:n_buf])
        s, out = read(fold_fn(False), fresh, exact)
        say("xla_buffer", op=op, buffer_rows=n_buf, padded_rows=padded,
            seconds=s, ns_per_row=1e9 * s / visited)
        del out, exact

        # the fetch alone, one call a listed block as the fold makes them
        idx = jnp.asarray(out_map)
        starts = jnp.asarray(np.minimum(np.flatnonzero(blocks) * ub,
                                        n_pad - ub).astype(np.int32))

        def fetch_fn(kernel):
            def body(i, acc):
                sl = jax.lax.dynamic_slice(idx, (starts[i],), (ub,))
                got = (pg.gather_reduce(reach_dev, sl, 1, op) if kernel
                       else reach_dev[sl])
                return jax.lax.dynamic_update_slice(acc, got, (i * ub,))

            return jax.jit(lambda: jax.lax.fori_loop(
                0, starts.shape[0], body,
                jnp.zeros((starts.shape[0] * ub,), dtype)))

        got = {}
        for route in ("xla", "kernel"):
            fn = fetch_fn(route == "kernel")
            got[route] = np.asarray(fn())
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                fn().block_until_ready()
                best = min(best, time.perf_counter() - t0)
            say("fetch", op=op, route=route, indices=visited,
                seconds=best, ns_per_index=1e9 * best / visited,
                rows_differ=int(np.count_nonzero(got[route] != got["xla"])))
        del got, reach_dev
    return 0


if __name__ == "__main__":
    sys.exit(main())
