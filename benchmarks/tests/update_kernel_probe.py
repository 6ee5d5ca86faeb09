#!/usr/bin/env python3
"""What a row costs the three updates that end a hop, by the route of their
row fetch, outside a cell.

    python3 benchmarks/tests/update_kernel_probe.py --seed <n>

One process on the chip, one JSON line per part. ``ellbfs._visited_update``,
``_frontier_replace`` and ``_ball_update`` are ``_fold_rows`` over the row
blocks they are handed; its fetch ``reach_chunks[out_map[block]]`` is either
the XLA gather (``use_pallas=False``) or ``hg_gather_or`` at width 1
(``use_pallas=True``, where ``_update_on_kernel`` admits the state). Here
the three programs run alone at the cells' shapes — a bitmap of 10,000,072
rows of 128 words, a stage-2 buffer of 2,108,462 rows, an ``out_map`` that
sends the rows ``--reach`` (the cells' entities, ``65:2000065``) into the
buffer class by class and every other row to its zero row — by both routes,
with two lists of ``ellbfs.UPDATE_ROWS``-row blocks:

- ``cell``: the blocks that hold a row of ``--reach``, what a plan over the
  cells' graph lists;
- ``all``: every block, what a store that interleaves entities and links
  would list.

Parts:

- ``text``, first: seconds to trace and lower, and to compile, each
  program by route, JAX's caches cleared before each (from nothing where no
  persistent compile cache is set);
- ``update``: seconds a pass (the least of ``--reps``), ns a row visited,
  and the words of 4096 sampled rows — every column — that differ from
  numpy after the first pass (and, for ``_ball_update``, whether the words
  of the columns that grew are the XLA route's);
- ``gather``: the fetch alone over the cell's listed rows' ``out_map``, ns
  an index, ``hg_gather_or`` at width 1 beside ``reach[idx]``, and the
  rows in which the two differ.

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
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the checkout

ROWS, BUFFER_ROWS, KW = 10_000_072, 2_108_462, 128
PROGRAMS = ("_visited_update", "_frontier_replace", "_ball_update")
COMBINE = {"_visited_update": lambda cur, reached: cur | reached,
           "_frontier_replace": lambda cur, reached: reached,
           "_ball_update": lambda cur, reached: cur | reached}


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--buffer-rows", type=int, default=BUFFER_ROWS)
    ap.add_argument("--reach", default="65:2000065",
                    help="first:past-last row that the buffer can reach")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel in Pallas's interpreter (CPU)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops import pallas_gather as pg

    if args.interpret:
        gather_or = pg.gather_or
        pg.gather_or = partial(gather_or, interpret=True)
    dev = jax.devices()[0]
    n_pad, n_buf = args.rows, args.buffer_rows
    n_atoms = n_pad - 7  # the dummy row, in the last block
    first, last = (int(x) for x in args.reach.split(":"))
    ub = min(eb.UPDATE_ROWS, n_pad)
    state_sds = jax.ShapeDtypeStruct((n_pad, KW), jnp.uint32)
    say("device", platform=dev.platform, kind=dev.device_kind, rows=n_pad,
        buffer_rows=n_buf, reach=[first, last], block_rows=ub,
        kernel_route=eb._update_on_kernel(state_sds, True),
        min_indices=pg.MIN_INDICES)
    rng = np.random.default_rng([args.seed, 39])

    # a plan's out_map: rows in order within a width class, the classes one
    # after another in the buffer; everything else reads the zero row
    out_map = np.full(n_pad, n_buf - 1, dtype=np.int32)
    cls = rng.integers(0, 9, size=last - first)
    out_map[first + np.argsort(cls, kind="stable")] = \
        np.arange(last - first) % (n_buf - 1)
    out_map_dev = jnp.asarray(out_map)

    @partial(jax.jit, static_argnames=("rows", "salt"))
    def hashed(rows, salt):
        # one fused pass, no temporary: a hash of (row, word, seed, salt);
        # the last row zero
        r = jax.lax.broadcasted_iota(jnp.uint32, (rows, KW), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (rows, KW), 1)
        x = (r * jnp.uint32(2654435761) + c * jnp.uint32(2246822519)
             + jnp.uint32((args.seed + salt) & 0xFFFFFFFF))
        x = (x ^ (x >> 15)) * jnp.uint32(2246822519)
        return jnp.where(r == rows - 1, jnp.uint32(0), x ^ (x >> 13))

    reach = hashed(n_buf, 1).block_until_ready()
    sample = np.unique(np.concatenate([
        rng.integers(0, n_pad, size=2048), rng.integers(first, last, size=2040),
        [0, first, last - 1, last, n_atoms, n_pad - 1]])).astype(np.int32)
    sample_dev = jnp.asarray(sample)
    reached = np.asarray(reach[jnp.asarray(out_map[sample])])

    n_blocks = -(-n_pad // ub)
    cell = np.zeros(n_blocks, dtype=bool)
    cell[first // ub : (last - 1) // ub + 1] = True
    lists = {"cell": cell, "all": np.ones(n_blocks, dtype=bool)}

    # what each program's text costs to lower and compile, by route
    rows_sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        eb._listed(out_map_dev, cell))
    reach_sds = jax.ShapeDtypeStruct((n_buf, KW), jnp.uint32)
    for name in PROGRAMS:
        for kernel in (False, True):
            jax.clear_caches()  # this program's trace too
            t0 = time.perf_counter()
            lowered = getattr(eb, name).lower(
                state_sds, reach_sds, rows_sds,
                jax.ShapeDtypeStruct((), jnp.int32), use_pallas=kernel)
            t1 = time.perf_counter()
            lowered.compile()
            t2 = time.perf_counter()
            say("text", program=name, route="kernel" if kernel else "xla",
                lower_s=t1 - t0, compile_s=t2 - t1,
                chars=len(lowered.as_text()))

    def folded_rows(blocks):
        folded = np.zeros(n_pad, dtype=bool)
        for b in np.flatnonzero(blocks):
            start = min(b * ub, n_pad - ub)
            folded[start : start + ub] = True
        return folded

    def read(name, kernel, rows_arg, folded):
        """Least seconds of ``--reps`` passes over a donated state, the
        sampled rows' words that differ from numpy after the first, and
        what the first pass returned beside the state."""
        fn = partial(getattr(eb, name), use_pallas=kernel)
        state = hashed(n_pad, 2)
        before = np.asarray(state[sample_dev])
        out = fn(state, reach, rows_arg, jnp.int32(n_atoms))
        state, extra = out if isinstance(out, tuple) else (out, None)
        want = np.where(folded[sample][:, None],
                        COMBINE[name](before, reached), before)
        want[sample == n_atoms] = 0
        differ = int(np.count_nonzero(np.asarray(state[sample_dev]) != want))
        extra = None if extra is None else np.asarray(extra)
        best = float("inf")
        for _ in range(args.reps):
            state.block_until_ready()
            t0 = time.perf_counter()
            out = fn(state, reach, rows_arg, jnp.int32(n_atoms))
            state = out[0] if isinstance(out, tuple) else out
            state.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        del state
        return best, differ, extra

    for name in PROGRAMS:
        for which, blocks in lists.items():
            rows_arg = eb._listed(out_map_dev, blocks)
            folded = folded_rows(blocks)
            visited = int(blocks.sum()) * ub
            grew = {}
            for kernel in (False, True):
                s, differ, grew[kernel] = read(name, kernel, rows_arg, folded)
                fields = {}
                if grew[kernel] is not None and kernel:
                    fields["grew_equal"] = bool(
                        np.array_equal(grew[True], grew[False]))
                say("update", program=name, route="kernel" if kernel
                    else "xla", list=which, blocks=int(blocks.sum()),
                    rows_visited=visited, seconds=s,
                    ns_per_row=1e9 * s / visited, words_differ=differ,
                    **fields)

    # the fetch alone: the cell's listed rows' out_map, one call
    idx = jnp.asarray(out_map[folded_rows(cell)])
    fetch = {"xla": jax.jit(lambda v, i: v[i]),
             "kernel": jax.jit(lambda v, i: pg.gather_or(v, i, 1))}
    got = {}
    for route, fn in fetch.items():
        got[route] = fn(reach, idx)
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(reach, idx).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        say("gather", route=route, indices=int(idx.shape[0]), seconds=best,
            ns_per_index=1e9 * best / int(idx.shape[0]))
    rows_differ = int(jnp.sum(jnp.any(got["xla"] != got["kernel"], axis=1)))
    say("gather_equal", rows_differ=rows_differ)
    del got

    return 0


if __name__ == "__main__":
    sys.exit(main())
