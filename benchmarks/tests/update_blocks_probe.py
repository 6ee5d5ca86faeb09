#!/usr/bin/env python3
"""What a row costs the two updates that end a hop, outside a cell.

    python3 benchmarks/tests/update_blocks_probe.py --seed <n>

One process on the chip, one JSON line per part (``PERF.md`` section 6, PR 32,
holds the readings). ``ellbfs._visited_update`` and ``_frontier_replace`` are
``_fold_rows`` over the row blocks they are handed; here ``_fold_rows`` runs
alone at the cells' shapes — a bitmap of 10,000,072 rows of 128 words, a
stage-2 buffer of 2,108,462 rows, an ``out_map`` that sends the rows
``--reach`` (the cells' entities, ``65:2000065``) into the buffer and every
other row to its zero row — at each ``--block-rows`` (log2), with two lists:

- ``cell``: the blocks that hold a row of ``--reach``, what a plan over the
  cells' graph lists;
- ``all``: every block, what a store that interleaves entities and links
  would list — the graph on which the mechanism does nothing;

and beside them ``parent``: the program before PR 32, a counted loop over
every block of 2^18 rows and a tail. Each line: seconds a pass (the least of
``--reps``), ns a row visited, and 4096 sampled rows of the state, in and out
of the listed blocks, against numpy. CPU rehearsal:
``JAX_PLATFORMS=cpu ... --rows 100072 --buffer-rows 20001 --reach 65:20065
--block-rows 10,12 --reps 1``.
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
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

ROWS, BUFFER_ROWS, KW = 10_000_072, 2_108_462, 128
COMBINE = {"visited_update": lambda cur, reached: cur | reached,
           "frontier_replace": lambda cur, reached: reached}


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--buffer-rows", type=int, default=BUFFER_ROWS)
    ap.add_argument("--reach", default="65:2000065",
                    help="first:past-last row that the buffer can reach")
    ap.add_argument("--block-rows", default="14,15,16,17,18",
                    help="log2 of the block sizes to read")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb

    run.place_caches()
    dev = jax.devices()[0]
    n_pad, n_buf = args.rows, args.buffer_rows
    n_atoms = n_pad - 7  # the dummy row, in the last block
    first, last = (int(x) for x in args.reach.split(":"))
    say("device", platform=dev.platform, kind=dev.device_kind, rows=n_pad,
        buffer_rows=n_buf, reach=[first, last],
        module_block_rows=eb.UPDATE_ROWS)
    rng = np.random.default_rng([args.seed, 32])

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

    def read(fn, name, rows_arg, folded) -> tuple[float, int]:
        """Least seconds of ``--reps`` passes over a donated state, and the
        sampled rows' words that differ from numpy after the first."""
        state = hashed(n_pad, 2)
        before = np.asarray(state[sample_dev])
        state = fn(state, reach, rows_arg, jnp.int32(n_atoms))
        want = np.where(folded[sample][:, None],
                        COMBINE[name](before, reached), before)
        want[sample == n_atoms] = 0
        differ = int(np.count_nonzero(np.asarray(state[sample_dev]) != want))
        best = float("inf")
        for _ in range(args.reps):
            state.block_until_ready()
            t0 = time.perf_counter()
            state = fn(state, reach, rows_arg, jnp.int32(n_atoms))
            state.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best, differ

    def parent_fold(state, reach_chunks, out_map, n_atoms, combine):
        """``_fold_rows`` as it stood before PR 32."""
        ub = 1 << 18
        n_full = n_pad // ub

        def fold(nxt, start, rows):
            cur = jax.lax.dynamic_slice(nxt, (start, 0), (rows, KW))
            sl = jax.lax.dynamic_slice(out_map, (start,), (rows,))
            return jax.lax.dynamic_update_slice(
                nxt, combine(cur, reach_chunks[sl]), (start, 0))

        nxt = (jax.lax.fori_loop(0, n_full, lambda i, v: fold(v, i * ub, ub),
                                 state) if n_full else state)
        if n_pad - n_full * ub:
            nxt = fold(nxt, n_full * ub, n_pad - n_full * ub)
        return nxt.at[n_atoms].set(jnp.uint32(0))

    every = np.ones(n_pad, dtype=bool)
    for name, combine in COMBINE.items():
        fn = jax.jit(partial(parent_fold, combine=combine), donate_argnums=0)
        s, differ = read(fn, name, out_map_dev, every)
        say("parent", program=name, rows_visited=n_pad, seconds=s,
            ns_per_row=1e9 * s / n_pad, words_differ=differ)
        for log2 in (int(x) for x in args.block_rows.split(",")):
            ub = min(1 << log2, n_pad)
            fn = jax.jit(partial(eb._fold_rows, combine=combine,
                                 block_rows=ub), donate_argnums=0)
            n_blocks = -(-n_pad // ub)
            cell = np.zeros(n_blocks, dtype=bool)
            cell[first // ub : (last - 1) // ub + 1] = True
            for which, blocks in (("cell", cell),
                                  ("all", np.ones(n_blocks, dtype=bool))):
                folded = np.zeros(n_pad, dtype=bool)
                for b in np.flatnonzero(blocks):
                    start = min(b * ub, n_pad - ub)
                    folded[start : start + ub] = True
                s, differ = read(fn, name,
                                 eb._listed(out_map_dev, blocks, ub), folded)
                visited = int(blocks.sum()) * ub
                say("update", program=name, block_rows=ub, list=which,
                    blocks=int(blocks.sum()), rows_visited=visited,
                    share=visited / n_pad, seconds=s,
                    ns_per_row=1e9 * s / visited, words_differ=differ)
    return 0


if __name__ == "__main__":
    sys.exit(main())
