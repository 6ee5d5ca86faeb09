#!/usr/bin/env python3
"""What a row costs the two counting passes, outside a cell.

    python3 benchmarks/tests/count_blocks_probe.py --seed <n>

One process on the chip, one JSON line per part (``PERF.md`` section 6, PR 37,
holds the readings). ``ellbfs._deg_sum`` and ``_reach_counts`` are ``_bitdot``
over the row blocks they are handed; here ``_bitdot`` runs alone at the cells'
shapes — a bitmap of 10,000,072 rows of 128 words (4096 seed columns), weights
``ones`` (a reach count) and ``degrees`` (0 … 127 on the rows ``--reach``, the
cells' entities ``65:2000065``, and 0 on every other row, as ``inc_deg`` is
where nothing targets a link) — at each ``--block-rows`` (log2), with two
lists:

- ``cell``: the blocks that hold a row of ``--reach``, what a plan over the
  cells' graph lists;
- ``all``: every block, what a store that interleaves entities and links
  would list — the graph on which the mechanism does nothing;

and beside them ``parent``: the program before PR 37, a counted loop over
every block of 2^15 rows. Each line: seconds a pass (the least of ``--reps``),
ns a row visited, and how many of the 4096 columns differ from numpy over the
listed rows (the bitmap's blocks read back and unpacked on the host; a row of
the clamped last block counted once), and the process's resident bytes on the
host (the chip's runtime holds 14 GB of the host before the probe makes
anything; six threads reading the bitmap back at once passed the machine's
40 GiB in PR 37's first call, two stay under 15 GB). CPU rehearsal:
``JAX_PLATFORMS=cpu ... --rows 100072 --reach 65:20065 --block-rows 10,12
--reps 1``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

ROWS, KW = 10_000_072, 128
HOST_ROWS = 1 << 14  # rows the host unpacks at a time: 64 MB of bits
HOST_THREADS = 2


def say(part: str, **fields) -> None:
    with open("/proc/self/statm") as f:  # the host's side of the process
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    print(json.dumps({"part": part, **fields, "host_rss_bytes": rss}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--reach", default="65:2000065",
                    help="first:past-last row that has a degree")
    ap.add_argument("--block-rows", default="15,16",
                    help="log2 of the block sizes to read")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb

    run.place_caches()
    dev = jax.devices()[0]
    n_pad = args.rows
    first, last = (int(x) for x in args.reach.split(":"))
    say("device", platform=dev.platform, kind=dev.device_kind, rows=n_pad,
        reach=[first, last], module_block_rows=eb.UPDATE_ROWS)
    rng = np.random.default_rng([args.seed, 37])

    @partial(jax.jit, static_argnames=("rows",))
    def hashed(rows):
        # one fused pass, no temporary: a hash of (row, word, seed)
        r = jax.lax.broadcasted_iota(jnp.uint32, (rows, KW), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (rows, KW), 1)
        x = (r * jnp.uint32(2654435761) + c * jnp.uint32(2246822519)
             + jnp.uint32(args.seed & 0xFFFFFFFF))
        x = (x ^ (x >> 15)) * jnp.uint32(2246822519)
        return x ^ (x >> 13)

    bitmap = hashed(n_pad).block_until_ready()
    degrees = np.zeros(n_pad, dtype=np.int32)
    degrees[first:last] = rng.integers(0, 128, size=last - first)
    weights = {"ones": np.ones(n_pad, dtype=np.int32), "degrees": degrees}
    on_dev = {name: jnp.asarray(w) for name, w in weights.items()}

    @jax.jit
    def rows_from(packed_t, at):
        return jax.lax.dynamic_slice(packed_t, (at, 0), (HOST_ROWS, KW))

    def host_counts(counted: np.ndarray) -> dict:
        """Both weights' column sums over the rows ``counted`` marks, in
        numpy: the rows read back from the device a piece at a time and
        unpacked to a byte a bit; a float32 product of at most 2^14 rows of
        weights under 128 is exact, the pieces add up in int64."""
        pieces = [(lo, min(lo + HOST_ROWS, n_pad))
                  for lo in range(0, n_pad, HOST_ROWS)
                  if counted[lo: lo + HOST_ROWS].any()]

        def piece(bounds):
            lo, hi = bounds
            keep = counted[lo:hi]
            at = min(lo, n_pad - HOST_ROWS)  # the last piece, clamped
            words = np.asarray(rows_from(bitmap, at))[lo - at:][keep]
            bits = np.unpackbits(words.view(np.uint8), axis=1,
                                 bitorder="little")
            return {"ones": bits.sum(axis=0, dtype=np.int64),
                    "degrees": (degrees[lo:hi][keep].astype(np.float32)
                                @ bits.astype(np.float32)).astype(np.int64)}

        sums = {name: np.zeros(KW * 32, dtype=np.int64) for name in weights}
        with ThreadPoolExecutor(max_workers=HOST_THREADS) as pool:
            for got in pool.map(piece, pieces):
                for name in sums:
                    sums[name] += got[name]
        return sums

    def read(fn, *rest) -> tuple[float, np.ndarray]:
        """Least seconds of ``--reps`` passes, and the first pass's sums."""
        out = np.asarray(fn(bitmap, *rest))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(bitmap, *rest).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best, out

    def parent_bitdot(packed_t, vec, block_rows=1 << 15):
        """``_bitdot`` as it stood before PR 37."""
        R, Kw = packed_t.shape
        block_rows = min(block_rows, R)
        shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]

        def body(i, acc):
            start = jnp.minimum(i * block_rows, R - block_rows)
            sl = jax.lax.dynamic_slice(packed_t, (start, 0), (block_rows, Kw))
            w = jax.lax.dynamic_slice(vec, (start,), (block_rows,))
            fresh = (start + jnp.arange(block_rows)) >= i * block_rows
            w = jnp.where(fresh, w, 0)
            bits = ((sl[:, None, :] >> shifts) & 1).astype(jnp.int32)
            return acc + jnp.sum(bits * w[:, None, None], axis=0)

        acc = jax.lax.fori_loop(0, -(-R // block_rows), body,
                                jnp.zeros((32, Kw), jnp.int32))
        return acc.T.reshape(Kw * 32)

    every = host_counts(np.ones(n_pad, dtype=bool))
    for name in weights:
        s, got = read(jax.jit(parent_bitdot), on_dev[name])
        say("parent", weights=name, rows_visited=n_pad, seconds=s,
            ns_per_row=1e9 * s / n_pad,
            columns_differ=int(np.count_nonzero(got != every[name])))
    for log2 in (int(x) for x in args.block_rows.split(",")):
        ub = min(1 << log2, n_pad)
        fn = jax.jit(partial(eb._bitdot, block_rows=ub))
        n_blocks = -(-n_pad // ub)
        cell = np.zeros(n_blocks, dtype=bool)
        cell[first // ub: (last - 1) // ub + 1] = True
        for which, blocks in (("cell", cell),
                              ("all", np.ones(n_blocks, dtype=bool))):
            # a block's own rows: the clamped last one's start from where
            # the block before ends
            want = (every if blocks.all()
                    else host_counts(np.repeat(blocks, ub)[:n_pad]))
            listed = eb._block_starts(blocks, n_pad, ub)
            visited = int(blocks.sum()) * ub
            for name in weights:
                s, got = read(fn, on_dev[name], *listed)
                say("bitdot", weights=name, block_rows=ub, list=which,
                    blocks=int(blocks.sum()), rows_visited=visited,
                    share=visited / n_pad, seconds=s,
                    ns_per_row=1e9 * s / visited,
                    columns_differ=int(np.count_nonzero(got != want[name])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
