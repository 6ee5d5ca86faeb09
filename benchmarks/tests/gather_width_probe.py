#!/usr/bin/env python3
"""What an index costs ``hg_gather_or`` at each chunk width, outside a cell.

    python3 benchmarks/tests/gather_width_probe.py --seed <n>

One process on the chip, one JSON line per part (``PERF.md`` section 6, PR 30,
holds the readings). The table is the cells' bitmap, 10,000,072 rows of 128
words; each width gathers the same number of indices, in chunks whose real
entries number ``w/2 + 1 .. w`` (what the smallest class width at or above
a row's degree leaves) and whose other entries are the zero row, as a plan's
pads are:

- ``width``: ``ops.pallas_gather.gather_or`` alone at one chunk width —
  seconds a pass (the least of ``--reps``), ns an index, pads included, the
  slots it holds in flight, and its first chunks against numpy;
- ``xla``: the XLA gather the upper pyramid levels run on
  (``ellbfs._reduce_level``, ``use_pallas=False``) at width 8, for the same
  indices;
- ``reckon`` (``--reckon <cell>``; host only, no plan built): from the
  cell's own degree tables, the plan's indices a hop by stage, level-0 class
  by class and the upper levels, for ``--classes``.

``--in-flight`` and ``--min-slots`` set ``pallas_gather.IN_FLIGHT`` and
``MIN_SLOTS`` for the process, as a test sets a constant: how the copies
outstanding move the price.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

ROWS, KW = 10_000_072, 128


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def chunks(rng, n_idx: int, w: int, rows: int) -> np.ndarray:
    """``n_idx`` indices in chunks of ``w``: the first ``w/2 + 1 .. w`` of a
    chunk are uniform rows, the rest the zero row (``rows - 1``)."""
    n = n_idx // w
    real = rng.integers(w // 2 + 1, w + 1, size=n)
    idx = rng.integers(0, rows - 1, size=(n, w), dtype=np.int64)
    idx[np.arange(w)[None, :] >= real[:, None]] = rows - 1
    return idx.reshape(-1).astype(np.int32)


def level_indices(deg: np.ndarray, classes, w_upper: int = 8) -> dict:
    """The classed plan's index counts for rows of these degrees: level 0
    by class width, then the upper levels (``ellbfs.build_reduce_plan``'s
    arithmetic on degrees alone)."""
    classes = np.asarray(classes)
    w_max = int(classes[-1])
    deg = deg[deg > 0]
    cls = np.minimum(np.searchsorted(classes, deg), len(classes) - 1)
    nchunk = np.where(deg > w_max, -(-deg // w_max), 1)
    lvl0 = {int(w): int(nchunk[cls == c].sum()) * int(w)
            for c, w in enumerate(classes)}
    rows = {int(w): int(np.count_nonzero(cls == c))
            for c, w in enumerate(classes)}
    upper, live = [], nchunk[nchunk > 1]
    while len(live):
        live = -(-live // w_upper)
        upper.append(int(live.sum()) * w_upper)
        live = live[live > 1]
    return {"lvl0": lvl0, "rows": rows, "upper": upper,
            "rows_above": int(np.count_nonzero(deg > w_max))}


def reckon(cell: str, seed: int, classes) -> None:
    """Degree arithmetic on the cell's generator: the whole graph, or the
    links of the run's family where the configuration has one (drawn as
    ``drivers/typed_back_to_back`` draws it)."""
    cfg = run.load_cell(cell, False)["config"]
    tb = run.load_module("builders", cfg["builder"]).tables(cfg, seed)
    l0, n = tb["entities"][1], tb["n_atoms"]
    arity, keep = tb["arities"], np.ones(n - l0, bool)
    if "family_types" in cfg:
        types = tb["type_of"][l0:]
        family = np.random.default_rng([seed, 5]).choice(
            np.unique(types), cfg["family_types"], replace=False)
        keep = np.isin(types, family)
    inc = np.bincount(tb["tgt_flat"][np.repeat(keep, arity)], minlength=n)
    say("reckon", cell=cell, seed=seed, classes=list(classes),
        entries=int(arity[keep].sum()),
        stage1=level_indices(arity[keep], classes),
        stage2=level_indices(inc, classes))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--widths", default=None,
                    help="default: ellbfs.CLASS_WIDTHS")
    ap.add_argument("--indices", type=int, default=1 << 23)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--in-flight", type=int, default=None)
    ap.add_argument("--min-slots", type=int, default=None)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--reckon", default=None, metavar="CELL")
    ap.add_argument("--classes", default=None,
                    help="default: ellbfs.CLASS_WIDTHS")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU rehearsal: the kernel through the interpreter")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops import pallas_gather as pg

    def widths_of(arg):
        return [int(w) for w in arg.split(",")] if arg else \
            list(eb.CLASS_WIDTHS)

    if args.reckon:
        reckon(args.reckon, args.seed, widths_of(args.classes))
        return 0
    widths = widths_of(args.widths)

    run.place_caches()
    if args.in_flight:
        pg.IN_FLIGHT = args.in_flight
    if args.min_slots:
        pg.MIN_SLOTS = args.min_slots
    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=dev.device_kind,
        in_flight=pg.IN_FLIGHT, min_slots=pg.MIN_SLOTS, rows=args.rows,
        indices=args.indices)
    rng = np.random.default_rng([args.seed, 30])

    @jax.jit
    def make_table():
        # one fused pass, no temporary (jax.random's would not fit beside
        # the table): a hash of (row, word, seed); the last row zero
        r = jax.lax.broadcasted_iota(jnp.uint32, (args.rows, KW), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (args.rows, KW), 1)
        x = (r * jnp.uint32(2654435761) + c * jnp.uint32(2246822519)
             + jnp.uint32(args.seed & 0xFFFFFFFF))
        x = (x ^ (x >> 15)) * jnp.uint32(2246822519)
        return jnp.where(r == args.rows - 1, jnp.uint32(0), x ^ (x >> 13))

    table = make_table().block_until_ready()

    def timed(fn, idx):
        out = fn(table, idx).block_until_ready()  # compile, warm
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(table, idx).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return out, best

    def differ(out, idx, w):
        n = min(512, len(idx) // w)
        rows = np.asarray(table[idx[: n * w]]).reshape(n, w, KW)
        return int(np.count_nonzero(
            np.asarray(out[:n]) != np.bitwise_or.reduce(rows, axis=1)))

    for w in widths:
        why = pg.declined(w, KW)
        if why is not None:
            say("width", w=w, declined=why)
            continue
        host_idx = chunks(rng, args.indices // w * w, w, args.rows)
        idx = jnp.asarray(host_idx)
        fn = jax.jit(lambda v, i, w=w: pg.gather_or(
            v, i, w, interpret=args.interpret))
        out, s = timed(fn, idx)
        say("width", w=w, slots=pg.slots(w), indices=len(host_idx),
            pad_share=float(np.mean(host_idx == args.rows - 1)),
            seconds=s, ns_per_index=1e9 * s / len(host_idx),
            words_differ=differ(out, host_idx, w))
        if w == 8:
            fn = jax.jit(lambda v, i: eb._reduce_level(v, i, 8, 1 << 16))
            out, s = timed(fn, idx)
            say("xla", w=8, indices=len(host_idx), seconds=s,
                ns_per_index=1e9 * s / len(host_idx),
                words_differ=differ(out, host_idx, 8))
        del out, idx
    return 0


if __name__ == "__main__":
    sys.exit(main())
