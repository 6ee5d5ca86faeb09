#!/usr/bin/env python3
"""What a scalar costs the XLA gather of a label round, outside the cell.

    python3 benchmarks/tests/wcc_gather_probe.py --seed <n>
    python3 benchmarks/tests/wcc_gather_probe.py --hashes [--root <checkout>]

One process, one JSON line per part (``PERF.md`` section 6, PR 38, holds the
readings).

Price (on the chip): a round of ``ops.connected_components`` is two
pyramids of int32 labels — the XLA gather of one 4-byte scalar an index,
then a min over each chunk (``ellbfs._reduce_classes``, no Pallas: the
kernel serves 512-byte rows alone) — and a fold. Here one class of the
pyramid runs alone over a label vector of ``--rows`` at each ``--widths``,
``--indices`` indices a pass (the cell's two stages hold 12.89M and 13.16M
level-0 indices), indices uniform over the rows: seconds a pass (the least
of ``--reps``), ns an index, and 4096 sampled chunks against numpy. Then
(``--plan-seed``) the cell's own restricted plan, built as the cell builds
it: its level lengths and widths, its ``n_pad`` and active row blocks —
the shapes ``tests/test_tpu_compile.py`` compiles the round at.

Hashes (no chip needed): the sha256 of ``lower(...).as_text()`` of the four
bitmap stage programs (``_stage``, ``_stage_lvl0_consume``,
``_stage_upper``, ``_visited_update``) and the two other updates, at the
typed cell's rehearsal shapes (the graph of ``dbpedia10m-wcc``'s
``rehearse`` sizes under the run's family, 64 seeds, chunk 1024), from the
program of ``--root``: run it on a parent checkout and on the change, and
compare. The compile cache keys on that text. CPU rehearsal of the price:
``JAX_PLATFORMS=cpu ... --rows 100072 --indices 65536 --reps 1``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROWS, INDICES = 10_000_072, 12_890_112


def say(part: str, **fields) -> None:
    print(json.dumps({"part": part, **fields}), flush=True)


def hashes(seed: int) -> None:
    """The lowered text of the bitmap programs at the rehearsal shapes."""
    import jax

    from builders import columnar_snapshot
    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops.snapshot import CSRSnapshot

    with open(os.path.join(BENCH, "configs", "dbpedia10m-wcc.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    tb = columnar_snapshot.tables(cfg, seed)
    snap = CSRSnapshot.from_tables(
        tb["type_of"], tb["is_link"], tb["tgt_offsets"],
        tb["tgt_flat"].astype(np.int32), value_rank=tb["value_rank"])
    link_types = np.unique(tb["type_of"][tb["entities"][1]:])
    family = np.sort(np.random.default_rng([seed, 5]).choice(
        link_types, cfg["family_types"], replace=False))
    sub = eb.restricted_for(snap, family.tolist())
    plans = eb.plans_for(sub)
    dev = eb._device_plans(sub, plans)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    s1, chunk, kw = plans.stage1, 1024, 2
    n_pad = plans.n_pad
    n2 = plans.stage2_n_lvl0
    w2 = plans.stage2_widths
    lv1 = tuple(map(sds, dev["levels1"]))
    lv2 = tuple(map(sds, dev["levels2"]))
    u32 = jax.ShapeDtypeStruct
    visited = u32((n_pad, kw), np.uint32)
    live = jax.eval_shape(lambda v, l: eb._stage(
        v, l, s1.widths, s1.n_lvl0, chunk, False), visited, lv1)
    lvl0 = jax.eval_shape(lambda v, l: eb._stage_lvl0_consume(
        v, l, w2[:n2], chunk, False), live, lv2[:n2])
    n_last = len(plans.stage2_levels[n2 - 1]) // w2[n2 - 1]
    reach = jax.eval_shape(lambda v, l: eb._stage_upper(
        v, l, w2[n2:], n_last, chunk), lvl0, lv2[n2:])
    rows = jax.tree_util.tree_map(sds, dev["rows"])
    n_atoms = u32((), np.int32)
    programs = {
        "_stage": (eb._stage, (visited, lv1),
                   dict(widths=s1.widths, n_lvl0=s1.n_lvl0, chunk=chunk,
                        use_pallas=False)),
        "_stage_lvl0_consume": (eb._stage_lvl0_consume, (live, lv2[:n2]),
                                dict(widths=w2[:n2], chunk=chunk,
                                     use_pallas=False)),
        "_stage_upper": (eb._stage_upper, (lvl0, lv2[n2:]),
                         dict(widths=w2[n2:], n_last=n_last, chunk=chunk)),
    }
    for name in ("_visited_update", "_frontier_replace", "_ball_update"):
        programs[name] = (getattr(eb, name),
                          (visited, reach, rows, n_atoms), {})
    for name, (fn, args, statics) in programs.items():
        text = fn.lower(*args, **statics).as_text()
        say("hash", program=name,
            sha256=hashlib.sha256(text.encode()).hexdigest(),
            chars=len(text))


def plan_shapes(seed: int) -> None:
    """The cell's restricted plan at its full size, as the cell builds it."""
    import run

    from hypergraphdb_tpu.ops import ellbfs as eb

    spec = run.load_cell("wcc10m.family16", rehearse=False)
    cfg = spec["config"]
    sut = run.load_module("builders", cfg["builder"]).build(cfg, seed, {})
    driver = run.load_module("drivers", spec["traffic"]["driver"]).Driver(
        sut, cfg, spec["traffic"], seed, {})
    sub = eb.restricted_for(sut.snap, driver.family.tolist())
    plans = eb.plans_for(sub)
    s1 = plans.stage1
    say("plan", seed=seed, n_pad=plans.n_pad,
        levels1=[len(x) for x in s1.levels], widths1=list(s1.widths),
        n1=s1.n_lvl0, levels2=[len(x) for x in plans.stage2_levels],
        widths2=list(plans.stage2_widths), n2=plans.stage2_n_lvl0,
        active_blocks=int(eb._active_blocks(plans).sum()),
        blocks=len(eb._active_blocks(plans)),
        total_indices=plans.total_indices,
        upper_indices=plans.upper_indices,
        admitted_entries=int(sub.n_edges_tgt))


def price(args) -> int:
    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb

    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=dev.device_kind)
    r = np.random.default_rng([args.seed, 38])
    labels = r.integers(0, args.rows, size=args.rows).astype(np.int32)
    lab = jnp.asarray(labels)
    for w in (int(x) for x in args.widths.split(",")):
        n = args.indices // w * w
        idx = r.integers(0, args.rows, size=n).astype(np.int32)
        ids = jnp.asarray(idx)
        fn = jax.jit(lambda v, i, w=w: eb._reduce_classes(
            jnp.full((i.shape[0] // w + 1,), eb.INT32_MAX, v.dtype), v,
            (i,), (w,), args.chunk, False))
        out = fn(lab, ids)
        out.block_until_ready()
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(lab, ids).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        got = np.asarray(out)
        pick = r.integers(0, n // w, size=min(4096, n // w))
        want = labels[idx.reshape(-1, w)[pick]].min(axis=1)
        say("price", width=w, indices=n, seconds=best,
            ns_per_index=1e9 * best / n,
            chunks_differ=int(np.count_nonzero(got[pick] != want)),
            zero_row=int(got[-1]))
    if args.plan_seed is not None:
        plan_shapes(args.plan_seed)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--indices", type=int, default=INDICES)
    ap.add_argument("--widths", default="2,8,20,56")
    ap.add_argument("--chunk", type=int, default=1 << 16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--plan-seed", type=int, default=None,
                    help="also build the cell's plan at this seed and print "
                         "its shapes (the cell's full size)")
    ap.add_argument("--hashes", action="store_true",
                    help="print the bitmap programs' lowered-text hashes")
    ap.add_argument("--root", default=os.path.dirname(BENCH),
                    help="the checkout whose program is imported")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.abspath(args.root))
    if args.hashes:
        hashes(args.seed)
        return 0
    return price(args)


if __name__ == "__main__":
    sys.exit(main())
