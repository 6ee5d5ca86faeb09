#!/usr/bin/env python3
"""``hg_gather_or`` by the FORM of its kernel body: what an index costs the
device, and what the body's text costs every run before the device starts.

    python3 benchmarks/tests/gather_tile_probe.py --seed <n>          # chip
    python3 benchmarks/tests/gather_tile_probe.py --describe          # here

One process, one JSON line per part (``ops/pallas_gather.py``'s docstring
and ``PERF.md`` section 6, PR 35, hold the readings). The table, the
indices and the pad shares are ``gather_width_probe.py``'s. The forms, each
through ``pallas_gather.gather_or``'s own padding and segments with ``pallas_gather._call`` swapped for the process, as a
test sets a constant:

- ``chunk``: the kernel as it stood before PR 35 — a loop step waits for
  ONE chunk's ``w`` single-row copies, ORs ``w`` rows of one sublane,
  stores one row (``(old slots + 1) * w`` copy starts in its text);
- ``tile``: the program's kernel as it is (a loop step reduces a sublane
  tile of eight chunks; the fill folded into the loop; a step of the
  rolled issue loop writes out ``written_out(w)`` chunks; Mosaic's bounds
  checks off, the indices clamped before the call);
- ``tile.d<D>``: the same with ``D`` slots in flight;
- ``tile.x<k>.d<D>``: ``k`` chunks a step of the issue loop written out
  (``k`` = 8: none rolled, ``8 * w`` copy starts);
- ``tile.c.x<k>.d<D>``: the same with every slot a CONSTANT of the code —
  the loop runs rounds of the ``D`` slots in turn (``D * k * w`` copy
  starts; ``tile.c.x8.d2`` is the text PR 34 measured, fill folded);
- ``tile.j[.t<T>].d<D>``: constant slots, the issue loop rolled over the
  copies of a chunk and the tile's ``T`` chunks (8 unless said) written
  out: what varies in a landing place is a leading dimension (``D * T``
  copy starts whatever the width); ``tile.jx…``: the same, slot computed;
- a last part ``.nb``: the same form compiled with Mosaic's bounds checks
  off (``CompilerParams(disable_bounds_checks=True)``: no ``shalt.err``
  sequence before a copy); ``.nbc``: checks off and every index clamped
  to the table on the scalar core;
- ``tile.obvious``: the tile kernel written the obvious way (PR 34's: an
  unrolled prologue over the slots and eight unrolled chunks a step —
  ``(D + 1) * 8 * w`` copy starts), for ``--trace-cost`` alone.

Parts: ``form`` (seconds a pass, the least of ``--reps``; ns an index, pads
included; its first chunks against numpy) and, with ``--trace-cost``,
``text`` (``dma_start`` equations in the traced call, seconds to trace,
to ``lower()`` and to ``compile()`` one segment, persistent cache off).
``--describe`` compiles for a DESCRIBED v5e where no chip is attached
(``text`` parts only: nothing runs). ``--grid-chunks`` sets
``pallas_gather.G`` for the process.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import gather_width_probe as gwp  # noqa: E402
import run  # noqa: E402

say = gwp.say


def chunk_slots(w: int) -> int:
    """``pallas_gather.slots`` before PR 35: slots of ``w`` copies."""
    return max(4, 1 << (-(-32 // w) - 1).bit_length())


def chunk_call(pg, seg_idx, values, w, interpret, checks=True):
    """``pallas_gather._call`` before PR 35, kept to be measured against."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, D, Kw = pg.G, chunk_slots(w), values.shape[1]

    def kernel(idx_ref, values, out_ref, rows, sems):
        g = pl.program_id(0)

        def start(c, slot):
            base = g * G * w + c * w
            for j in range(w):
                pltpu.make_async_copy(
                    values.at[pl.ds(idx_ref[base + j], 1), :],
                    rows.at[pl.ds(slot * w + j, 1), :], sems.at[slot],
                ).start()

        for p in range(D):
            start(p, p)

        def body(c, _):
            slot = jax.lax.rem(c, D)
            base = slot * w
            pltpu.make_async_copy(rows.at[pl.ds(base, w), :],
                                  rows.at[pl.ds(base, w), :],
                                  sems.at[slot]).wait()
            res = rows[pl.ds(base, 1), :]
            for j in range(1, w):
                res = res | rows[pl.ds(base + j, 1), :]
            out_ref[pl.ds(c, 1), :] = res

            @pl.when(c + D < G)
            def _():
                start(c + D, slot)

            return 0

        jax.lax.fori_loop(0, G, body, 0)

    n_out = seg_idx.shape[0] // w
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_out // G,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((G, Kw), lambda i, s: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((D * w, Kw), jnp.uint32),
                            pltpu.SemaphoreType.DMA((D,))]),
        out_shape=jax.ShapeDtypeStruct((n_out, Kw), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            disable_bounds_checks=not checks),
        interpret=interpret, name="hg_gather_or",
    )(seg_idx, values)


def tile_call(pg, seg_idx, values, w, interpret, *, D, per_step,
              const_slots=False, obvious=False, roll_j=False, T=None,
              checks=True, clamp=False):
    """The tile kernel with ``per_step`` chunks of a tile written out a
    step of the issue loop; ``const_slots``: the loop runs ROUNDS of the
    ``D`` slots in turn, each slot's reduce and issue written out, so that
    where a copy lands and what it signals are constants of the code
    (``D * per_step * w`` copy starts); ``obvious``: PR 34's text as the
    issue reckoned it (an unrolled prologue, no fold); ``roll_j``: the
    issue loop runs over the copies of a chunk, ``j``, and the tile's
    ``T`` chunks are written out — the one index of a landing place that
    varies is then a LEADING dimension of the scratch, not a sublane
    (``D * T`` copy starts with constant slots, ``T`` without)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, T, Kw = pg.G, T or pg.TILE, values.shape[1]
    NT = G // T
    last = values.shape[0] - 1

    def kernel(idx_ref, values, out_ref, rows, sems):
        g = pl.program_id(0)

        def row(at):
            r = idx_ref[at]
            return jnp.clip(r, 0, last) if clamp else r

        def issue(t, slot):
            base = (g * G + t * T) * w

            def copy_j(j, _):
                for i in range(T):
                    pltpu.make_async_copy(
                        values.at[pl.ds(row(base + i * w + j), 1), :],
                        rows.at[slot, j, pl.ds(i, 1), :], sems.at[slot],
                    ).start()
                return 0

            if roll_j:
                jax.lax.fori_loop(0, w, copy_j, 0)
                return

            def some(i0, _):
                for k in range(per_step):
                    i = i0 * per_step + k
                    for j in range(w):
                        pltpu.make_async_copy(
                            values.at[pl.ds(row(base + i * w + j), 1), :],
                            rows.at[slot, j, pl.ds(i, 1), :], sems.at[slot],
                        ).start()
                return 0

            if per_step == T:
                some(0, 0)
            else:
                jax.lax.fori_loop(0, T // per_step, some, 0)

        def reduce(t, slot):
            pltpu.make_async_copy(rows.at[slot], rows.at[slot],
                                  sems.at[slot]).wait()
            res = rows[slot, 0]
            for j in range(1, w):
                res = res | rows[slot, j]
            out_ref[pl.ds(pl.multiple_of(t * T, T), T), :] = res

        if obvious:
            for p in range(D):
                issue(p, p)

            def body(t, _):
                slot = jax.lax.rem(t, D)
                reduce(t, slot)
                pl.when(t + D < NT)(lambda: issue(t + D, slot))
                return 0

            jax.lax.fori_loop(0, NT, body, 0)
            return

        if const_slots:
            def round_(r, _):
                for slot in range(D):
                    s = r * D + slot
                    pl.when(r >= 1)(functools.partial(reduce, s - D, slot))
                    pl.when(s < NT)(functools.partial(issue, s, slot))
                return 0

            jax.lax.fori_loop(0, NT // D + 1, round_, 0)
            return

        def body(s, _):
            slot = jax.lax.rem(s, D)
            pl.when(s >= D)(lambda: reduce(s - D, slot))
            pl.when(s < NT)(lambda: issue(s, slot))
            return 0

        jax.lax.fori_loop(0, NT + D, body, 0)

    n_out = seg_idx.shape[0] // w
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_out // G,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((G, Kw), lambda i, s: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((D, w, T, Kw), jnp.uint32),
                            pltpu.SemaphoreType.DMA((D,))]),
        out_shape=jax.ShapeDtypeStruct((n_out, Kw), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            disable_bounds_checks=not checks),
        interpret=interpret, name="hg_gather_or",
    )(seg_idx, values)


def make_table(rows: int, seed: int):
    """``gather_width_probe``'s table (it builds its own inside ``main``):
    one fused pass, a hash of (row, word, seed); the last row zero."""
    import jax
    import jax.numpy as jnp

    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, gwp.KW), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, gwp.KW), 1)
    x = (r * jnp.uint32(2654435761) + c * jnp.uint32(2246822519)
         + jnp.uint32(seed & 0xFFFFFFFF))
    x = (x ^ (x >> 15)) * jnp.uint32(2246822519)
    return jnp.where(r == rows - 1, jnp.uint32(0), x ^ (x >> 13))


def form_call(pg, name: str):
    """``_call`` for a form's name, or None for the program's own."""
    if name == "tile":
        return None
    if name in ("chunk", "chunk.nb"):
        return functools.partial(chunk_call, pg, checks=name == "chunk")
    if name == "tile.obvious":
        return functools.partial(tile_call, pg, D=4, per_step=pg.TILE,
                                 obvious=True)
    parts = name.split(".")[1:]          # ["c", "x2", "d4"] … ["d4"]
    per_step = [int(p[1:]) for p in parts if p[0] == "x"] or [1]
    tile = [int(p[1:]) for p in parts if p[0] == "t"] or [None]
    slots = [int(p[1:]) for p in parts if p[0] == "d"]
    return functools.partial(tile_call, pg, D=slots[0],
                             checks="nb" not in parts and "nbc" not in parts,
                             clamp="nbc" in parts,
                             per_step=per_step[0], T=tile[0],
                             const_slots=parts[0] in ("c", "j"),
                             roll_j=parts[0] in ("j", "jx"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--widths", default=None,
                    help="default: ellbfs.CLASS_WIDTHS")
    ap.add_argument("--forms", default="chunk,tile,tile.d2,tile.d4,"
                                        "tile.x8.d2,tile.x8.d4")
    ap.add_argument("--indices", type=int, default=1 << 23)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rows", type=int, default=gwp.ROWS)
    ap.add_argument("--grid-chunks", type=int, default=None)
    ap.add_argument("--trace-cost", action="store_true",
                    help="dma_start equations, lower() and compile() "
                         "seconds of one segment, by width and form")
    ap.add_argument("--describe", action="store_true",
                    help="no chip: --trace-cost for a described v5e")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU rehearsal: the kernels through the interpreter")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops import pallas_gather as pg

    widths = ([int(w) for w in args.widths.split(",")] if args.widths
              else list(eb.CLASS_WIDTHS))
    forms = args.forms.split(",")
    if args.grid_chunks:
        pg.G = args.grid_chunks
    own_call = pg._call

    def gather(name, w):
        """A jitted ``gather_or`` at width ``w`` under form ``name``
        (``pg._call`` is read when the function is traced)."""
        call = form_call(pg, name)

        def fn(v, i):
            pg._call = call or own_call
            try:
                return pg.gather_or(v, i, w, interpret=args.interpret)
            finally:
                pg._call = own_call
        return jax.jit(fn)

    if args.describe or args.trace_cost:
        # a compile that is timed is a compile that is made
        jax.config.update("jax_enable_compilation_cache", False)
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = {"sharding": SingleDeviceSharding(topo.devices[0])}
        say("device", described="v5e:2x2", grid_chunks=pg.G)
    else:
        run.place_caches()
        dev = jax.devices()[0]
        where = {}
        say("device", platform=dev.platform, kind=dev.device_kind,
            grid_chunks=pg.G, rows=args.rows, indices=args.indices)

    if args.describe or args.trace_cost:
        for name in forms + ["tile.obvious"]:
            for w in widths:
                v = jax.ShapeDtypeStruct((1 << 20, gwp.KW), jnp.uint32,
                                         **where)
                i = jax.ShapeDtypeStruct((pg._seg(w),), jnp.int32, **where)
                fn = gather(name, w)
                t0 = time.perf_counter()
                jaxpr = jax.make_jaxpr(fn)(v, i)  # the trace lower() reuses
                t1 = time.perf_counter()
                lowered = fn.lower(v, i)
                t2 = time.perf_counter()
                lowered.compile()
                say("text", form=name, w=w,
                    dma_starts=str(jaxpr).count("dma_start"),
                    trace_s=t1 - t0, lower_s=t2 - t1,
                    compile_s=time.perf_counter() - t2)
        if args.describe:
            return 0

    rng = np.random.default_rng([args.seed, 30])
    table = jax.jit(functools.partial(make_table, args.rows,
                                      args.seed))().block_until_ready()

    for w in widths:
        host_idx = gwp.chunks(rng, args.indices // w * w, w, args.rows)
        idx = jnp.asarray(host_idx)
        n = min(512, len(host_idx) // w)
        want = np.bitwise_or.reduce(
            np.asarray(table[host_idx[: n * w]]).reshape(n, w, gwp.KW),
            axis=1)
        for name in forms:
            fn = gather(name, w)
            out = fn(table, idx).block_until_ready()  # compile, warm
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                fn(table, idx).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            say("form", form=name, w=w, indices=len(host_idx), seconds=best,
                ns_per_index=1e9 * best / len(host_idx),
                words_differ=int(np.count_nonzero(
                    np.asarray(out[:n]) != want)))
            del out
        del idx
    return 0


if __name__ == "__main__":
    sys.exit(main())
