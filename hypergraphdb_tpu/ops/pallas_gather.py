"""Pallas TPU kernel: double-buffered HBM row gather + fixed-width OR.

The pull-BFS reduction (:mod:`hypergraphdb_tpu.ops.ellbfs`) spends its time
gathering Kw-word rows of the transposed visited bitmap through CSR index
plans — the access pattern of the reference's incidence-set walk
(``core/src/java/org/hypergraphdb/algorithms/HGBreadthFirstTraversal.java:49-66``)
re-laid as one row fetch per edge. This module implements that fetch as a
hand-pipelined Pallas kernel: a grid over output blocks, scalar-prefetched
indices, :func:`slots` in-flight slots of ``w`` single-row async copies
each (double-buffered DMA), and a VPU OR-chain per output chunk, at any
chunk width ``w`` a segment holds a grid step of (the pull plan's level 0
comes in width classes, ``ellbfs.CLASS_WIDTHS``).

Measured reality on v5e (first a microbench of PR 22, 4M×512B table, 2M
random rows, 3 reps; then the 10M-atom cells):

======================  ==============  ===========
path                    rows/s          effective
======================  ==============  ===========
XLA gather, 128B rows   ~22M            ~2.9 GB/s
XLA gather, 512B rows   ~30M            ~15 GB/s
this kernel, 512B rows  ~29-31M         ~16 GB/s
======================  ==============  ===========

The "~30M descriptors/s issue floor" those rows were read as is not one:
in the cells the kernel reads 55M rows/s — 18.2 ns an index at w = 8,
the same in all four level-0 gathers of both cells, whatever share of the
indices are pads (13-39%: a fetch of the zero row costs what a real one
does; predicating pad fetches away measured slower, 19.5M useful
fetches/s against 29.4M). The kernel is bound by the copies it ISSUES,
not by bytes and not by latency, so a hop costs its plan's index count.
By chunk width, alone over a 10M×512B table (ns an index, pads included;
``benchmarks/tests/gather_width_probe.py``, PERF.md section 6, PR 30):

=====  =====  =====  =====  =====  =====  =====  =====  =====  =====  =====
w      2      4      6      8      10     14     20     28     40     56
=====  =====  =====  =====  =====  =====  =====  =====  =====  =====  =====
ns     23.6   20.6   21.1   18.6   19.1   18.2   15.5   15.1   14.6   14.5
=====  =====  =====  =====  =====  =====  =====  =====  =====  =====  =====

A chunk costs ~20 ns of its own, an index ~14.3 at best; multiples of 32
are slow (16.5-16.7 at 32, 64, 128 between neighbours at 14.3-15.1), odd
widths cost what the next even one does, and the XLA gather reads 17.4
at w = 8 over the same indices. The other lever is ROW WIDTH — 512-byte
rows (4096-seed blocks) quadruple the useful bytes per descriptor — which
is why ``ellbfs`` carries visited-only state to fit wide blocks in HBM.
The kernel is kept as the default TPU path at supported widths (a 3-hop
traversal read 12.92 s on it and 14.32 s on the XLA gather, PR 22), with
the XLA gather as the fallback everywhere else.

Constraints (Mosaic, this toolchain): rows must be exactly 128 lanes
(``ROW_WORDS`` — narrower VMEM blocks fail to compile, and at 256+ the
single-row DMA fails the tiling check; :func:`declined`), and the
scalar-prefetched index segment must fit the 1 MB SMEM, so long index
arrays are processed in ``SEG``-index segments under ``lax.scan``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hypergraphdb_tpu import verify as hgverify

#: per-core SMEM budget the scalar-prefetched index segment must fit
#: (matches hglint HG503's model of PrefetchScalarGridSpec operands)
SMEM_BUDGET = 1 << 20
#: indices per pallas_call: 512 KB of the 1 MB SMEM budget
SEG = 1 << 17
# import-time twin of the hglint HG503 contract: one int32 index segment
# must leave SMEM headroom for Mosaic's own scalar state — a SEG bump that
# blows the budget should fail here, not in opaque Mosaic allocation
# (a real raise, not an assert: the guard must survive `python -O`)
if SEG * 4 > SMEM_BUDGET // 2:
    raise ValueError(
        "pallas_gather.SEG: scalar-prefetch segment exceeds half the "
        "SMEM budget"
    )
#: output chunks per grid step
G = 256
#: single-row copies the kernel keeps outstanding, at least, and the slots
#: it never goes below (see :func:`slots`)
IN_FLIGHT = 32
MIN_SLOTS = 4
#: below this many indices the XLA gather's lower fixed cost wins
MIN_INDICES = 1 << 15
#: per-core VMEM budget the kernel's working set must fit (see
#: ``_vmem_bytes``); matches hglint HG501's default budget
VMEM_BUDGET = 16 << 20
#: the one row width (uint32 words; 4096 seeds) the kernel compiles at
ROW_WORDS = 128


def slots(w: int) -> int:
    """In-flight DMA slots of ``w`` row copies each: the power of two that
    keeps at least ``IN_FLIGHT`` copies outstanding, and at least
    ``MIN_SLOTS``. The kernel is bound by the copies it can ISSUE, not by
    their latency, so slots beyond that only lengthen each grid step's
    fill and drain: at w = 8, 16 slots (128 copies, the constant this
    replaces) read 18.9 ns an index and 4 read 18.5; at w = 24, 8 slots
    16.7 and 4 15.4; two slots starve at w = 16 (19.2). A power of two
    because a chunk's slot is ``c mod slots``, once a chunk — 13 slots at
    w = 10 cost 20.0 ns an index, 16 cost 19.4 (PERF.md section 6, PR 30)."""
    return max(MIN_SLOTS, 1 << (-(-IN_FLIGHT // w) - 1).bit_length())


def _seg(w: int) -> int:
    """Indices one ``pallas_call`` takes at width ``w``: the whole
    ``G``-chunk grid steps that fit ``SEG``."""
    return SEG // (G * w) * (G * w)


def whole_segments(n: int, w: int) -> int:
    """The most indices up to ``n`` that :func:`gather_or` takes at width
    ``w`` without a pad chunk (it pads a call to whole segments, and a pad
    chunk costs what a real one does): whole segments of ``n``, or ``n``
    itself where it is less than one."""
    seg = _seg(w)
    return n // seg * seg if n >= seg else n


def _vmem_bytes(w: int, Kw: int) -> int:
    """Static VMEM working set of one ``_call``: the (G, Kw) uint32 output
    window double-buffered across grid steps + the (slots(w)*w, Kw) uint32
    DMA row scratch. ``w``/``Kw`` are runtime-chosen, so hglint HG502
    cannot fold this bound — this guard enforces it instead (the kernel
    would otherwise die in Mosaic allocation with an opaque error, or only
    on hardware while CPU interpret tests pass)."""
    return 4 * Kw * (2 * G + slots(w) * w)


def declined(w: int, Kw: int) -> str | None:
    """None when the kernel serves ``w``-wide chunks of ``Kw``-word rows;
    otherwise the reason callers must take the XLA gather. The ONE gate:
    ``gather_or`` raises it, ``ellbfs._reduce_level`` routes on it, and
    ``tests/test_tpu_compile.py`` holds it to what the v5e compiler
    accepts."""
    if Kw != ROW_WORDS:
        return (f"rows of {Kw} words: Mosaic accepts the single-row DMA "
                f"only at {ROW_WORDS}-word rows (narrower VMEM blocks fail "
                f"to compile; wider is refused with 'Slice shape along "
                f"dimension 0 must be aligned to tiling (8), but is 1')")
    if not 1 <= w <= SEG // G:
        # a segment is whole G-chunk grid steps; a chunk wider than
        # SEG / G would leave the grid empty and the buffer unwritten
        return f"w={w}: a grid step of {G} chunks must fit SEG={SEG}"
    if _vmem_bytes(w, Kw) > VMEM_BUDGET:
        return (f"VMEM working set {_vmem_bytes(w, Kw)} B (w={w}, "
                f"Kw={Kw}) exceeds the {VMEM_BUDGET} B per-core budget")
    return None


def _kernel(idx_ref, values, out_ref, rows, sems, *, w, Kw):
    g = pl.program_id(0)
    D = slots(w)

    def start(c, slot):
        base = g * G * w + c * w
        rbase = slot * w
        for j in range(w):
            pltpu.make_async_copy(
                values.at[pl.ds(idx_ref[base + j], 1), :],
                rows.at[pl.ds(rbase + j, 1), :],
                sems.at[slot],
            ).start()

    for p in range(D):
        start(p, p)

    def body(c, _):
        slot = jax.lax.rem(c, D)
        base = slot * w
        pltpu.make_async_copy(
            rows.at[pl.ds(base, w), :],
            rows.at[pl.ds(base, w), :],
            sems.at[slot],
        ).wait()
        res = rows[pl.ds(base, 1), :]
        for j in range(1, w):
            res = res | rows[pl.ds(base + j, 1), :]
        out_ref[pl.ds(c, 1), :] = res

        @pl.when(c + D < G)
        def _():
            start(c + D, slot)

        return 0

    jax.lax.fori_loop(0, G, body, 0)


def _call(seg_idx: jax.Array, values: jax.Array, w: int,
          interpret: bool) -> jax.Array:
    Kw = values.shape[1]
    n_out = seg_idx.shape[0] // w
    D = slots(w)
    # budget enforced by gather_or's _vmem_bytes guard (runtime shapes)
    return pl.pallas_call(  # hglint: disable=HG502
        functools.partial(_kernel, w=w, Kw=Kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_out // G,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((G, Kw), lambda i, s: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((D * w, Kw), jnp.uint32),
                            pltpu.SemaphoreType.DMA((D,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n_out, Kw), jnp.uint32),
        interpret=interpret,
        name="hg_gather_or",  # the kernel's name in a profile
    )(seg_idx, values)


@hgverify.entry(
    shapes=lambda: (hgverify.sds((8, 128), "uint32"),
                    hgverify.sds((2048,), "int32")),
    statics={"w": 8, "interpret": True},
)
def gather_or(values: jax.Array, idx: jax.Array, w: int,
              interpret: bool = False) -> jax.Array:
    """``OR over groups of w``: returns ``(len(idx)//w, Kw)`` uint32 where
    row c = OR of ``values[idx[c*w : (c+1)*w]]``. ``len(idx) % w == 0`` and
    a shape :func:`declined` admits required. Trace-safe (callable under
    jit)."""
    E = idx.shape[0]
    Kw = values.shape[1]
    if E % w:
        raise ValueError(f"gather_or: need len(idx) % {w} == 0, got E={E}")
    why = declined(w, Kw)
    if why is not None:
        raise ValueError(f"gather_or: {why}")
    n_out = E // w
    # pad to whole G-chunk blocks (pad chunks gather row 0 and are sliced
    # off — chunks are independent, so garbage rows never mix in)
    seg = min(_seg(w), _ceil(E, G * w))
    E_pad = _ceil(E, seg)
    if E_pad != E:
        idx = jnp.concatenate(
            [idx, jnp.zeros((E_pad - E,), dtype=idx.dtype)]
        )
    if E_pad == seg:
        out = _call(idx, values, w, interpret)
    else:
        _, outs = jax.lax.scan(
            lambda c, s: (c, _call(s, values, w, interpret)),
            None, idx.reshape(E_pad // seg, seg),
        )
        out = outs.reshape(E_pad // w, Kw)
    return out[:n_out] if E_pad != E else out


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


#: backends whose probe has passed (a failed probe raises and is never
#: recorded — see :func:`pallas_ok`)
_PROBED: set = set()


def pallas_ok() -> bool:
    """Does the kernel serve on the default backend? Decided from the
    PLATFORM: off anywhere but a TPU, vetoed by ``HG_PALLAS_GATHER=0``. On
    a TPU it is probed once with a tiny instance, and a probe that Mosaic
    refuses or that answers wrong RAISES: the caller asked for the chip's
    kernel, and running the XLA gather instead without saying so would
    hide a broken chip path behind correct answers."""
    if os.environ.get("HG_PALLAS_GATHER", "1") in ("0", "false", "no"):
        return False
    backend = jax.default_backend()
    if backend != "tpu":
        return False
    if backend not in _PROBED:
        vals = jnp.arange(8 * ROW_WORDS, dtype=jnp.uint32).reshape(
            8, ROW_WORDS)
        idx = jnp.asarray(np.tile(np.arange(8, dtype=np.int32), G))
        out = gather_or(vals, idx, 8)
        expect = np.bitwise_or.reduce(
            np.asarray(vals)[np.asarray(idx)].reshape(-1, 8, ROW_WORDS),
            axis=1,
        )
        if not np.array_equal(np.asarray(out), expect):
            raise RuntimeError(
                "pallas_gather probe: the kernel compiled but answered "
                f"wrong on {jax.devices()[0].device_kind}"
            )
        _PROBED.add(backend)
    return True
