"""Pallas TPU kernel: double-buffered HBM row gather + fixed-width OR.

The pull-BFS reduction (:mod:`hypergraphdb_tpu.ops.ellbfs`) spends its time
gathering Kw-word rows of the transposed visited bitmap through CSR index
plans — the access pattern of the reference's incidence-set walk
(``core/src/java/org/hypergraphdb/algorithms/HGBreadthFirstTraversal.java:49-66``)
re-laid as one row fetch per edge. This module implements that fetch as a
hand-pipelined Pallas kernel: a grid over output blocks, scalar-prefetched
indices, ``D`` in-flight slots of ``w`` single-row async copies each
(double-buffered DMA), and a VPU OR-chain per output chunk.

Measured reality on v5e (microbench, 4M×512B table, 2M random rows, 3 reps):

======================  ==============  ===========
path                    rows/s          effective
======================  ==============  ===========
XLA gather, 128B rows   ~22M            ~2.9 GB/s
XLA gather, 512B rows   ~30M            ~15 GB/s
this kernel, 512B rows  ~29-31M         ~16 GB/s
======================  ==============  ===========

Both paths sit at the chip's ~30M descriptors/s issue floor for
row-granular HBM access; predicating away pad-row fetches or splitting
descriptors across DMA priorities moves nothing (measured 19.5M useful
fetches/s predicated vs 29.4M unpredicated). The lever that actually buys
bandwidth is ROW WIDTH — 512-byte rows (4096-seed blocks) quadruple the
useful bytes per descriptor — which is why ``ellbfs`` carries visited-only
state to fit wide blocks in HBM. The kernel is kept as the default TPU
path at supported widths (it edges out XLA slightly and pins the layout),
with the XLA gather as the fallback everywhere else.

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
#: in-flight DMA slots (D*w outstanding row copies)
D = 16
#: below this many indices the XLA gather's lower fixed cost wins
MIN_INDICES = 1 << 15
#: per-core VMEM budget the kernel's working set must fit (see
#: ``_vmem_bytes``); matches hglint HG501's default budget
VMEM_BUDGET = 16 << 20
#: the one row width (uint32 words; 4096 seeds) the kernel compiles at
ROW_WORDS = 128


def _vmem_bytes(w: int, Kw: int) -> int:
    """Static VMEM working set of one ``_call``: the (G, Kw) uint32 output
    window double-buffered across grid steps + the (D*w, Kw) uint32 DMA
    row scratch. ``w``/``Kw`` are runtime-chosen, so hglint HG502 cannot
    fold this bound — this guard enforces it instead (the kernel would
    otherwise die in Mosaic allocation with an opaque error, or only on
    hardware while CPU interpret tests pass)."""
    return 4 * Kw * (2 * G + D * w)


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
    if SEG % (G * w):
        # segmenting slices idx in SEG blocks of whole G-chunk groups; a
        # width that doesn't divide them would truncate the grid to zero
        # and return an unwritten buffer
        return f"w={w} must divide SEG/G={SEG // G}"
    if _vmem_bytes(w, Kw) > VMEM_BUDGET:
        return (f"VMEM working set {_vmem_bytes(w, Kw)} B (w={w}, "
                f"Kw={Kw}) exceeds the {VMEM_BUDGET} B per-core budget")
    return None


def _kernel(idx_ref, values, out_ref, rows, sems, *, w, Kw):
    g = pl.program_id(0)

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
        pltpu.make_async_copy(
            rows.at[pl.ds(slot * w, w), :],
            rows.at[pl.ds(slot * w, w), :],
            sems.at[slot],
        ).wait()
        base = slot * w
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
    blk = G * w
    seg_pad = min(SEG, _ceil(E, blk))
    E_pad = _ceil(E, seg_pad)
    if E_pad != E:
        idx = jnp.concatenate(
            [idx, jnp.zeros((E_pad - E,), dtype=idx.dtype)]
        )
    if E_pad <= SEG:
        out = _call(idx, values, w, interpret)
    else:
        _, outs = jax.lax.scan(
            lambda c, s: (c, _call(s, values, w, interpret)),
            None, idx.reshape(E_pad // SEG, SEG),
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
