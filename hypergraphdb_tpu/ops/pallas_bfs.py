"""Fused Pallas pull-BFS megakernel: a whole hop in one ``pallas_call``.

``ops/ellbfs.py`` made the 3-hop pull BFS *correct at scale* by staging a
hop as four host-sequenced jits (``_stage`` → ``_stage_lvl0_consume`` →
``_stage_upper`` → ``_visited_update``) so the 5-6 GB stage buffers free
between launches. The price is that every hop round-trips link-live and
reach-chunk state through HBM twice and pays four dispatch RTTs — BENCH
r05 measured 13.1B edges/s but only 25 GB/s effective, **3% of the v5e
HBM peak**: the chain is latency-bound, not bandwidth-bound. This is the
materialization-boundary lesson of the EmptyHeaded/TrieJax line (PAPERS):
on accelerators, graph workloads are dominated by where intermediate sets
land, not by FLOPs.

This module removes the boundary. One :func:`pl.pallas_call` per hop runs

- **level expansion**: for every output atom row, a double-buffered
  HBM→VMEM DMA pipeline gathers the visited rows of its *fused
  adjacency* — the host-composed atom→atom relation ``{t : t ∈ tgt(l),
  l ∈ inc(v)}`` (stage 1 ∘ stage 2 of the ellbfs pyramid collapsed into
  one padded chunk plan),
- **visited dedup**: a VPU OR-fold accumulates the gathered rows straight
  into a VMEM-resident output block seeded with the old visited rows
  (OR is the dedup — no sort, no unique, no frontier array), and
- **frontier compaction**: nothing but the new visited block ever leaves
  the chip — the monotone-closure trick of ``ellbfs`` (pull from VISITED,
  frontiers telescope) means the frontier is never materialized at all.

Chunk plans ride scalar prefetch (SMEM), mirroring ``pallas_gather.py``'s
``PrefetchScalarGridSpec`` + DMA-semaphore scaffolding and its
``_vmem_bytes`` budget discipline; hglint HG5xx models the same windows.
Hops chain on-device inside ONE jit (no host sequencing, no stage
buffers: peak state is two visited bitmaps instead of visited + 10.5 GB
of stage chunks), and per-hop degree sums / final reach counts reuse the
``ellbfs`` bit-dot so results are bit-identical to the unfused chain.

Layout: the visited bitmap keeps the transposed ``(rows, Kw)`` uint32
form but rows pad up to ``KWP_MIN = 128`` lanes (512-byte rows — the
measured descriptor-rate lever, and Mosaic's minimum VMEM window width).
Narrow seed blocks (K < 4096) still run fused at 128 lanes; the spare
words are zero and sliced off on exit.

Fallback contract: everything here is gated — :func:`pallas_bfs_ok`
is False off-TPU (decided from the platform) and on a TPU probes the
kernel once, RAISING if the chip's compiler refuses it; plan builders
decline, with a reason, geometries whose SMEM/VMEM windows exceed budget
and row widths Mosaic refuses; and callers (``ellbfs.bfs_pull``,
``ops/serving``) keep the unfused chain for what is declined, so CPU
tier-1 exercises the exact same entry points with ``use_pallas``
resolving to False.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hypergraphdb_tpu import verify as hgverify
from hypergraphdb_tpu.ops.ellbfs import (
    ReducePlan,
    _apply_plan,
    _bitdot,
    _ceil_to,
    _segmented_ranges,
    build_reduce_plan,
)
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot

WORD = 32

#: fused-adjacency chunk width (visited rows OR'd per chunk); must divide
#: the DMA slot layout the same way pallas_gather's ``w`` does
W = 8
#: output rows per grid step — one (8, 128) uint32 tile per buffer
B = 8
#: max blocks per segment (grid size of one pallas_call); segments scan
SEG_BLOCKS = 256
#: in-flight DMA slots (D*W outstanding row copies)
D = 8
#: lane width of a visited row — the ONLY one the kernel compiles at:
#: narrower VMEM windows fail Mosaic, and wider rows fail its tiling check
#: on the single-row DMA (``plan_supported`` declines them); also the
#: 512-byte descriptor lever
KWP_MIN = 128
#: per-core SMEM budget for the scalar-prefetched chunk plan (matches
#: hglint HG503's model); we claim at most half, like pallas_gather.SEG
SMEM_BUDGET = 1 << 20
#: per-core VMEM budget the kernel working set must fit (hglint HG501)
VMEM_BUDGET = 16 << 20
#: upper-level / overlay reduction stream chunk (XLA path)
CHUNK = 1 << 16


def _vmem_bytes(kwp: int, w: int = W) -> int:
    """Static VMEM working set of one hop call: the (B, kwp) old-visited
    and output windows double-buffered across grid steps, plus the
    (D*w, kwp) DMA row scratch. ``kwp`` is runtime-chosen, so hglint
    HG502 cannot fold this bound — this guard enforces it instead.
    ``w`` must be the PLAN's chunk width (``geom.w``), not assumed."""
    return 4 * kwp * (2 * B + 2 * B + D * w)


def _smem_bytes(cap: int, nb: int, w: int = W) -> int:
    """Scalar-prefetch bytes of one hop call: the (cap*w,) int32 fused
    index segment, the (cap,) chunk→row map, and the (nb+1,) block
    bounds. Must leave Mosaic its own SMEM headroom (half budget).
    ``w`` must be the PLAN's chunk width (``geom.w``), not assumed."""
    return 4 * (cap * w + cap + nb + 1)


# ---------------------------------------------------------------- host plans


class FusedGeom(NamedTuple):
    """Static geometry of a fused plan (hashable — rides jit statics)."""

    n_atoms: int     # N; row N is the dummy row
    n_rows: int      # padded row space = n_seg * nb * B; last row is zero
    n_seg: int       # pallas_call segments per hop
    nb: int          # blocks (grid steps) per segment
    cap: int         # chunk capacity per segment
    w: int           # chunk width
    zero_row: int    # guaranteed-all-zero visited row (= n_rows - 1)
    total_entries: int  # real fused-adjacency entries (traffic model)


@dataclass(frozen=True)
class FusedPlan:
    """Host precompute for the fused hop over one snapshot.

    The fused adjacency composes the two ellbfs stages on host: row ``v``
    lists every atom ``t`` with ``t ∈ tgt(l)`` for some incident link
    ``l ∈ inc(v)`` (duplicates kept — OR is idempotent and dedup would
    cost a sort). Rows pad to whole ``w``-chunks (pad entries gather the
    zero row); chunks order row-major, rows tile into ``B``-row blocks,
    blocks into ``nb``-block segments of uniform ``cap`` chunk capacity.
    """

    geom: FusedGeom
    blk_off: np.ndarray     # (n_seg, nb+1) int32 — chunk bounds per block
    chunk_rows: np.ndarray  # (n_seg, cap) int32 — segment-local row per chunk
    idx: np.ndarray         # (n_seg, cap*w) int32 — visited rows to gather
    inc_deg: np.ndarray     # (n_rows,) int32 — incidence degree (edge count)

    @property
    def smem_ok(self) -> bool:
        return _smem_bytes(self.geom.cap, self.geom.nb,
                           self.geom.w) <= SMEM_BUDGET // 2


def build_fused_plan(snap: CSRSnapshot, w: int = W) -> FusedPlan:
    """Compose the snapshot's two CSR stages into the fused chunk plan."""
    N = snap.num_atoms
    n1 = N + 1
    inc_off = np.asarray(snap.inc_offsets[: n1 + 1], dtype=np.int64)
    inc_links = np.asarray(snap.inc_links[: snap.n_edges_inc],
                           dtype=np.int64)
    tgt_off = np.asarray(snap.tgt_offsets[: n1 + 1], dtype=np.int64)
    tgt_flat = np.asarray(snap.tgt_flat[: snap.n_edges_tgt], dtype=np.int32)

    e_inc = len(inc_links)
    # per incidence entry: arity of its link; fused degree per atom = the
    # segment sum over its incidence row (all cumsums — no np.repeat, the
    # plan-build lesson of VERDICT r4)
    ar = tgt_off[inc_links + 1] - tgt_off[inc_links]
    pre = np.zeros(e_inc + 1, dtype=np.int64)
    np.cumsum(ar, out=pre[1:])
    fused_deg = pre[inc_off[1 : n1 + 1]] - pre[inc_off[:n1]]
    nchunk = -(-fused_deg // w)  # ceil; 0 for empty rows

    # row space: n1 atom rows + at least one spare all-zero row, tiled
    # into B-row blocks and nb-block segments
    n_blocks = -(-(n1 + 1) // B)
    nb = min(n_blocks, SEG_BLOCKS)
    n_seg = -(-n_blocks // nb)
    n_rows = n_seg * nb * B
    zero_row = n_rows - 1

    row_chunks = np.zeros(n_rows, dtype=np.int64)
    row_chunks[:n1] = nchunk
    row_chunk_starts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(row_chunks, out=row_chunk_starts[1:])
    total_chunks = int(row_chunk_starts[-1])

    # segment tiling: segment s covers rows [s*nb*B, (s+1)*nb*B); its
    # chunk span is the row-chunk-starts slice at those boundaries; cap =
    # the widest segment (uniform shapes keep the per-hop scan traceable)
    rows_per_seg = nb * B
    seg_off = row_chunk_starts[:: rows_per_seg]  # exactly n_seg + 1 entries
    seg_counts = seg_off[1:] - seg_off[:-1]
    cap = max(int(seg_counts.max(initial=0)), 1)

    geom = FusedGeom(
        n_atoms=N, n_rows=n_rows, n_seg=n_seg, nb=nb, cap=cap, w=w,
        zero_row=zero_row, total_entries=int(fused_deg.sum()),
    )
    if _smem_bytes(cap, nb, w) > SMEM_BUDGET // 2:
        # hub rows blow the scalar-prefetch window: decline CHEAPLY,
        # before materializing the O(composition) fused adjacency — on a
        # hub-heavy graph that array can dwarf the CSR itself, and the
        # staged chain is about to serve this snapshot anyway
        empty = np.zeros((0,), dtype=np.int32)
        return FusedPlan(
            geom=geom, blk_off=empty.reshape(0, nb + 1),
            chunk_rows=empty.reshape(0, 0), idx=empty.reshape(0, 0),
            inc_deg=empty,
        )

    # flat level-0 index array, padded per row (pad → zero row)
    idx_flat = np.full(total_chunks * w, zero_row, dtype=np.int32)
    if e_inc:
        # atom id per incidence entry, via boundary marks (O(E) cumsum):
        # atom_of[e] = #{row starts inc_off[1..n1-1] that are <= e}
        marks = np.zeros(e_inc, dtype=np.int64)
        bounds = inc_off[1:n1]
        np.add.at(marks, bounds[bounds < e_inc], 1)
        atom_of = np.cumsum(marks)
        row_pad_starts = row_chunk_starts * w
        dst_start = (
            row_pad_starts[atom_of] + (pre[:e_inc] - pre[inc_off[atom_of]])
        )
        live = np.nonzero(ar)[0]
        if len(live):
            dst = _segmented_ranges(dst_start[live], ar[live])
            src = _segmented_ranges(tgt_off[inc_links[live]], ar[live])
            idx_flat[dst] = tgt_flat[src]

    # chunk → row map (global), via the same boundary-marks trick
    chunk_row_g = np.zeros(max(total_chunks, 1), dtype=np.int64)
    if total_chunks:
        bmarks = np.zeros(total_chunks, dtype=np.int64)
        bounds = row_chunk_starts[1:n_rows]
        np.add.at(bmarks, bounds[bounds < total_chunks], 1)
        chunk_row_g = np.cumsum(bmarks)

    blk_off = np.zeros((n_seg, nb + 1), dtype=np.int32)
    chunk_rows = np.zeros((n_seg, cap), dtype=np.int32)
    idx = np.full((n_seg, cap * w), zero_row, dtype=np.int32)
    for s in range(n_seg):
        c0, c1 = int(seg_off[s]), int(seg_off[s + 1])
        blk_off[s] = (
            row_chunk_starts[s * rows_per_seg : (s + 1) * rows_per_seg + 1 : B]
            - c0
        ).astype(np.int32)
        n_c = c1 - c0
        if n_c:
            chunk_rows[s, :n_c] = (
                chunk_row_g[c0:c1] - s * rows_per_seg
            ).astype(np.int32)
            idx[s, : n_c * w] = idx_flat[c0 * w : c1 * w]

    inc_deg = np.zeros(n_rows, dtype=np.int32)
    inc_deg[:n1] = (inc_off[1 : n1 + 1] - inc_off[:n1]).astype(np.int32)
    inc_deg[N] = 0  # dummy row counts nothing

    return FusedPlan(geom=geom, blk_off=blk_off, chunk_rows=chunk_rows,
                     idx=idx, inc_deg=inc_deg)


def fused_plans_for(snap: CSRSnapshot) -> FusedPlan:
    """Fused plan for a snapshot — memoized on the snapshot object (the
    ``plans_for`` discipline; rebuilt only when the snapshot changes)."""
    plan = getattr(snap, "_fused_plan", None)
    if plan is None:
        plan = build_fused_plan(snap)
        object.__setattr__(snap, "_fused_plan", plan)
    return plan


class DeviceFusedPlan(NamedTuple):
    """Device staging of a :class:`FusedPlan` (a pytree of arrays; the
    static geometry travels separately as a :class:`FusedGeom`)."""

    blk_off: jax.Array
    chunk_rows: jax.Array
    idx: jax.Array
    inc_deg: jax.Array


def device_fused_plan(snap: CSRSnapshot) -> tuple[DeviceFusedPlan, FusedGeom]:
    dev = getattr(snap, "_fused_device", None)
    if dev is None:
        plan = fused_plans_for(snap)
        if plan.blk_off.shape[0] != plan.geom.n_seg:
            # build_fused_plan declined (SMEM window) without
            # materializing the adjacency — callers must gate on
            # plan_supported/fused_ready before staging
            raise ValueError(
                "fused plan declined for this snapshot: "
                + (plan_supported(snap, WORD) or "SMEM window overflow")
            )
        dev = (
            DeviceFusedPlan(
                blk_off=jnp.asarray(plan.blk_off),
                chunk_rows=jnp.asarray(plan.chunk_rows),
                idx=jnp.asarray(plan.idx),
                inc_deg=jnp.asarray(plan.inc_deg),
            ),
            plan.geom,
        )
        object.__setattr__(snap, "_fused_device", dev)
    return dev


# ---------------------------------------------------------------- the kernel


def _hop_kernel(blk_off_ref, chunk_rows_ref, idx_ref, visited_hbm, vis_blk,
                out_ref, rows, sems, *, w, block_rows, d):
    """One grid step = one B-row output block of the new visited bitmap.

    The block's chunk span comes from the scalar-prefetched bounds; each
    chunk is ``w`` single-row async copies into one of ``d`` DMA slots
    (double buffering: chunk c+d streams while chunk c folds), OR-folded
    on the VPU and OR'd into the block-local output row — the old visited
    rows seed the output, so expansion, dedup, and the visited update are
    one pass with nothing intermediate leaving VMEM."""
    b = pl.program_id(0)
    c_lo = blk_off_ref[b]
    c_hi = blk_off_ref[b + 1]
    nc = c_hi - c_lo
    out_ref[...] = vis_blk[...]

    def start(c, slot):
        for j in range(w):
            pltpu.make_async_copy(
                visited_hbm.at[pl.ds(idx_ref[c * w + j], 1), :],
                rows.at[pl.ds(slot * w + j, 1), :],
                sems.at[slot],
            ).start()

    for p in range(d):
        @pl.when(p < nc)
        def _(p=p):
            start(c_lo + p, p)

    def body(i, _):
        slot = jax.lax.rem(i, d)
        pltpu.make_async_copy(
            rows.at[pl.ds(slot * w, w), :],
            rows.at[pl.ds(slot * w, w), :],
            sems.at[slot],
        ).wait()
        base = slot * w
        res = rows[pl.ds(base, 1), :]
        for j in range(1, w):
            res = res | rows[pl.ds(base + j, 1), :]
        r = chunk_rows_ref[c_lo + i] - b * block_rows
        out_ref[pl.ds(r, 1), :] = out_ref[pl.ds(r, 1), :] | res

        @pl.when(i + d < nc)
        def _():
            start(c_lo + i + d, slot)

        return 0

    jax.lax.fori_loop(0, nc, body, 0)


def _hop_call(blk_off_s, chunk_rows_s, idx_s, visited, vis_seg, *,
              nb, w, interpret):
    kwp = visited.shape[1]
    # budget enforced by the callers' _vmem_bytes/_smem_bytes guards
    # (runtime shapes, same discipline as pallas_gather)
    return pl.pallas_call(  # hglint: disable=HG502
        functools.partial(_hop_kernel, w=w, block_rows=B, d=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((B, kwp), lambda i, s0, s1, s2: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((B, kwp), lambda i, s0, s1, s2: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((D * w, kwp), jnp.uint32),
                            pltpu.SemaphoreType.DMA((D,))],
        ),
        out_shape=jax.ShapeDtypeStruct((nb * B, kwp), jnp.uint32),
        interpret=interpret,
    )(blk_off_s, chunk_rows_s, idx_s, visited, vis_seg)


def _hop_fused(visited: jax.Array, plan: DeviceFusedPlan, geom: FusedGeom,
               interpret: bool) -> jax.Array:
    """One full hop: new visited = old | fused-adjacency OR-gather."""
    kwp = visited.shape[1]
    rows_per_seg = geom.nb * B
    if geom.n_seg == 1:
        return _hop_call(
            plan.blk_off[0], plan.chunk_rows[0], plan.idx[0],
            visited, visited, nb=geom.nb, w=geom.w, interpret=interpret,
        )

    def body(_, xs):
        off, cr, ix, s = xs
        vis_seg = jax.lax.dynamic_slice(
            visited, (s * rows_per_seg, 0), (rows_per_seg, kwp)
        )
        return None, _hop_call(off, cr, ix, visited, vis_seg,
                               nb=geom.nb, w=geom.w, interpret=interpret)

    _, outs = jax.lax.scan(
        body, None,
        (plan.blk_off, plan.chunk_rows, plan.idx,
         jnp.arange(geom.n_seg, dtype=jnp.int32)),
    )
    return outs.reshape(geom.n_rows, kwp)


# ------------------------------------------------------------- delta overlay


class OverlayArrays(NamedTuple):
    """Device half of a :class:`DeltaOverlayPlan` (pytree of arrays)."""

    levels1: tuple     # stage-1 index pyramid (delta links ← visited rows)
    levels2: tuple     # stage-2 pyramid, level 0 composed into stage-1 space
    out_map: jax.Array  # (A,) int32 — stage-2 concat chunk per delta row
    rows: jax.Array     # (A,) int32 — UNIQUE atom rows gaining delta edges


@dataclass(frozen=True)
class DeltaOverlayPlan:
    """Host plan for the delta COO's pull contribution: the miniature twin
    of ``ellbfs.build_pull_plans`` over ONLY the delta edges, with output
    restricted to the atoms that actually gained incidence — so applying
    the overlay costs O(delta), not O(graph). Built once per device-delta
    refresh (cached on the delta object) from the delta's own padded
    arrays, so it describes exactly what the unfused kernel sees."""

    arrays: OverlayArrays
    widths1: tuple
    widths2: tuple


def overlay_plan_for(delta, n_atoms: int,
                     geom: FusedGeom) -> Optional[DeltaOverlayPlan]:
    """Overlay plan for a DeviceDelta (None = delta carries no edges).
    Raises nothing: any structural surprise simply returns None and the
    caller falls back to the unfused chain."""
    cached = getattr(delta, "_overlay_plan", None)
    if cached is not None:
        plan, key = cached
        if key == (n_atoms, geom.zero_row):
            return plan
    plan = _build_overlay(delta, n_atoms, geom)
    try:
        delta._overlay_plan = (plan, (n_atoms, geom.zero_row))
    except Exception:  # pragma: no cover  # hglint: disable=HG1005
        pass  # frozen delta variants reject the cache slot; rebuilt per call
    return plan


def _build_overlay(delta, n_atoms: int,
                   geom: FusedGeom) -> Optional[DeltaOverlayPlan]:
    tgt_src = np.asarray(delta.tgt_src)
    tgt_flat = np.asarray(delta.tgt_flat)
    inc_links = np.asarray(delta.inc_links)
    inc_src = np.asarray(delta.inc_src)
    real_t = tgt_src != n_atoms       # pad fill is the dummy row id
    real_i = inc_links != n_atoms
    if not real_t.any() or not real_i.any():
        return None

    # stage 1: delta links' target lists as a compact CSR
    ts, tf = tgt_src[real_t], tgt_flat[real_t]
    order = np.argsort(ts, kind="stable")
    ts, tf = ts[order], tf[order]
    links_u, l_counts = np.unique(ts, return_counts=True)
    n_links = len(links_u)
    l_off = np.zeros(n_links + 1, dtype=np.int64)
    np.cumsum(l_counts, out=l_off[1:])
    s1 = build_reduce_plan(l_off, tf, n_links, zero_row=geom.zero_row)

    # stage 2: delta incidence grouped by atom, level 0 composed through
    # stage-1's out_map (the build_pull_plans composition)
    isrc, il = inc_src[real_i], inc_links[real_i]
    order = np.argsort(isrc, kind="stable")
    isrc, il = isrc[order], il[order]
    lpos = np.searchsorted(links_u, il)
    # a delta incidence whose link has no target entries contributes
    # nothing — point it at the stage-1 zero chunk
    bad = (lpos >= n_links) | (links_u[np.minimum(lpos, n_links - 1)] != il)
    lpos = np.where(bad, n_links, lpos)
    atoms_u, a_counts = np.unique(isrc, return_counts=True)
    n_a = len(atoms_u)
    a_off = np.zeros(n_a + 1, dtype=np.int64)
    np.cumsum(a_counts, out=a_off[1:])
    s2 = build_reduce_plan(a_off, lpos, n_a, zero_row=n_links)
    out_map_ext = np.concatenate(
        [s1.out_map, np.asarray([s1.concat_size], dtype=np.int32)]
    )
    lvl0 = out_map_ext[s2.levels[0]]

    arrays = OverlayArrays(
        levels1=tuple(jnp.asarray(l) for l in s1.levels),
        levels2=tuple(jnp.asarray(l)
                      for l in (lvl0,) + s2.levels[1:]),
        out_map=jnp.asarray(s2.out_map),
        rows=jnp.asarray(atoms_u.astype(np.int32)),
    )
    return DeltaOverlayPlan(arrays=arrays, widths1=s1.widths,
                            widths2=s2.widths)


def _overlay_reach(visited: jax.Array, ov: OverlayArrays,
                   widths1: tuple, widths2: tuple) -> jax.Array:
    """The delta edges' pull contribution for ``ov.rows``: (A, Kwp)."""
    buf1 = _apply_plan(visited, ov.levels1, widths1, CHUNK, False)
    buf2 = _apply_plan(buf1, ov.levels2, widths2, CHUNK, False)
    return buf2[ov.out_map]


# --------------------------------------------------------------- fused BFS


def _seed_rows(seeds: jax.Array, n_rows: int, kwp: int) -> jax.Array:
    """Transposed seed bitmap over the fused row space — the
    ``ellbfs._seed_bitmap`` construction at ``kwp`` lane width, WITHOUT
    clearing the dummy row (serve parity keeps pad-lane seed bits; pull
    callers clear it explicitly)."""
    K = seeds.shape[0]
    k = jnp.arange(K, dtype=jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), (k & 31).astype(jnp.uint32))
    onehot = jnp.zeros((K, kwp), dtype=jnp.uint32).at[k, k >> 5].set(bit)
    return jnp.zeros((n_rows, kwp), dtype=jnp.uint32).at[seeds].add(onehot)


#: the ONE toy fused instance both this module's and ``ops/serving``'s
#: ``@hgverify.entry`` exemplars trace — a plan-layout change edits it
#: here and both harvests follow (no copy-paste drift)
EXEMPLAR_GEOM = FusedGeom(n_atoms=14, n_rows=16, n_seg=1, nb=2, cap=4,
                          w=8, zero_row=15, total_entries=20)


def exemplar_shapes() -> tuple:
    """``(DeviceFusedPlan, seeds, n_atoms)`` avals matching
    :data:`EXEMPLAR_GEOM` — the shared hgverify exemplar builder."""
    return (
        DeviceFusedPlan(
            blk_off=hgverify.sds((1, 3), "int32"),
            chunk_rows=hgverify.sds((1, 4), "int32"),
            idx=hgverify.sds((1, 32), "int32"),
            inc_deg=hgverify.sds((16,), "int32"),
        ),
        hgverify.sds((32,), "int32"),
        hgverify.sds((), "int32"),
    )


@hgverify.entry(
    shapes=exemplar_shapes,
    statics={
        "geom": EXEMPLAR_GEOM,
        "kwp": 128, "max_hops": 2, "count_edges": True,
        "clear_dummy": True, "widths1": None, "widths2": None,
        "interpret": True,
    },
)
@partial(jax.jit, static_argnames=(
    "geom", "kwp", "max_hops", "count_edges", "clear_dummy",
    "widths1", "widths2", "interpret",
))
def _bfs_fused(
    plan: DeviceFusedPlan,
    seeds: jax.Array,          # (K,) int32 — K % 32 == 0, K <= kwp * 32
    n_atoms: jax.Array,        # scalar int32 — dummy row id
    geom: FusedGeom,
    kwp: int,
    max_hops: int,
    count_edges: bool,
    clear_dummy: bool,
    overlay: Optional[OverlayArrays] = None,
    widths1: Optional[tuple] = None,
    widths2: Optional[tuple] = None,
    interpret: bool = False,
) -> tuple[jax.Array, tuple, jax.Array]:
    """The whole fused BFS in ONE dispatch: seed bitmap → ``max_hops``
    fused hops (+ optional delta overlay per hop) → per-hop degree sums →
    reach counts. Returns ``(visited (n_rows, kwp) uint32, s_per_hop
    tuple, reach (kwp*32,) int32)``. Bit-identical to the unfused
    ``ellbfs`` chain on the same inputs."""
    visited = _seed_rows(seeds, geom.n_rows, kwp)
    if clear_dummy:
        visited = visited.at[n_atoms].set(jnp.uint32(0))
    s_ins = []
    for _ in range(max_hops):
        if count_edges:
            # the barrier makes the hop wait for this hop's degree sum:
            # left free, the TPU scheduler sinks every bit-dot to the end
            # and keeps each hop's 5 GB bitmap alive for it (26.7 GB of
            # temps at 10M rows x 3 hops, against 6.3 GB with it —
            # tests/test_tpu_compile.py)
            visited, s_in = jax.lax.optimization_barrier(
                (visited, _bitdot(visited, plan.inc_deg))
            )
            s_ins.append(s_in)
        if overlay is not None:
            ov = _overlay_reach(visited, overlay, widths1, widths2)
        visited = _hop_fused(visited, plan, geom, interpret)
        if overlay is not None:
            visited = visited.at[overlay.rows].set(
                visited[overlay.rows] | ov
            )
    reach = _bitdot(visited, jnp.ones((geom.n_rows,), jnp.int32))
    return visited, tuple(s_ins), reach


def bfs_pull_fused(
    snap: CSRSnapshot,
    seeds: np.ndarray,
    max_hops: int,
    count_edges: bool = True,
    interpret: bool = False,
):
    """Fused-path twin of one ``ellbfs._bfs_pull_device`` block: returns
    ``(visited_t (n_pad, Kw) uint32, s_ins list, reach (K,) int32)`` with
    the exact ``bfs_pull`` per-block contract (pad seeds = dummy row,
    dummy row cleared). ``Kw`` is the caller's K/32; lanes pad to
    :data:`KWP_MIN` internally and slice off on exit."""
    plan, geom = device_fused_plan(snap)
    seeds = jnp.asarray(np.asarray(seeds, dtype=np.int32))
    K = seeds.shape[0]
    kw = K // WORD
    kwp = max(_ceil_to(kw, KWP_MIN), KWP_MIN)
    visited, s_ins, reach = _bfs_fused(
        plan, seeds, jnp.int32(geom.n_atoms), geom, kwp, max_hops,
        count_edges, True, interpret=interpret,
    )
    n_pad = _ceil_to(geom.n_atoms + 1, 8)
    visited_t = visited[:n_pad, :kw]
    return visited_t, [s[:K] for s in s_ins], reach[:K]


def serve_fused_kwargs(base_snap: CSRSnapshot, delta,
                       k_bucket: int) -> Optional[dict]:
    """The ``ops/serving.bfs_serve_batch_fused`` argument bundle for one
    pinned (base, delta) pair, or None when the fused path must decline
    (budget overflow, or a delta whose overlay cannot be planned). Does
    NOT check tombstones or the backend probe — the executor owns those
    gates (it sees the pinned view's dead set and the runtime config)."""
    if plan_supported(base_snap, k_bucket) is not None:
        return None
    plan, geom = device_fused_plan(base_snap)
    kwp = max(_ceil_to(max(k_bucket, WORD) // WORD, KWP_MIN), KWP_MIN)
    out = {
        "fused": plan,
        "n_atoms": jnp.int32(geom.n_atoms),
        "geom": geom,
        "kwp": kwp,
        "overlay": None,
        "widths1": None,
        "widths2": None,
    }
    if delta is not None:
        ov = overlay_plan_for(delta, base_snap.num_atoms, geom)
        if ov is not None:
            out.update(overlay=ov.arrays, widths1=ov.widths1,
                       widths2=ov.widths2)
    return out


def first_r_from_bitmap(visited: jax.Array, n1: jax.Array,
                        top_r: int, K: int) -> jax.Array:
    """The serving compaction (``ops/serving.bfs_serve_batch`` contract)
    read straight off the transposed bitmap: per seed the ``top_r``
    smallest reached atom ids ascending, SENTINEL-padded — streamed in
    row blocks with a per-block top-k + merge so the (rows, K) unpack
    transient stays bounded instead of materializing whole."""
    from hypergraphdb_tpu.ops.setops import SENTINEL

    R, kwp = visited.shape
    rb = min(4096, R)
    n_blocks = -(-R // rb)
    cols = jnp.arange(K, dtype=jnp.int32)
    word = cols >> 5
    bit = (cols & 31).astype(jnp.uint32)
    init = jnp.full((K, top_r), SENTINEL, jnp.int32)
    # a block holds at most rb candidate rows — clamp the per-block top_k
    # so top_r > rb (the dense path serves it fine) cannot over-ask the
    # rb-wide lane at trace time; the merge below still yields top_r
    blk_r = min(top_r, rb)

    def body(i, cur):
        start = jnp.minimum(i * rb, R - rb)
        blk = jax.lax.dynamic_slice(visited, (start, 0), (rb, kwp))
        ids = start + jnp.arange(rb, dtype=jnp.int32)
        # the last block's clamped start overlaps the previous block; the
        # fresh mask zeroes already-counted rows (the _bitdot discipline)
        fresh = ids >= i * rb
        hit = ((blk[:, word] >> bit[None, :]) & 1).astype(bool)
        valid = fresh & (ids < n1)
        masked = jnp.where(hit & valid[:, None], ids[:, None], SENTINEL)
        blk_top = -jax.lax.top_k(-masked.T, blk_r)[0]
        merged = jnp.sort(
            jnp.concatenate([cur, blk_top], axis=1), axis=1
        )
        return merged[:, :top_r]

    return jax.lax.fori_loop(0, n_blocks, body, init)


# ----------------------------------------------------------------- gating


#: backends whose probe has passed (a failed probe raises, so it is never
#: recorded: the next caller sees the same error, not a quiet fallback)
_PROBED: set = set()


def pallas_bfs_ok() -> bool:
    """Does the fused hop kernel serve on the default backend? Decided
    from the PLATFORM: off anywhere but a TPU (CPU tier-1 runs the same
    entry points through the unfused chain), vetoed by ``HG_PALLAS_BFS=0``.
    On a TPU the kernel is probed once with a tiny instance, and a probe
    that Mosaic refuses or that answers wrong RAISES — a chip that cannot
    run its own kernel is a fault to surface, never a reason to serve the
    XLA chain without saying so."""
    if os.environ.get("HG_PALLAS_BFS", "1") in ("0", "false", "no"):
        return False
    backend = jax.default_backend()
    if backend != "tpu":
        return False
    if backend not in _PROBED:
        _probe()
        _PROBED.add(backend)
    return True


def _probe() -> None:
    """A 2-block, 1-segment instance with a known OR pattern; raises what
    the compiler raises, or ``RuntimeError`` on a wrong answer."""
    kwp = KWP_MIN
    n_rows = 2 * B
    visited = jnp.zeros((n_rows, kwp), jnp.uint32).at[0, 0].set(
        jnp.uint32(1)
    )
    # one chunk: row 1 pulls row 0 (w copies of it)
    blk_off = jnp.asarray([[0, 1, 1]], jnp.int32)
    chunk_rows = jnp.asarray([[1]], jnp.int32)
    idx = jnp.zeros((1, W), jnp.int32)
    out = _hop_call(blk_off[0], chunk_rows[0], idx[0], visited, visited,
                    nb=2, w=W, interpret=False)
    res = np.asarray(out)
    if not (res[1, 0] == 1 and res[0, 0] == 1 and res[2:].sum() == 0):
        raise RuntimeError(
            "pallas_bfs probe: the fused hop kernel compiled but answered "
            f"wrong on {jax.devices()[0].device_kind}"
        )


def fused_ready(snap: CSRSnapshot, k_block: int) -> bool:
    """Should ``bfs_pull`` route this seed block through the fused path?
    Requires the backend probe, ``k_block`` a WORD multiple, and the
    snapshot's plan inside the SMEM/VMEM windows."""
    if k_block % WORD or not pallas_bfs_ok():
        return False
    return plan_supported(snap, k_block) is None


def plan_supported(snap: CSRSnapshot, k_block: int) -> Optional[str]:
    """None when the fused plan fits the budget model for this block
    width; otherwise the human-readable reason it must fall back."""
    kwp = max(_ceil_to(max(k_block, WORD) // WORD, KWP_MIN), KWP_MIN)
    if kwp != KWP_MIN:
        # cheap declines before the O(E) plan build
        return (f"visited rows of {kwp} words ({k_block} seeds): Mosaic "
                f"accepts the kernel's single-row DMA only at {KWP_MIN}-"
                f"word rows — wider is refused with 'Slice shape along "
                f"dimension 0 must be aligned to tiling (8), but is 1' "
                f"(v5e, tests/test_tpu_compile.py); block seeds at "
                f"{KWP_MIN * WORD} or fewer")
    if _vmem_bytes(kwp) > VMEM_BUDGET:
        # snapshot plans are always built at the default chunk width W
        return (f"VMEM working set {_vmem_bytes(kwp)} B exceeds the "
                f"{VMEM_BUDGET} B budget at kwp={kwp}")
    plan = fused_plans_for(snap)
    g = plan.geom
    if _vmem_bytes(kwp, g.w) > VMEM_BUDGET:
        return (f"VMEM working set {_vmem_bytes(kwp, g.w)} B exceeds the "
                f"{VMEM_BUDGET} B budget at kwp={kwp}, w={g.w}")
    if not plan.smem_ok:
        return (f"scalar-prefetch segment "
                f"{_smem_bytes(g.cap, g.nb, g.w)} B "
                f"exceeds half the {SMEM_BUDGET} B SMEM budget "
                f"(cap={g.cap}) — hub rows too wide to prefetch")
    return None


def fused_bytes_per_hop(geom: FusedGeom, K: int) -> int:
    """HBM traffic model of one fused hop, the honest-counting twin of
    ``bench.pull_bytes_per_run``: one Kwp-word row DMA per fused chunk
    entry, the scalar plan reads, and one read+write of the (n_rows, kwp)
    visited state; no stage buffers, no out_map re-gather."""
    kwp = max(_ceil_to(max(K, WORD) // WORD, KWP_MIN), KWP_MIN)
    row_bytes = kwp * 4
    n_chunks = -(-geom.total_entries // geom.w)
    per_hop = geom.total_entries * row_bytes        # gathered rows
    per_hop += n_chunks * (geom.w + 1) * 4          # idx + chunk_rows reads
    per_hop += geom.n_rows * row_bytes * 2          # visited read + write
    return per_hop
