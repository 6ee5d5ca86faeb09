"""Batched serving entry points: fixed-shape micro-batch kernels.

The serving runtime (``hypergraphdb_tpu/serve``) coalesces independent
caller requests into shape-bucketed device batches. These are the two
kernels it dispatches — both return **compact** per-request results
(counts + the first ``top_r`` matches) so the host link carries
O(K · top_r) per batch instead of O(K · N):

- :func:`bfs_serve_batch` — K-seed BFS over the incremental
  (base ∪ delta) pair (``ops/incremental.bfs_levels_delta`` semantics),
  compacted on device to per-seed reach counts + the ``top_r`` smallest
  reached atom ids.
- :func:`pattern_serve_batch` — K conjunctive incident patterns
  (``And(Incident(a), Incident(b), ..., [AtomType])``) via the hub-proof
  ELL intersection (``ops/setops.incident_intersection_ell``), with a
  PER-REQUEST type filter (``type_vec`` lane < 0 = no type constraint) so
  one compiled program serves typed and untyped queries in the same
  micro-batch — a scalar ``type_handle`` would force one batch group per
  type and starve coalescing.

Both kernels tolerate padding lanes natively: pad BFS seeds with the
dummy row id (``dev.num_atoms`` — reaches nothing), pad pattern anchors
with the dummy row (empty incidence — zero candidates). Pad-lane outputs
are well-defined garbage the runtime discards by lane index.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from hypergraphdb_tpu import verify as hgverify
from hypergraphdb_tpu.ops.incremental import DeviceDelta, bfs_levels_delta
from hypergraphdb_tpu.ops.setops import SENTINEL, incident_intersection_ell
from hypergraphdb_tpu.ops.snapshot import DeviceSnapshot

#: ``type_vec`` lane value meaning "no type constraint for this request"
NO_TYPE = -1


@hgverify.entry(
    shapes=lambda: (hgverify.dev_snapshot_exemplar(),
                    hgverify.device_delta_exemplar(),
                    hgverify.sds((8,), "int32")),
    statics={"max_hops": 2, "top_r": 4},
)
@partial(jax.jit, static_argnames=("max_hops", "top_r"))
def bfs_serve_batch(
    dev: DeviceSnapshot,
    delta: DeviceDelta,
    seeds: jax.Array,   # (K,) int32 — pad lanes carry dev.num_atoms
    max_hops: int,
    top_r: int,
) -> tuple[jax.Array, jax.Array]:
    """K-seed BFS over base ∪ delta with on-device result compaction.

    Returns ``(counts (K,) int32, first_r (K, top_r) int32)``: per-seed
    |visited| (INCLUDING the live seed — ``ops/ellbfs`` reach-count
    convention) and the ``top_r`` smallest reached atom ids in ascending
    order, SENTINEL-padded past the count. A request whose full result set
    exceeds ``top_r`` is flagged truncated by the runtime
    (``counts > top_r``)."""
    _, visited = bfs_levels_delta(
        dev, delta, seeds, max_hops, with_levels=False
    )
    counts = visited.sum(axis=1).astype(jnp.int32)
    return counts, first_r_dense(visited, top_r)


#: columns per step of :func:`first_r_dense`'s sweep: bounds the int32
#: transient at K·FIRST_R_BLOCK·4 bytes and keeps each ``top_k`` narrow
FIRST_R_BLOCK = 8192


def first_r_dense(mask: jax.Array, top_r: int, base=0) -> jax.Array:
    """Per row of a dense ``(K, n)`` bool mask, the ``top_r`` SMALLEST set
    column ids (``base +`` column), ascending, SENTINEL-padded — the
    serving compaction, swept in :data:`FIRST_R_BLOCK`-column steps with
    a per-step ``top_k`` + merge.

    One ``top_k`` over the whole row is what this replaces: past ~270K
    columns the v5e compiler refuses it (``TopKBatchMajorSmallK`` runs
    out of scoped VMEM — kept as a test in ``tests/test_tpu_compile.py``),
    and its ``(K, n)`` int32 operand is 4x the mask it ranks."""
    K, n = mask.shape
    rb = min(FIRST_R_BLOCK, n)
    blk_r = min(top_r, rb)

    def block_top(i):
        # the last block's clamped start overlaps the previous one; the
        # fresh mask drops the columns already swept
        start = jnp.minimum(i * rb, n - rb)
        blk = jax.lax.dynamic_slice(mask, (0, start), (K, rb))
        col = start + jnp.arange(rb, dtype=jnp.int32)
        hit = blk & (col >= i * rb)[None, :]
        ranked = jnp.where(hit, (base + col)[None, :], SENTINEL)
        # top_k of the negation = the SMALLEST ids; re-negating flips the
        # descending sort back to ascending
        return -jax.lax.top_k(-ranked, blk_r)[0]

    # block 0 seeds the carry (so it carries the mask's own sharding type
    # when this runs inside a shard_map region)
    first = block_top(0)
    if blk_r < top_r:
        first = jnp.concatenate(
            [first, jnp.full_like(first[:, :1], SENTINEL).repeat(
                top_r - blk_r, axis=1)], axis=1)

    def body(i, cur):
        merged = jnp.concatenate([cur, block_top(i)], axis=1)
        return -jax.lax.top_k(-merged, top_r)[0]

    return jax.lax.fori_loop(1, -(-n // rb), body, first)


@hgverify.entry(
    shapes=lambda: (hgverify.dev_snapshot_exemplar(),
                    hgverify.sds((32, 4), "int32"),
                    hgverify.sds((4, 2), "int32"),
                    hgverify.sds((4,), "int32")),
    statics={"pad_len": 8, "top_r": 4},
)
@partial(jax.jit, static_argnames=("pad_len", "top_r"))
def pattern_serve_batch(
    dev: DeviceSnapshot,
    tgt_ell: jax.Array,   # (N+1, W) int32 ELL targets (ops/setops.ell_targets)
    anchors: jax.Array,   # (K, P) int32 — anchors[:, 0] has the SMALLEST row
    type_vec: jax.Array,  # (K,) int32 — per-request type handle, NO_TYPE = any
    pad_len: int,
    top_r: int,
) -> tuple[jax.Array, jax.Array]:
    """K conjunctive incident patterns with per-request type filters.

    Returns ``(counts (K,) int32, first_r (K, top_r) int32)``: per-query
    survivor count and the first ``top_r`` matching link ids ascending,
    SENTINEL-padded. Links live in the BASE snapshot only — the serving
    runtime merges the delta memtable host-side (the LSM read-correction of
    ``query/compiler.DeviceValueConjPlan``)."""
    rows0, mask = incident_intersection_ell(
        dev, tgt_ell, anchors, pad_len, None
    )
    safe = jnp.where(rows0 == SENTINEL, 0, rows0)
    want = type_vec[:, None]
    mask = mask & ((want < 0) | (dev.type_of[safe] == want))
    counts = mask.sum(axis=1).astype(jnp.int32)
    ranked = jnp.where(mask, rows0, SENTINEL)
    first_r = jax.lax.sort(ranked, dimension=1)[:, :top_r]
    return counts, first_r
