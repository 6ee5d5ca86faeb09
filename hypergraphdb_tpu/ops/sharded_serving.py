"""Multi-chip serving kernels: the mesh-sharded twins of ``ops/serving``.

The serving runtime's batched kernels (``bfs_serve_batch`` /
``pattern_serve_batch`` / the ``ops/join`` lane executor) each run on ONE
chip; these route the same micro-batch contracts through ``shard_map``
programs over the device mesh (``parallel.sharded.AXIS``), so a serve
bucket's work spreads across every chip of a pod and the pinned snapshot
no longer has to fit one chip's HBM:

- :func:`bfs_serve_batch_sharded` — K-seed BFS over the ROW-SHARDED
  (base ∪ delta) pair (``parallel.sharded.bfs_packed_sharded_delta``:
  per hop, two all-gathers of packed frontier words cross ICI), with the
  result compaction ALSO on the mesh: each device counts + top-``r``'s
  its own row range, counts ``psum`` up, and the per-device candidate
  windows gather (``psum`` of slot-placed windows) + merge into the global ``top_r`` smallest ids
  — O(K · n_dev · top_r) ints on ICI however large the graph.
- :func:`pattern_serve_batch_sharded` — K conjunctive incident patterns,
  CANDIDATE-sharded: the smallest anchor's incidence row (host-gathered
  per lane, its target tuples and type labels riding along) splits
  across devices along the candidate axis; each device membership-tests
  its slice against every other anchor in O(L_loc · P · W) contiguous
  work, then the same psum + all-gather-merge compaction. No
  device-resident ELL matrix at all — the only per-batch device state is
  O(K · pad · W).
- :func:`execute_join_sharded` — the PR-10 worst-case-optimal join lane
  executor, LANE-sharded: one ``shard_map`` program runs the whole
  multiway-intersection step chain on each device for its K/n_dev lanes
  of the batch (relations replicated — sharding the relations themselves
  is the ROADMAP follow-up), counts/truncation/tuple windows reassembled
  along the lane axis.

All three keep the single-chip kernels' result contracts bit-for-bit
(compact ``(counts, first_r)`` / ``JoinExecution``), so the serving
runtime's collect path — including the host-side LSM memtable
corrections, which stay exactly as they are — needs no sharded variant.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from hypergraphdb_tpu import verify as hgverify
from hypergraphdb_tpu.ops.bitfrontier import unpack_bits
from hypergraphdb_tpu.ops.serving import first_r_dense
from hypergraphdb_tpu.ops.setops import ELL_MAX_WIDTH, SENTINEL, _bucket
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot
from hypergraphdb_tpu.parallel.sharded import (
    AXIS,
    ShardedDelta,
    ShardedSnapshot,
    bfs_packed_sharded_delta,
)


#: one carrier per DISTINCT mesh topology — keyed by (axis names,
#: device ids), NOT id(mesh): recycled runtimes mint a fresh Mesh object
#: per executor, and an identity key would pin every dead mesh (plus its
#: device-resident carrier arrays) for the life of the process
_CARRIERS: dict = {}


def mesh_carrier(mesh) -> ShardedSnapshot:
    """A MINIMAL ShardedSnapshot whose only job is carrying ``mesh``
    into kernels that need no row-sharded state (the pattern lanes: all
    real operands are host-assembled per batch). Constant shapes, so
    prewarm and dispatch share one compiled program and one AOT key —
    and a pattern-only pod never pays the O(E) sharded-base build."""
    key = (tuple(mesh.axis_names),
           tuple(int(d.id) for d in mesh.devices.flat))
    hit = _CARRIERS.get(key)
    if hit is not None:
        return hit
    n_dev = int(mesh.devices.size)
    n_loc = 128
    n_pad = n_dev * n_loc
    from jax.sharding import NamedSharding

    shard = NamedSharding(mesh, P(AXIS))

    def put(a):
        return jax.device_put(jnp.asarray(a), shard)

    carrier = ShardedSnapshot(
        mesh=mesh, num_atoms=n_pad - 1, n_loc=n_loc, edge_chunk=8,
        inc_src=put(np.zeros(n_dev * 8, np.int32)),
        inc_dst=put(np.zeros(n_dev * 8, np.int32)),
        tgt_src=put(np.zeros(n_dev * 8, np.int32)),
        tgt_dst=put(np.zeros(n_dev * 8, np.int32)),
        type_of=put(np.zeros(n_pad, np.int32)),
        is_link=put(np.zeros(n_pad, bool)),
        arity=put(np.zeros(n_pad, np.int32)),
        value_rank_hi=put(np.zeros(n_pad, np.uint32)),
        value_rank_lo=put(np.zeros(n_pad, np.uint32)),
    )
    _CARRIERS[key] = carrier
    return carrier


def _merge_first_r(local_first: jax.Array, top_r: int) -> jax.Array:
    """Gather each device's ascending candidate window and merge to the
    global ``top_r`` smallest (SENTINEL-padded): the one collective the
    compaction epilogues share. Runs INSIDE a shard_map region.

    The gather is a ``psum`` of each device's window placed at its own
    slot of a zero buffer, not ``lax.all_gather``: shard_map's replication
    check types ``all_gather`` as device-varying, which ``out_specs=P()``
    rejects, while ``psum`` is typed replicated — and every slot has one
    writer, so the sum IS the gathered value."""
    k_rows, k_loc = local_first.shape
    n_dev = jax.lax.axis_size(AXIS)
    slot = jax.lax.axis_index(AXIS).astype(jnp.int32) * k_loc
    cand = jax.lax.psum(
        jax.lax.dynamic_update_slice(
            jnp.zeros((k_rows, n_dev * k_loc), local_first.dtype),
            local_first, (jnp.int32(0), slot),
        ),
        AXIS,
    )
    short = top_r - cand.shape[1]
    if short > 0:  # tiny graphs: fewer candidate slots than top_r
        cand = jnp.concatenate(
            [cand, jnp.full((k_rows, short), SENTINEL, cand.dtype)],
            axis=1,
        )
    # top_k of the negation = the top_r SMALLEST; re-negating restores
    # ascending order (the ops/serving compaction idiom)
    return -jax.lax.top_k(-cand, top_r)[0]


@hgverify.entry(
    shapes=lambda: (hgverify.sharded_snapshot_exemplar(),
                    hgverify.sharded_delta_exemplar(),
                    hgverify.sds((8,), "int32")),
    statics={"max_hops": 2, "top_r": 4},
    mesh=(AXIS,),
)
@partial(jax.jit, static_argnames=("max_hops", "top_r"))
def bfs_serve_batch_sharded(
    sdev: ShardedSnapshot,
    sdelta: ShardedDelta,
    seeds: jax.Array,   # (K,) int32 — pad lanes carry sdev.num_atoms
    max_hops: int,
    top_r: int,
) -> tuple[jax.Array, jax.Array]:
    """The mesh twin of ``ops.serving.bfs_serve_batch``: same
    ``(counts (K,) int32, first_r (K, top_r) int32)`` contract, computed
    from the row-sharded packed BFS. Pad lanes (dummy-row seeds) reach
    nothing — the dummy row is outside every device's live mask."""
    visited_p, _, _ = bfs_packed_sharded_delta(
        sdev, sdelta, seeds, max_hops, with_levels=False
    )
    n_loc = sdev.n_loc
    k_loc = min(top_r, n_loc)

    def compact(vis_loc):
        # vis_loc (K, n_loc/WORD): this device's row range of the packed
        # visited bitmaps (live-masked by the BFS program)
        row_start = jax.lax.axis_index(AXIS).astype(jnp.int32) * n_loc
        bits = unpack_bits(vis_loc)                       # (K, n_loc)
        counts = jax.lax.psum(
            bits.sum(axis=1).astype(jnp.int32), AXIS
        )
        local_first = first_r_dense(bits, k_loc, base=row_start)
        return counts, _merge_first_r(local_first, top_r)

    fn = jax.shard_map(
        compact, mesh=sdev.mesh,
        in_specs=(P(None, AXIS),), out_specs=(P(), P()),
    )
    return fn(visited_p)


# --------------------------------------------------------------------------
# candidate-sharded conjunctive patterns
# --------------------------------------------------------------------------


def pattern_host_rows(
    snap: CSRSnapshot,
    anchors: np.ndarray,   # (K, P) int64/int32 — [:, 0] has the SMALLEST row
    pad_len: int,
    n_dev: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side batch assembly for :func:`pattern_serve_batch_sharded`:
    per lane, the smallest anchor's incidence row (the candidate set),
    each candidate's type label, and each candidate's target tuple —
    gathered from the CSR's HOST arrays, so no (N+1, W) ELL matrix ever
    occupies device memory. The candidate axis is rounded up to a
    multiple of ``n_dev`` (the shard_map split). Returns
    ``(rows0 (K, L) int32 SENTINEL-padded, row0_types (K, L) int32,
    tgt_tuples (K, L, W) int32 -1-padded)``."""
    anchors = np.asarray(anchors, dtype=np.int64)
    K = anchors.shape[0]
    N = snap.num_atoms
    L = max(int(pad_len), 1)
    L = -(-L // n_dev) * n_dev
    a0 = np.clip(anchors[:, 0], 0, N)
    off = snap.inc_offsets
    starts = off[a0].astype(np.int64)
    lens = off[a0 + 1].astype(np.int64) - starts
    lane = np.arange(L, dtype=np.int64)
    have = lane[None, :] < np.minimum(lens, L)[:, None]
    idx = np.minimum(starts[:, None] + lane[None, :],
                     max(len(snap.inc_links) - 1, 0))
    rows0 = np.where(have, snap.inc_links[idx] if len(snap.inc_links)
                     else 0, SENTINEL).astype(np.int32)
    safe = np.where(have, rows0, N).astype(np.int64)  # dummy row: empty
    row0_types = np.where(have, snap.type_of[safe], -1).astype(np.int32)
    W = _bucket(max(int(snap.arity[: N + 1].max(initial=0)), 1), minimum=2)
    tstart = snap.tgt_offsets[safe].astype(np.int64)          # (K, L)
    tlen = snap.tgt_offsets[safe + 1].astype(np.int64) - tstart
    wlane = np.arange(W, dtype=np.int64)
    tvalid = wlane[None, None, :] < tlen[:, :, None]
    tidx = np.minimum(tstart[:, :, None] + wlane[None, None, :],
                      max(len(snap.tgt_flat) - 1, 0))
    tgt = np.where(tvalid, snap.tgt_flat[tidx] if len(snap.tgt_flat)
                   else 0, -1).astype(np.int32)
    return rows0, row0_types, tgt


@hgverify.entry(
    shapes=lambda: (hgverify.sharded_snapshot_exemplar(),
                    hgverify.sds((8, 16), "int32"),
                    hgverify.sds((8, 16), "int32"),
                    hgverify.sds((8, 16, 4), "int32"),
                    hgverify.sds((8, 2), "int32"),
                    hgverify.sds((8,), "int32")),
    statics={"top_r": 4},
    mesh=(AXIS,),
)
@partial(jax.jit, static_argnames=("top_r",))
def pattern_serve_batch_sharded(
    sdev: ShardedSnapshot,    # mesh carrier; its arrays are unused (DCE'd)
    rows0: jax.Array,         # (K, L) int32 — candidate link ids, SENTINEL pad
    row0_types: jax.Array,    # (K, L) int32 — candidates' type handles
    tgt_tuples: jax.Array,    # (K, L, W) int32 — candidates' target tuples
    anchors: jax.Array,       # (K, P) int32 — [:, 0] is the candidate row
    type_vec: jax.Array,      # (K,) int32 — per-request type, < 0 = any
    top_r: int,
) -> tuple[jax.Array, jax.Array]:
    """The mesh twin of ``ops.serving.pattern_serve_batch``: candidates
    split across devices along L; each device target-tuple-membership
    tests its slice against anchors 1..P-1 and type-filters with the
    labels that rode along — then counts ``psum`` and the per-device
    ``top_r`` windows all-gather-merge. ``L`` must be a multiple of the
    mesh size (``pattern_host_rows`` rounds it)."""
    L = rows0.shape[1]
    k_loc = min(top_r, max(L // int(sdev.mesh.devices.size), 1))

    def local(r0, rt, tg, anc, tv):
        mask = r0 != SENTINEL
        for p in range(1, anc.shape[1]):
            mask = mask & jnp.any(tg == anc[:, p, None, None], axis=-1)
        mask = mask & ((tv[:, None] < 0) | (rt == tv[:, None]))
        counts = jax.lax.psum(mask.sum(axis=1).astype(jnp.int32), AXIS)
        ranked = jnp.where(mask, r0, SENTINEL)
        local_first = -jax.lax.top_k(-ranked, k_loc)[0]
        return counts, _merge_first_r(local_first, top_r)

    fn = jax.shard_map(
        local, mesh=sdev.mesh,
        in_specs=(P(None, AXIS), P(None, AXIS), P(None, AXIS, None),
                  P(), P()),
        out_specs=(P(), P()),
    )
    return fn(rows0, row0_types, tgt_tuples, anchors, type_vec)


def pattern_sharded_ok(snap: CSRSnapshot) -> bool:
    """Route gate: the host-assembled target tuples use the same arity
    cap as the single-chip ELL path (wider links fall back to host)."""
    N = snap.num_atoms
    return int(snap.arity[: N + 1].max(initial=0)) <= ELL_MAX_WIDTH


# --------------------------------------------------------------------------
# lane-sharded join execution
# --------------------------------------------------------------------------


def execute_join_sharded(
    snap: CSRSnapshot,
    sdev: ShardedSnapshot,
    plan,                    # join/planner.JoinPlan
    consts: np.ndarray,      # (K, n_consts) int32
    *,
    top_r: int = 16,
    n_real: int = None,
    row_cap: int = None,
    pad_cap: int = None,
    slot_budget: int = None,
):
    """The mesh twin of ``ops.join.execute_join`` for the serving lanes:
    ONE shard_map program runs the plan's whole expand-step chain per
    device over its K/n_dev lanes (``K`` must divide by the mesh size —
    the serve buckets do), with the same pad/row-bucket schedule
    arithmetic applied to the per-device lane count. Relations are
    replicated across the mesh in this v1 (each chip holds the full CSR
    for the join path; sharding the relations is the ROADMAP follow-up)
    — what the mesh buys today is the step chain's candidate expansion
    and intersection running n_dev-wide. Returns an
    ``ops.join.JoinExecution`` with the lane axis reassembled, same
    counts/trunc/tuples contract as the single-chip executor."""
    from hypergraphdb_tpu.ops.join import (
        DEFAULT_PAD_CAP,
        DEFAULT_ROW_CAP,
        DEFAULT_SLOT_BUDGET,
        JoinExecution,
        _rel_arrays,
        _rel_host_offsets,
        join_expand_step,
        join_finalize,
    )

    row_cap = DEFAULT_ROW_CAP if row_cap is None else row_cap
    pad_cap = DEFAULT_PAD_CAP if pad_cap is None else pad_cap
    slot_budget = DEFAULT_SLOT_BUDGET if slot_budget is None else slot_budget
    mesh = sdev.mesh
    n_dev = int(mesh.devices.size)
    dev = snap.device
    K, A = (int(consts.shape[0]), int(consts.shape[1]))
    if K % n_dev:
        raise ValueError(
            f"lane count {K} must divide by the mesh size {n_dev}"
        )
    k_loc = K // n_dev
    n_real = K if n_real is None else int(n_real)
    consts = np.ascontiguousarray(consts, dtype=np.int32)
    consts_dev = jnp.asarray(consts) if A else jnp.zeros((K, 0), jnp.int32)

    # the per-step schedule (pads, row buckets, relation arrays, statics)
    # is host-computed ONCE for the whole batch — identical on every
    # device, with row buckets sized to the per-device lane count
    sched = []
    rels: list = []          # flat replicated array operands

    def rel_slot(arrs) -> tuple:
        idx = []
        for a in arrs:
            for i, have in enumerate(rels):
                if have is a:
                    idx.append(i)
                    break
            else:
                rels.append(a)
                idx.append(len(rels) - 1)
        return tuple(idx)

    R = k_loc
    for s in plan.steps:
        if s.source_key.kind == "const":
            off_h = _rel_host_offsets(snap, s.source_rel)
            real = consts[:n_real]
            keys = np.clip(real[:, s.source_key.index], 0, snap.num_atoms)
            w = int(np.max(off_h[keys + 1] - off_h[keys], initial=1))
        else:
            w = 4 * (int(s.width_est) + 1)
        pad = _bucket(
            max(min(w, pad_cap, max(slot_budget // max(R, 1), 8)), 1),
            minimum=8,
        )
        rows_out = min(_bucket(R * pad), row_cap, R * pad)
        exp_ix = rel_slot(_rel_arrays(snap, dev, s.source_rel))
        filt_sel = []
        filt_ix = []
        for f in s.filters:
            fo, ff = _rel_arrays(snap, dev, f.rel)
            filt_sel.append((f.rev, f.key.kind, f.key.index))
            filt_ix.append(rel_slot((fo, ff)))
        sched.append({
            "exp_ix": exp_ix, "filt_ix": tuple(filt_ix),
            "exp_sel": (s.source_key.kind, s.source_key.index),
            "filt_sel": tuple(filt_sel),
            "type_handle": (-1 if s.type_handle is None
                            else int(s.type_handle)),
            "pad": pad, "rows_out": rows_out, "dedupe": s.dedupe,
        })
        R = rows_out
    type_ix = rel_slot((dev.type_of,))[0]
    sort_cols = tuple(plan.order.index(v) for v in plan.sig.vars)
    n_cols0 = 0

    def lane_prog(consts_loc, *rel_ops):
        lane_base = jax.lax.axis_index(AXIS).astype(jnp.int32) * k_loc
        cols = jnp.zeros((k_loc, n_cols0), jnp.int32)
        lanes = jnp.arange(k_loc, dtype=jnp.int32)          # LOCAL lanes
        valid = (lane_base + lanes) < n_real
        counts = jnp.zeros(k_loc, jnp.int32)
        trunc = jnp.zeros(k_loc, bool)
        for st in sched:
            n_dist = int(cols.shape[1]) if plan.distinct else 0
            cols, lanes, valid, counts, step_trunc = join_expand_step(
                rel_ops[st["exp_ix"][0]], rel_ops[st["exp_ix"][1]],
                cols, lanes, valid, consts_loc,
                tuple(rel_ops[i] for i, _ in st["filt_ix"]),
                tuple(rel_ops[j] for _, j in st["filt_ix"]),
                rel_ops[type_ix],
                exp_sel=st["exp_sel"], filt_sel=st["filt_sel"],
                type_handle=st["type_handle"],
                pad=st["pad"], rows_out=st["rows_out"], n_lanes=k_loc,
                n_distinct_cols=n_dist,
                distinct_consts=plan.distinct and A > 0,
                dedupe=st["dedupe"],
            )
            trunc = trunc | step_trunc
        tuples = join_finalize(cols, lanes, valid, top_r=top_r,
                               n_lanes=k_loc, sort_cols=sort_cols)
        return counts, trunc, tuples

    fn = jax.shard_map(
        lane_prog, mesh=mesh,
        in_specs=(P(AXIS),) + (P(),) * len(rels),
        out_specs=(P(AXIS), P(AXIS), P(AXIS)),
    )
    counts, trunc, tuples = fn(consts_dev, *rels)
    return JoinExecution(order=plan.order, counts=counts, trunc=trunc,
                         tuples=tuples)
