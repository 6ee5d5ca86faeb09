"""Bit-packed BFS frontiers — the 10M-atom-scale traversal engine.

Round-1's dense ``(K, N)`` bool frontiers (``ops/frontier.py``) cannot reach
BASELINE config-4 scale: at K=1024 seeds over N=10M atoms they need 10 GB per
bool array + 41 GB of int32 levels, vs 16 GB HBM on a v5e chip. This module
keeps the same GraphBLAS push-BFS semantics (SimpleALGenerator neighbor rule,
``HGBreadthFirstTraversal.java:49-66``) but stores every per-seed bitmap as
**bit-packed uint32 words** — a 32× cut — and bounds transients:

- persistent state is ``frontier``/``visited`` of shape (K, W) uint32 with
  ``W = ceil((N+1)/32)``: 1.28 GB total at K=1024, N=10M;
- the scatter destination is the only dense bool array, (K_block, M); K is
  processed in ``k_block``-sized blocks so it stays ~1-2 GB;
- edge relations stream through a ``lax.scan`` in ``edge_chunk`` slices, so
  the per-edge gather transient is (K_block, edge_chunk) instead of
  (K_block, E);
- levels, when requested, are int8 (max 127 hops — plenty; the reference's
  ``maxDistance`` defaults are single digits).

Edges touched per seed (the benchmark's edges/s numerator) fall out of the
scatter loop for free: each incidence entry whose source bit is live is
counted as it is gathered — no separate O(K·N) degree pass.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from hypergraphdb_tpu import verify as hgverify
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot, DeviceSnapshot

WORD = 32


def words_for(nbits: int) -> int:
    """uint32 words needed to hold ``nbits`` bits."""
    return (nbits + WORD - 1) // WORD


# ------------------------------------------------------------------ bit ops


def pack_bits(bits: jax.Array) -> jax.Array:
    """(..., M) bool with M % 32 == 0 → (..., M//32) uint32."""
    *lead, m = bits.shape
    w = m // WORD
    chunks = bits.reshape(*lead, w, WORD).astype(jnp.uint32)
    weights = jnp.left_shift(
        jnp.uint32(1), jnp.arange(WORD, dtype=jnp.uint32)
    )
    return (chunks * weights).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(packed: jax.Array) -> jax.Array:
    """(..., W) uint32 → (..., W*32) bool."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    *lead, w, _ = bits.shape
    return bits.astype(bool).reshape(*lead, w * WORD)


def test_bits(packed: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather bits: packed (..., W) uint32, idx (I,) int32 → (..., I) bool."""
    word = packed[..., idx >> 5]
    shift = (idx & 31).astype(jnp.uint32)
    return ((word >> shift) & jnp.uint32(1)).astype(bool)


def popcount(packed: jax.Array, axis=-1) -> jax.Array:
    """Population count summed along ``axis`` (int32)."""
    return jax.lax.population_count(packed).astype(jnp.int32).sum(axis=axis)


def valid_word_mask(n_valid: int, w: int, offset: int = 0) -> np.ndarray:
    """(w,) uint32 mask with bit j of word i set iff
    ``offset + i*32 + j < n_valid`` — clears the dummy row and pad bits.

    Host-side (numpy) reference for the device mask ``bfs_packed_block``
    builds inline with ``pack_bits`` — kept for host callers/tests; not on
    the traced BFS path (hglint HG103)."""
    ids = offset + np.arange(w * WORD, dtype=np.int64)
    bits = ids < n_valid
    return np.packbits(
        bits.reshape(w, WORD), axis=-1, bitorder="little"
    ).view("<u4").reshape(w)


# ------------------------------------------------------------------ kernels


def _scatter_relation(
    src: jax.Array,       # (C, chunk) int32 — message source ids (global)
    dst: jax.Array,       # (C, chunk) int32 — destination ids (local to dest)
    f_packed: jax.Array,  # (K, W_src) uint32 — source bitmaps
    m_dest: int,          # destination bool width
    count: bool,
    varying_axis: Optional[str] = None,
) -> tuple[jax.Array, jax.Array]:
    """Stream edge chunks: OR source bits into a dense bool destination.

    Returns (packed destination (K, m_dest//32) uint32, per-seed live-edge
    counts (K,) int32 — zeros when ``count`` is False).

    ``varying_axis``: when called inside a ``shard_map`` body over a mesh
    axis, the scan carry accumulates the device-local edge slice, so the
    replicated zero init must be cast to axis-varying.
    """
    K = f_packed.shape[0]

    def body(carry, sd):
        dest, cnt = carry
        s, d = sd
        bit = test_bits(f_packed, s)          # (K, chunk)
        dest = dest.at[:, d].max(bit)
        if count:
            cnt = cnt + bit.sum(axis=1, dtype=jnp.int32)
        return (dest, cnt), None

    init = (
        jnp.zeros((K, m_dest), dtype=bool),
        jnp.zeros((K,), dtype=jnp.int32),
    )
    if varying_axis is not None:
        init = jax.lax.pcast(init, (varying_axis,), to="varying")
    (dest, cnt), _ = jax.lax.scan(body, init, (src, dst))
    return pack_bits(dest), cnt


class PackedBFSResult(NamedTuple):
    visited: jax.Array        # (K, W) uint32 — packed reachable-set bitmaps
    edges_touched: jax.Array  # (K,) int32 — incidence entries with live source
    levels: Optional[jax.Array]  # (K, M) int8 or None — hop distance, -1 unreached


@hgverify.entry(
    shapes=lambda: (hgverify.dev_snapshot_exemplar(),
                    hgverify.sds((8,), "int32")),
    statics={"max_hops": 2, "edge_chunk": 64, "with_levels": False},
)
@partial(
    jax.jit,
    static_argnames=("max_hops", "edge_chunk", "with_levels"),
)
def bfs_packed_block(
    dev: DeviceSnapshot,
    seeds: jax.Array,     # (K,) int32
    max_hops: int,
    edge_chunk: int = 1 << 19,
    with_levels: bool = False,
) -> PackedBFSResult:
    """One seed-block of bit-packed multi-hop BFS, single device.

    The whole loop is one XLA program: per hop, two edge-relation scans
    (atom→link, link→target) each ending in a bit-pack — no host syncs,
    mirroring ``ops.frontier.bfs_levels`` at 1/32 the state footprint.

    ``max_hops`` is capped at 127 so levels fit int8 (the reference's
    ``maxDistance`` is single digits in practice).
    """
    if max_hops > 127:
        raise ValueError("bfs_packed: max_hops > 127 would overflow int8 levels")
    K = seeds.shape[0]
    N = dev.num_atoms
    w = words_for(N + 1)
    m = w * WORD

    def chunked(a):
        e = a.shape[0]
        pad = (-e) % edge_chunk
        if pad:
            a = jnp.concatenate([a, jnp.full((pad,), N, dtype=a.dtype)])
        return a.reshape(-1, edge_chunk)

    inc_src = chunked(dev.inc_src)
    inc_links = chunked(dev.inc_links)
    tgt_src = chunked(dev.tgt_src)
    tgt_flat = chunked(dev.tgt_flat)

    # clears dummy slot N + pad bits; built with jnp so tracing stays free
    # of host numpy work (hglint HG103) — XLA folds it to a constant
    valid = pack_bits(jnp.arange(m, dtype=jnp.int32) < N)

    frontier = jnp.zeros((K, w), dtype=jnp.uint32)
    bitv = jnp.left_shift(jnp.uint32(1), (seeds & 31).astype(jnp.uint32))
    frontier = frontier.at[jnp.arange(K), seeds >> 5].max(bitv)
    visited = frontier
    if with_levels:
        levels = jnp.where(unpack_bits(frontier), 0, -1).astype(jnp.int8)
    else:
        levels = jnp.zeros((), dtype=jnp.int8)

    def body(i, state):
        frontier, visited, counts, levels = state
        link_packed, c = _scatter_relation(
            inc_src, inc_links, frontier, m, count=True
        )
        nbr_packed, _ = _scatter_relation(
            tgt_src, tgt_flat, link_packed, m, count=False
        )
        nxt = nbr_packed & valid & ~visited
        if with_levels:
            levels = jnp.where(
                unpack_bits(nxt), (i + 1).astype(jnp.int8), levels
            )
        return nxt, visited | nxt, counts + c, levels

    frontier, visited, counts, levels = jax.lax.fori_loop(
        0, max_hops, body,
        (frontier, visited, jnp.zeros((K,), dtype=jnp.int32), levels),
    )
    return PackedBFSResult(
        visited, counts, levels if with_levels else None
    )


# ------------------------------------------------------------------ host API


def bfs_packed(
    snap: CSRSnapshot,
    seeds: np.ndarray,
    max_hops: int,
    k_block: int = 256,
    edge_chunk: int = 1 << 19,
    with_levels: bool = False,
) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Blocked driver: K seeds in ``k_block`` slices so the dense scatter
    transient stays ~``k_block × N`` bytes regardless of K.

    Returns (visited_packed (K, W) uint32, edges_touched (K,) int64,
    levels (K, N+1) int8 or None).
    """
    dev = snap.device
    seeds = np.asarray(seeds, dtype=np.int32)
    K = len(seeds)
    vis_out, cnt_out, lev_out = [], [], []
    for s in range(0, K, k_block):
        block = seeds[s : s + k_block]
        pad = k_block - len(block)
        if pad:
            block = np.concatenate([block, np.zeros(pad, dtype=np.int32)])
        res = bfs_packed_block(
            dev, jnp.asarray(block), max_hops,
            edge_chunk=edge_chunk, with_levels=with_levels,
        )
        take = k_block - pad
        vis_out.append(np.asarray(res.visited)[:take])
        cnt_out.append(np.asarray(res.edges_touched)[:take])
        if with_levels:
            lev_out.append(np.asarray(res.levels)[:take])
    visited = np.concatenate(vis_out)
    counts = np.concatenate(cnt_out).astype(np.int64)
    levels = (
        np.concatenate(lev_out)[:, : snap.num_atoms + 1]
        if with_levels else None
    )
    return visited, counts, levels


def unpack_visited(visited_packed: np.ndarray, n: int) -> np.ndarray:
    """(K, W) uint32 → (K, n) bool on host (numpy, no device round-trip)."""
    bits = np.unpackbits(
        visited_packed.view(np.uint8).reshape(len(visited_packed), -1),
        axis=1, bitorder="little",
    )
    return bits[:, :n].astype(bool)


# ------------------------------------------------------------------ planning


def bfs_memory_bytes(
    n_atoms: int,
    e_inc: int,
    e_tgt: int,
    k_block: int = 256,
    n_dev: int = 1,
    edge_chunk: int = 1 << 19,
    with_levels: bool = False,
) -> dict:
    """Per-device HBM budget of the packed BFS at a given scale — the
    planning contract VERDICT r1 asked for (config-4 must fit under a v5e
    chip's 16 GB). Pure arithmetic; a unit test pins the config-4 numbers."""
    w_full = words_for(n_atoms + 1)
    n_loc = -(-(n_atoms + 1) // (n_dev * 128)) * 128
    w_loc = n_loc // WORD if n_dev > 1 else w_full
    m_loc = n_loc if n_dev > 1 else w_full * WORD
    state = 3 * k_block * w_loc * 4            # frontier, visited, next (packed)
    gathered = 2 * k_block * w_full * 4        # all-gathered packed bitmaps
    scatter_dest = k_block * m_loc             # dense bool destination
    edge_transient = k_block * edge_chunk * 5  # gathered words + bool bits
    edges = (e_inc + e_tgt) * 2 * 4 // n_dev   # COO src+dst per relation
    atoms = (n_atoms // n_dev) * (4 * 3 + 1 + 8)  # type/arity/offsets,flag,rank
    levels = k_block * m_loc if with_levels else 0
    total = (
        state + gathered + scatter_dest + edge_transient + edges + atoms
        + levels
    )
    return {
        "state": state, "gathered": gathered, "scatter_dest": scatter_dest,
        "edge_transient": edge_transient, "edges": edges, "atoms": atoms,
        "levels": levels, "total": total,
    }
