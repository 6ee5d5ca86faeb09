"""CSRSnapshot — the immutable device-resident image of the hypergraph.

The central TPU-native idea (SURVEY §7 design stance): the mutable,
transactional store lives on host; queries and traversals run against an
**immutable CSR snapshot in HBM**. A snapshot is a long-lived read
transaction — MVCC maps onto versioned snapshots instead of pointer-chased
B-trees (the reference reads incidence sets through BDB cursors,
``BJEStorageImplementation.java:307``; here they are two flat gather-friendly
arrays).

Layout (all int32, padded to lane multiples, ``N = id_space`` = one past the
largest atom handle, with one extra dummy row ``N`` used as scatter/gather
dump for padding):

- ``inc_offsets[N+2]``, ``inc_links[E_inc]`` — incidence CSR: links pointing
  at each atom (sorted per row).
- ``inc_src[E_inc]`` — row id per entry (the "COO expansion" that makes the
  whole incidence relation one scatter op).
- ``tgt_offsets[N+2]``, ``tgt_flat[E_tgt]``, ``tgt_src[E_tgt]`` — target CSR:
  the ordered target tuple of each link atom.
- ``type_of[N+1]`` — type handle per atom (-1 for dead ids).
- ``is_link[N+1]`` — link flag per atom.
- ``arity[N+1]`` — target count per atom.
- ``value_rank[N+1]`` (uint64) — order-preserving 64-bit rank of each atom's
  value key PAYLOAD (``utils/ordered_bytes.rank64`` over the key minus its
  kind byte), enabling device-side value comparisons without host payloads
  (SURVEY §7 hard part 3). For fixed-width kinds (int/float/bool/time the
  payload is ≤ 8 bytes) the rank is EXACT — device eq/range filters need no
  host verification; variable-width kinds (str/bytes) tie on rank equality.
- ``value_kind[N+1]`` (uint8) — the kind byte of each atom's value key, so
  rank comparisons never cross kinds (ranks of different kinds are
  incomparable once the kind prefix is stripped).
- ``by_type``: type handle → sorted array of atom ids (the device form of
  the by-type system index).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import cached_property
from typing import Optional

import numpy as np

from hypergraphdb_tpu.obs.device import phase
from hypergraphdb_tpu.utils.ordered_bytes import rank64, rank_ambiguous

#: sentinel for padded entries in id arrays
PAD = np.int32(-1)


def _register_device_snapshot_pytree() -> None:
    """Register DeviceSnapshot as a jax pytree so jitted kernels can take it
    directly, regardless of which ops module is imported first."""
    import jax

    jax.tree_util.register_pytree_node(
        DeviceSnapshot,
        lambda s: (
            (
                s.inc_offsets, s.inc_links, s.inc_src,
                s.tgt_offsets, s.tgt_flat, s.tgt_src,
                s.type_of, s.is_link, s.arity,
                s.value_rank_hi, s.value_rank_lo, s.value_kind,
            ),
            s.num_atoms,
        ),
        lambda aux, ch: DeviceSnapshot(aux, *ch),
    )


def _pad_to(arr: np.ndarray, multiple: int, fill) -> np.ndarray:
    n = len(arr)
    m = ((n + multiple - 1) // multiple) * multiple if n else multiple
    if m == n:
        return arr
    out = np.full(m, fill, dtype=arr.dtype)
    out[:n] = arr
    return out


def _group_by_type(type_of_n: np.ndarray) -> dict[int, np.ndarray]:
    """type handle → sorted array of atom ids (device by-type index form)."""
    by_type: dict[int, np.ndarray] = {}
    live = type_of_n >= 0
    if live.any():
        th_arr = type_of_n[live]
        id_arr = np.nonzero(live)[0].astype(np.int32)
        order = np.lexsort((id_arr, th_arr))
        th_sorted, id_sorted = th_arr[order], id_arr[order]
        uniq, starts = np.unique(th_sorted, return_index=True)
        bounds = np.append(starts, len(th_sorted))
        for i, t in enumerate(uniq.tolist()):
            by_type[int(t)] = id_sorted[bounds[i] : bounds[i + 1]].copy()
    return by_type


def _incidence_transpose(
    tgt_src: np.ndarray, tgt_flat: np.ndarray, N: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Incidence CSR derived as the TRANSPOSE of the target relation: entry
    (t ← l) for every (l → t) edge, deduped, each row sorted by link id.
    Returns (inc_offsets (N+2,) int32, inc_links, inc_src)."""
    if len(tgt_flat):
        pair_order = np.lexsort((tgt_src, tgt_flat))
        pt = tgt_flat[pair_order].astype(np.int64)
        pl = tgt_src[pair_order].astype(np.int64)
        keep = np.ones(len(pt), dtype=bool)
        keep[1:] = (pt[1:] != pt[:-1]) | (pl[1:] != pl[:-1])
        pt, pl = pt[keep], pl[keep]
    else:
        pt = pl = np.empty(0, dtype=np.int64)
    inc_counts = np.bincount(pt, minlength=N + 1)
    inc_offsets = np.zeros(N + 2, dtype=np.int32)
    np.cumsum(inc_counts, out=inc_offsets[1 : N + 2])
    return inc_offsets, pl.astype(np.int32), pt.astype(np.int32)


@dataclass
class CSRSnapshot:
    version: int
    num_atoms: int          # id space size (N); row N is the dummy slot
    inc_offsets: np.ndarray
    inc_links: np.ndarray
    inc_src: np.ndarray
    tgt_offsets: np.ndarray
    tgt_flat: np.ndarray
    tgt_src: np.ndarray
    type_of: np.ndarray
    is_link: np.ndarray
    arity: np.ndarray
    value_rank: np.ndarray
    value_kind: np.ndarray = field(default_factory=lambda: np.empty(0, np.uint8))
    #: (N+1,) uint64 — SECOND rank word (key payload bytes 8..16), the
    #: hgindex tie-break for variable-width kinds; empty on snapshots
    #: packed before the column existed (consumers treat empty as
    #: "no tie-break: var-width columns stay host-served"). HOST-side
    #: only — DeviceSnapshot's pytree is unchanged; the device twin
    #: rides each ValueIndexColumn's rank2 words instead.
    value_rank2: np.ndarray = field(
        default_factory=lambda: np.empty(0, np.uint64))
    #: (N+1,) bool — True where the atom's 128-bit rank pair is NOT a
    #: faithful stand-in for its full key (payload >16 bytes, or NUL in
    #: the first 16 — ``utils/ordered_bytes.rank_ambiguous``). Only
    #: consulted for variable-width kinds; fixed-width exactness is a
    #: property of the KIND, not the atom.
    value_ambig: np.ndarray = field(
        default_factory=lambda: np.empty(0, bool))
    by_type: dict[int, np.ndarray] = field(default_factory=dict)
    n_edges_inc: int = 0    # real (unpadded) incidence entries
    n_edges_tgt: int = 0    # real (unpadded) target entries

    @staticmethod
    @phase("hg.snapshot.from_tables")
    def from_tables(
        type_of: np.ndarray,      # (N,) int32 type handle per atom, -1 dead
        is_link: np.ndarray,      # (N,) bool
        tgt_offsets: np.ndarray,  # (N+1,) int — target CSR offsets
        tgt_flat: np.ndarray,     # (E,) int — ordered targets per link
        value_rank: Optional[np.ndarray] = None,  # (N,) uint64 payload ranks
        value_kind: Optional[np.ndarray] = None,  # (N,) uint8 kind bytes
        value_rank2: Optional[np.ndarray] = None,  # (N,) uint64 tie-break word
        value_ambig: Optional[np.ndarray] = None,  # (N,) bool rank ambiguity
        version: int = 0,
        pad_multiple: int = 128,
    ) -> "CSRSnapshot":
        """Assemble a snapshot directly from columnar tables — the
        dataset-scale bulk path (the analogue of the reference's
        subgraph-as-stream loading, ``storage/RAMStorageGraph.java``),
        bypassing per-atom store writes entirely. Used by the benchmark
        generators to build 10M-atom graphs in seconds; ``pack`` routes
        through the same assembly."""
        N = len(type_of)
        type_col = np.full(N + 1, -1, dtype=np.int32)
        type_col[:N] = type_of
        link_col = np.zeros(N + 1, dtype=bool)
        link_col[:N] = is_link
        arity = np.zeros(N + 1, dtype=np.int32)
        lens = np.asarray(tgt_offsets[1:]) - np.asarray(tgt_offsets[:-1])
        arity[:N] = lens.astype(np.int32)
        rank_col = np.zeros(N + 1, dtype=np.uint64)
        if value_rank is not None:
            rank_col[:N] = value_rank
        kind_col = np.zeros(N + 1, dtype=np.uint8)
        if value_kind is not None:
            kind_col[:N] = value_kind
        rank2_col = np.zeros(N + 1, dtype=np.uint64)
        if value_rank2 is not None:
            rank2_col[:N] = value_rank2
        ambig_col = np.zeros(N + 1, dtype=bool)
        if value_ambig is not None:
            ambig_col[:N] = value_ambig
        elif value_kind is not None and value_rank2 is None:
            # rank-only callers (the bulk bench path) carry no keys to
            # derive the tie-break from: variable-width atoms must stay
            # rank-ambiguous (→ host-served windows), preserving the
            # pre-tie-break behavior instead of guessing exactness
            from hypergraphdb_tpu.storage.value_index import FIXED_WIDTH_KINDS

            fixed = np.isin(
                kind_col[:N],
                np.frombuffer(bytes(FIXED_WIDTH_KINDS), dtype=np.uint8))
            ambig_col[:N] = (kind_col[:N] != 0) & ~fixed
        off = np.zeros(N + 2, dtype=np.int32)
        off[1 : N + 1] = np.asarray(tgt_offsets[1:], dtype=np.int32)
        off[N + 1] = off[N]
        tgt_flat = np.asarray(tgt_flat, dtype=np.int32)
        tgt_src = np.repeat(
            np.arange(N, dtype=np.int32), lens.astype(np.int64)
        )
        inc_offsets, inc_links, inc_src = _incidence_transpose(
            tgt_src, tgt_flat, N
        )
        e_inc, e_tgt = len(inc_links), len(tgt_flat)
        return CSRSnapshot(
            version=version,
            num_atoms=N,
            inc_offsets=inc_offsets,
            inc_links=_pad_to(inc_links, pad_multiple, N),
            inc_src=_pad_to(inc_src, pad_multiple, N),
            tgt_offsets=off,
            tgt_flat=_pad_to(tgt_flat, pad_multiple, N),
            tgt_src=_pad_to(tgt_src, pad_multiple, N),
            type_of=type_col,
            is_link=link_col,
            arity=arity,
            value_rank=rank_col,
            value_kind=kind_col,
            value_rank2=rank2_col,
            value_ambig=ambig_col,
            by_type=_group_by_type(type_col[:N]),
            n_edges_inc=e_inc,
            n_edges_tgt=e_tgt,
        )

    # ------------------------------------------------------------------ pack
    @staticmethod
    def extract_tables(graph, value_ranks: bool = True) -> dict:
        """Read the committed store into raw host tables — the ONLY part of
        packing that must see a consistent store state. Background
        compaction (``ops/incremental.SnapshotManager``) holds the commit
        lock just for this extraction and runs the expensive CSR assembly
        (``pack(tables=...)``) lock-free."""
        backend = graph.backend
        ids, offsets, flat = backend.bulk_links()
        value_items = None
        if value_ranks:
            try:
                from hypergraphdb_tpu.core.graph import IDX_BY_VALUE

                idx = backend.get_index(IDX_BY_VALUE, create=False)
                if idx is not None:
                    value_items = list(idx.bulk_items())
            except Exception:
                value_items = None
        peek = int(graph.handles.peek) if hasattr(graph.handles, "peek") else 0
        return {
            "ids": np.asarray(ids, dtype=np.int64),
            "offsets": np.asarray(offsets, dtype=np.int64),
            "flat": np.asarray(flat, dtype=np.int64),
            "peek": max(peek, int(backend.max_handle())),
            "value_items": value_items,
        }

    @staticmethod
    def pack(graph, version: Optional[int] = None, pad_multiple: int = 128,
             capacity: Optional[int] = None, value_ranks: bool = True,
             tables: Optional[dict] = None,
             ) -> "CSRSnapshot":
        """Pack the committed store into CSR arrays (the ``storage/tpu-jax``
        snapshot step from BASELINE.json's north star).

        ``capacity`` over-allocates the id space so atoms added AFTER the
        pack still fit in this snapshot's bitmap width — the prerequisite
        for delta overlays (``ops/incremental.py``): base and delta share
        one frontier shape, so no recompilation on ingest. ``tables`` (from
        :meth:`extract_tables`) lets callers separate the store read from
        the assembly."""
        if tables is None:
            tables = CSRSnapshot.extract_tables(graph, value_ranks)
        ids = tables["ids"]
        offsets = tables["offsets"]
        flat = tables["flat"]
        n = tables["peek"]
        if capacity is not None:
            n = max(n, int(capacity))
        N = n  # id space; dummy row is N

        type_of = np.full(N + 1, -1, dtype=np.int32)
        is_link = np.zeros(N + 1, dtype=bool)
        arity = np.zeros(N + 1, dtype=np.int32)
        value_rank = np.zeros(N + 1, dtype=np.uint64)
        value_kind = np.zeros(N + 1, dtype=np.uint8)
        value_rank2 = np.zeros(N + 1, dtype=np.uint64)
        value_ambig = np.zeros(N + 1, dtype=bool)

        # fully vectorized record decode (the 10M-atom scale path — no
        # per-atom Python): record layout is (type, value, flags, *targets),
        # see core/graph.py
        starts = offsets[:-1]
        lens = offsets[1:] - starts
        ok = lens >= 3
        vids = ids[ok]
        vstarts = starts[ok]
        vlens = lens[ok]
        type_of[vids] = flat[vstarts].astype(np.int32)
        value_handles = flat[vstarts + 1]
        is_link[vids] = (flat[vstarts + 2].astype(np.int64) & 1).astype(bool)
        arities = (vlens - 3).astype(np.int32)
        arity[vids] = arities

        # target COO: for record j, positions vstarts[j]+3 .. end
        rec_of = np.repeat(np.arange(len(vids)), vlens)
        pos_in_rec = np.arange(len(rec_of)) - np.repeat(
            np.cumsum(vlens) - vlens, vlens
        )
        tmask = pos_in_rec >= 3
        rec_sel = rec_of[tmask]
        tgt_flat_coo = flat[
            np.repeat(vstarts, vlens)[tmask] + pos_in_rec[tmask]
        ].astype(np.int32)
        tgt_src_coo = vids[rec_sel].astype(np.int32)

        # target CSR grouped by source link (records already id-ascending)
        tgt_counts = np.zeros(N + 1, dtype=np.int64)
        tgt_counts[vids] = arities
        tgt_offsets = np.zeros(N + 2, dtype=np.int32)
        np.cumsum(tgt_counts, out=tgt_offsets[1 : N + 2])
        e_tgt = len(tgt_flat_coo)
        tgt_flat_arr = tgt_flat_coo
        tgt_src_arr = tgt_src_coo

        # incidence CSR = transpose of the target relation (shared helper)
        inc_offsets, inc_links_arr, inc_src_arr = _incidence_transpose(
            tgt_src_coo, tgt_flat_coo, N
        )
        e_inc = len(inc_links_arr)

        # value ranks via the by-value system index: one rank64 per DISTINCT
        # key (values repeat heavily in real graphs), scattered to handles.
        # The kind byte is stripped into its own column so the 8 rank bytes
        # all carry payload — exact (tie-free) for fixed-width kinds.
        if tables["value_items"] is not None:
            # lazy import keeps ops/ free of module-level storage deps
            from hypergraphdb_tpu.storage.value_index import FIXED_WIDTH_KINDS

            for key, hs in tables["value_items"]:
                sel = hs[hs <= N]
                payload = key[1:]
                value_rank[sel] = rank64(payload)
                value_kind[sel] = key[0] if key else 0
                # the hgindex tie-break pair: second word + ambiguity bit
                # (payload beyond 16 bytes, or NUL among the first 16 —
                # there zero-padding stops being a faithful order/identity
                # map and the window must host-serve). Fixed-width kinds
                # are NEVER ambiguous: their 8-byte payload fits the first
                # rank word entirely, NUL bytes and all.
                value_rank2[sel] = rank64(payload[8:16])
                if key and key[0] not in FIXED_WIDTH_KINDS:
                    value_ambig[sel] = rank_ambiguous(payload)

        # pad edge arrays to lane multiples; padded entries point at the
        # dummy row N (whose frontier/visited value is always False)
        inc_links_p = _pad_to(inc_links_arr, pad_multiple, N)
        inc_src_p = _pad_to(inc_src_arr, pad_multiple, N)
        tgt_flat_p = _pad_to(tgt_flat_arr, pad_multiple, N)
        tgt_src_p = _pad_to(tgt_src_arr, pad_multiple, N)

        # by-type sorted id arrays (device form of the by-type index)
        by_type = _group_by_type(type_of[:N])

        return CSRSnapshot(
            version=version if version is not None else getattr(
                graph, "_mutations", 0
            ),
            num_atoms=N,
            inc_offsets=inc_offsets,
            inc_links=inc_links_p,
            inc_src=inc_src_p,
            tgt_offsets=tgt_offsets,
            tgt_flat=tgt_flat_p,
            tgt_src=tgt_src_p,
            type_of=type_of,
            is_link=is_link,
            arity=arity,
            value_rank=value_rank,
            value_kind=value_kind,
            value_rank2=value_rank2,
            value_ambig=value_ambig,
            by_type=by_type,
            n_edges_inc=e_inc,
            n_edges_tgt=e_tgt,
        )

    # ------------------------------------------------------------------ host views
    def incidence_row(self, atom: int) -> np.ndarray:
        s, e = int(self.inc_offsets[atom]), int(self.inc_offsets[atom + 1])
        return self.inc_links[s:e]

    def targets_row(self, atom: int) -> np.ndarray:
        s, e = int(self.tgt_offsets[atom]), int(self.tgt_offsets[atom + 1])
        return self.tgt_flat[s:e]

    def type_set(self, type_handle: int) -> np.ndarray:
        return self.by_type.get(int(type_handle), np.empty(0, dtype=np.int32))

    def restrict_links(self, link_types) -> "CSRSnapshot":
        """The sub-hypergraph whose links have a type atom among
        ``link_types``: both relations keep only the entries of admitted
        links (``type_of[link]`` in the family), rows keep their order, the
        id space and every per-atom column stay the parent's (shared, not
        copied — an excluded link is still an atom that an admitted link
        may target). A traversal over the result follows exactly the links
        a ``DefaultALGenerator(link_predicate = type in family)`` follows
        over the parent. A type atom no link has admits nothing; where the
        family admits every link entry the parent itself comes back. Not
        memoised here: ``ops/ellbfs.restricted_for`` keeps one per family
        on the parent, beside its plan."""
        N, e_tgt, e_inc = self.num_atoms, self.n_edges_tgt, self.n_edges_inc
        admit = np.isin(self.type_of,
                        np.fromiter(link_types, dtype=np.int64)
                        ) & self.is_link
        keep_tgt = admit[self.tgt_src[:e_tgt]]
        keep_inc = admit[self.inc_links[:e_inc]]
        if keep_tgt.all() and keep_inc.all():
            return self

        def kept(keep, rows, entries):
            """One relation filtered: its offsets, row ids and entries,
            padded as ``from_tables`` pads them."""
            rows = rows[: len(keep)][keep]
            offsets = np.zeros(N + 2, dtype=np.int32)
            np.cumsum(np.bincount(rows, minlength=N + 1), out=offsets[1:])
            return (offsets, _pad_to(rows, 128, N),
                    _pad_to(entries[: len(keep)][keep], 128, N))

        tgt_offsets, tgt_src, tgt_flat = kept(
            keep_tgt, self.tgt_src, self.tgt_flat)
        inc_offsets, inc_src, inc_links = kept(
            keep_inc, self.inc_src, self.inc_links)
        return replace(
            self, inc_offsets=inc_offsets, inc_links=inc_links,
            inc_src=inc_src, tgt_offsets=tgt_offsets, tgt_flat=tgt_flat,
            tgt_src=tgt_src, n_edges_inc=int(inc_offsets[-1]),
            n_edges_tgt=int(tgt_offsets[-1]))

    # ------------------------------------------------------------------ device
    @cached_property
    def device(self) -> "DeviceSnapshot":
        """Transfer to the default device (HBM) once; cached."""
        return DeviceSnapshot.from_host(self)


@dataclass
class DeviceSnapshot:
    """The jnp-array twin of a CSRSnapshot, resident in device memory."""

    num_atoms: int
    inc_offsets: "jax.Array"  # noqa: F821
    inc_links: "jax.Array"  # noqa: F821
    inc_src: "jax.Array"  # noqa: F821
    tgt_offsets: "jax.Array"  # noqa: F821
    tgt_flat: "jax.Array"  # noqa: F821
    tgt_src: "jax.Array"  # noqa: F821
    type_of: "jax.Array"  # noqa: F821
    is_link: "jax.Array"  # noqa: F821
    arity: "jax.Array"  # noqa: F821
    # the 64-bit order-preserving value ranks, split into two uint32 words
    # (compare lexicographically hi-then-lo): jnp.asarray would silently
    # truncate uint64 to its LOW 32 bits under default x64-disabled JAX,
    # destroying the ordering
    value_rank_hi: "jax.Array"  # noqa: F821
    value_rank_lo: "jax.Array"  # noqa: F821
    value_kind: "jax.Array"  # noqa: F821 — uint8 kind byte per atom

    @staticmethod
    def from_host(snap: CSRSnapshot) -> "DeviceSnapshot":
        import jax.numpy as jnp

        return DeviceSnapshot(
            num_atoms=snap.num_atoms,
            inc_offsets=jnp.asarray(snap.inc_offsets),
            inc_links=jnp.asarray(snap.inc_links),
            inc_src=jnp.asarray(snap.inc_src),
            tgt_offsets=jnp.asarray(snap.tgt_offsets),
            tgt_flat=jnp.asarray(snap.tgt_flat),
            tgt_src=jnp.asarray(snap.tgt_src),
            type_of=jnp.asarray(snap.type_of),
            is_link=jnp.asarray(snap.is_link),
            arity=jnp.asarray(snap.arity),
            value_rank_hi=jnp.asarray(
                (snap.value_rank >> np.uint64(32)).astype(np.uint32)
            ),
            value_rank_lo=jnp.asarray(
                (snap.value_rank & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            ),
            value_kind=jnp.asarray(
                snap.value_kind
                if len(snap.value_kind) == snap.num_atoms + 1
                else np.zeros(snap.num_atoms + 1, dtype=np.uint8)
            ),
        )


_register_device_snapshot_pytree()
