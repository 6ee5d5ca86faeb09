"""Pull-mode, seed-transposed BFS — the fast path for config-4 scale.

Round 2's ``ops/bitfrontier.py`` made 10M-atom frontiers *fit* (bit-packed
``(K, W)`` bitmaps) but not *fast*: its push scan does a ``test_bits``
gather plus an ``.at[:, d].max`` scatter **per (seed, edge)** — K×E scalar
probes per hop. Measured on v5e, XLA lowers both to a latency-bound unit
running ~10⁸ indices/s, which is why the round-2 bench saw 324 s/run and
<1% of HBM (VERDICT r2 Weak #2).

This module keeps the same BFS semantics (``SimpleALGenerator`` neighbor
rule: frontier atom → incident links → their targets, reference
``HGBreadthFirstTraversal.java:49-66``) but re-lays the computation so the
expensive primitive is **one row gather per edge**, not K probes per edge:

- the reached set is stored **transposed**: ``V[(N+1, Kw)] uint32`` — bit
  k of word ``V[v, k>>5]`` says "seed k has reached atom v". One row per
  atom carries ALL seeds of the block at once (128 bytes at K=1024; 512
  bytes at K=4096 — the wide mode that feeds the Pallas gather, see
  ``ops/pallas_gather.py``).
- the FIRST hop follows the frontier: its K single-atom frontiers are
  known exactly, so the seeds' own neighbourhood (incident links → their
  targets) is expanded on the host from the snapshot's CSR arrays and its
  bits are placed into the seed bitmap — work ∝ Σ deg(seeds), not ∝ E
  (``_seed_pairs``, ``_sparse_first_hop``; the sparse step of a
  direction-optimising BFS).
  Only seeds whose neighbourhood is a sizeable share of the plan (hubs on
  a small graph) send hop 1 down the pull chain too; the rule reads the
  input alone (``SPARSE_SHARE``).
- every later hop is two *pull* reductions with NO scatters, pulling from
  VISITED (monotone closure — no separate frontier array, half the state):
  stage 1: ``link_live[l] = OR_{t ∈ targets(l)} V[t]``
  stage 2: ``reach[v]    = OR_{l ∈ incident(v)} link_live[l]``
  Each is a gather of edge-many rows followed by a fixed-width reduction
  over host-precomputed padded index plans (:class:`ReducePlan`). Level 0
  is a few WIDTH CLASSES (``CLASS_WIDTHS``): a CSR row of degree d sits
  whole in ONE chunk of the smallest class width >= d, so the segment-OR
  of a class is a plain ``reshape(-1, w, Kw) → OR(axis=1)`` — XLA's fused
  streaming path, no segment ids, no conflicts — and the row is finished
  where it was gathered. Only a row above the widest class (``W_MAX``) is
  cut into ``W_MAX``-wide chunks and handled by recursion (upper level ℓ
  reduces rows of up to ``W_MAX · w_upper^ℓ`` entries): hubs, a few
  thousand rows of the 10M-atom graph. A gathered index costs the chip
  the same whether it is an entry or a pad (``ops/pallas_gather.py``), so
  the plan's index count IS the hop's time: one width-8 level 0 gathered
  1.63-1.81 indices an entry plus an upper pyramid over every row past 8
  entries (PERF.md section 6, PR 30).
- levels compose: stage 2's level-0 indices (every class) are
  pre-composed with stage 1's output map on host, so link-space results
  are consumed directly without materializing a per-link destination
  array.
- a hop ends in an update of the state from the stage buffer
  (``_visited_update``, a match's ``_frontier_replace``), over the row
  blocks the hop's plan can reach and no other (``_active_blocks``: a row
  is reached only if its atom has an incidence set, so a store that lays
  entities out before links folds the entities' blocks alone); where the
  stages gather on the kernel, so does the update's row fetch, at width 1
  (``_update_on_kernel``): ``hg_gather_or`` for a bitmap,
  ``hg_gather_scalar`` for a whole-graph operator's flat state.
- per-seed edge counts (the benchmark numerator) are one exact pass a
  seed block (``_deg_sum``: bit-unpack, weight by degree and sum in
  ``int32``, fused on the vector unit) — no gathers — over the row blocks
  in which the state can hold a bit and no other, as ``_reach_counts`` is
  (``_bitdot``: the plan's active blocks, and the seeds' own).
- a LINK PREDICATE (``bfs_pull(..., link_types=F)``: follow a link only if
  its type atom is in ``F`` — ``DefaultALGenerator``'s ``linkPredicate``,
  ``DefaultALGenerator.java:73``, for a family of link types) is not a
  second traversal: it is this one over the sub-hypergraph the family
  selects (``CSRSnapshot.restrict_links``, both relations filtered by
  ``type_of[link]``, id space unchanged), with a plan of its own per
  (snapshot, family) (:func:`restricted_for`), so the gathers move the
  admitted entries only. ``DefaultALGenerator``'s other options stay
  host-only (``algorithms/traversals.py``): the sibling predicate, the
  ordered-link directions (preceding / succeeding targets, reverse order)
  and a predicate that differs by hop.
- every operator runs ONE expansion step (:func:`_expand`: the sparse
  placement, or stage 1 → stage 2's level 0 → its upper levels and the
  operator's update): :func:`bfs_pull` H times on a visited set,
  :func:`path_match` over a plan per step on a frontier, and
  :func:`pair_distances` alternately on TWO balls, one grown from each
  end of a pair, with a meet test (``_meet``: do the balls share a row,
  column by column) after every expansion — HOW FAR apart, a length per
  pair (``GraphClassics.dijkstra`` at unit weights), where the hop count
  follows the data: a batch ends when its last pair is met or exhausted.
- one WHOLE-GRAPH operator runs the same plan: :func:`connected_components`,
  min-label rounds until a fixpoint — the pyramid and the fold reduce with
  the state's reduction (``_reduction``: OR over uint32 seed words, min over
  a flat int32 vector of labels, the buffer's zero row and every padded
  index holding its identity); and :func:`pagerank`, a fixed count of
  iterations that SUM float32 ranks through the same two pyramids (the
  first reduction that is not idempotent: every entry covered once, the
  fold replacing). On a TPU their level 0 gathers on the row-gather
  kernel's scalar form (``pallas_gather.gather_reduce``: the state whole
  in VMEM as rows of 128, a row loaded an index and its one lane kept),
  chosen, as the OR's kernel is, from what the code observes
  (``_kernel_of``: the state's shape and dtype, the gate); the upper
  levels and the fold's fetch keep the XLA gather.

Geometry note: each gather row is ``Kw = K/32`` uint32 words (32 lanes for
K=1024). Gathers remain the dominant cost and are bound by the indices
issued, but the total index count per hop drops from ``K × E`` to the
plan's ``~1.4 × E`` (the classes' padding) — three orders of magnitude at
K=1024.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from hypergraphdb_tpu import verify as hgverify
from hypergraphdb_tpu.obs.device import phase
from hypergraphdb_tpu.obs.registry import default_registry
from hypergraphdb_tpu.ops import pallas_gather as _pg
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot

WORD = 32

#: The identity of the min over labels: what the buffer's zero row and every
#: padded index of a label pyramid read, and a label no atom has.
INT32_MAX = int(np.iinfo(np.int32).max)


class _Reduction(NamedTuple):
    """What a pyramid and a fold reduce with: ``combine`` is elementwise,
    associative and commutative, and ``identity`` is what the buffer's
    zero row and every padded index hold.

    The invariant the pyramid keeps is EXACT coverage: every CSR entry is
    gathered once, into the one chunk of its row, and every padded index
    reads the identity — so a combine need not be idempotent, and a sum
    is as exact as an OR. The one place that visits a row twice is
    ``_fold_rows``' clamped last block: a fold's own combine must give the
    same row both times. The OR and the min do, and so does a fold that
    REPLACES the state's row; an accumulating add does not (it would count
    the overlap twice), so a sum is folded by replacement."""

    combine: Callable
    identity: int | float
    #: the scalar form of the row-gather kernel's name for it
    #: (``pallas_gather.SCALAR_OPS``), where a flat state takes the kernel
    scalar_op: Optional[str] = None


#: OR over ``(S, Kw)`` uint32 rows of seed bits: a traversal, a match, a
#: pair search.
_OR_WORDS = _Reduction(jnp.bitwise_or, 0)
#: min over a flat ``(S,)`` int32 vector of labels:
#: :func:`connected_components`.
_MIN_LABELS = _Reduction(jnp.minimum, INT32_MAX, "min")
#: sum over a flat ``(S,)`` float32 vector of rank shares: :func:`pagerank`.
_SUM_FLOATS = _Reduction(jnp.add, 0.0, "sum")


def _reduction(state) -> _Reduction:
    """The reduction of a state, where it enters a pyramid or a fold (the
    values of ``_apply_plan``, the buffer of ``_reduce_into``, the state of
    ``_fold_rows``), passed down from there: ``(S, Kw)`` uint32 seed words
    take the OR, a flat ``(S,)`` int32 label vector the min, a flat
    ``(S,)`` float32 vector the sum, and any other state is an error, never
    a default. One pyramid and one fold serve all three; the bitmap and
    label programs lower to the text they had before the sum came."""
    if state.ndim == 2 and state.dtype == jnp.uint32:
        return _OR_WORDS
    if state.ndim == 1 and state.dtype == jnp.int32:
        return _MIN_LABELS
    if state.ndim == 1 and state.dtype == jnp.float32:
        return _SUM_FLOATS
    raise TypeError(f"no reduction for a {state.dtype} state of shape "
                    f"{state.shape}: (S, Kw) uint32 words, (S,) int32 "
                    f"labels or (S,) float32 sums")


def _at(row, x) -> tuple:
    """The start index of row ``row`` of ``x`` for a dynamic (update) slice,
    whatever the rank: ``(row, 0)`` a bitmap's, ``(row,)`` a vector's."""
    return (row,) + (0,) * (x.ndim - 1)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# ------------------------------------------------------------------ host plans

#: Chunk widths of a plan's level 0, ascending: a CSR row of degree d is
#: reduced in ONE chunk of the smallest width >= d, where it is gathered;
#: only a row above the last width (``W_MAX``) is cut into chunks (of
#: ``W_MAX``) and climbs the upper pyramid. Constants, not a knob: fixed
#: from what an index costs ``hg_gather_or`` at each width on the chip
#: (``benchmarks/tests/gather_width_probe.py``; PERF.md section 6, PR 30).
#: Even widths to 10, then steps of about the square root of two, so that a
#: row's padding stays under a third; none a multiple of 32, where the
#: kernel pays 16.6 ns an index against 14.5-15.3 at 28, 40 and 56; odd
#: widths cost what the next even one does. Every class earns its place on
#: the 10M-atom cells' degree tables (without 28 an untyped hop is 154 ms
#: longer, without 14 — the least — 7 ms).
CLASS_WIDTHS = (2, 4, 6, 8, 10, 14, 20, 28, 40, 56)
W_MAX = CLASS_WIDTHS[-1]
for _w in CLASS_WIDTHS:
    # a real raise, not an assert: the guard must survive `python -O`
    if (_why := _pg.declined(_w, _pg.ROW_WORDS)) is not None:
        raise ValueError(f"ellbfs.CLASS_WIDTHS: {_why}")
del _w, _why

#: Indices a scan step of a level-0 class moves, in units of the caller's
#: ``chunk``: ``chunk`` output rows at width 8, as many indices at every
#: other width, so that a step's gather transient does not follow the class
STEP_WIDTH = 8


@dataclass(frozen=True)
class ReducePlan:
    """Padded-gather tree reduction over one CSR relation.

    The first ``n_lvl0`` of ``levels`` are level 0's WIDTH CLASSES: they
    index caller-provided value rows (with ``zero_row`` pointing at a
    guaranteed-all-zero row), and between them cover every non-empty row
    once — a row of degree d <= the widest class sits whole in one chunk
    of the smallest class width >= d (rows in CSR order within a class)
    and is finished there; a row above it is cut into chunks of the widest
    class, in that class's array. A class no row falls in has no array.
    The levels after them are the upper pyramid and cover ONLY those cut
    rows, still unfinished (more than one chunk): their indices are local
    to the previous level's chunk array (the first upper level's to the
    widest class's), with index ``len(prev_chunks)`` meaning the per-level
    appended zero row. A relation with no row above the widest class has
    no upper level.

    A row's final chunk therefore lives in the chunk array of whichever
    level it finished at; ``out_map[r]`` addresses the **concatenation** of
    all level chunk arrays (in order, classes first) with one global zero
    row at the very end (``concat_size``). Empty rows map to the zero row.
    All index arrays are int32; every level's length is a multiple of its
    width.
    """

    levels: tuple[np.ndarray, ...]
    widths: tuple[int, ...]
    n_lvl0: int          # leading levels that read the caller's values
    out_map: np.ndarray  # (R,) int32 into concat space; empty rows → zero row
    n_rows: int
    concat_size: int     # total chunks across levels; zero row lives here

    @property
    def total_indices(self) -> int:
        return int(sum(len(l) for l in self.levels))

    @property
    def upper_indices(self) -> int:
        return int(sum(len(l) for l in self.levels[self.n_lvl0:]))


def build_reduce_plan(
    offsets: np.ndarray,
    flat: np.ndarray,
    n_rows: int,
    zero_row: int,
    classes: Sequence[int] = CLASS_WIDTHS,
    w_upper: int = 8,
) -> ReducePlan:
    """Build the padded index pyramid for ``reduce_or`` over CSR rows.

    ``offsets``/``flat`` describe rows ``0..n_rows``; ``zero_row`` indexes
    an all-zero value row used for level-0 padding. ``classes`` are level
    0's chunk widths, ascending (one width: every row in chunks of it);
    upper levels use ``w_upper``.
    """
    offsets = np.asarray(offsets, dtype=np.int64)
    flat = np.asarray(flat, dtype=np.int32)
    deg = offsets[1 : n_rows + 1] - offsets[:n_rows]
    w_max = int(classes[-1])
    cls = np.minimum(np.searchsorted(np.asarray(classes), deg),
                     len(classes) - 1)  # rows above w_max: the widest class
    cls[deg == 0] = -1

    levels, widths = [], []
    out_map = np.full(n_rows, -1, dtype=np.int64)
    level_offset = n_prev = 0  # the newest level's section, and its chunks
    rows = counts = starts = np.empty(0, np.int64)
    for c, w in enumerate(classes):
        members = np.flatnonzero(cls == c)  # CSR order: stable in a class
        if not len(members):
            continue
        rows = members
        counts = -(-deg[rows] // w)  # 1, but for the widest class's cut rows
        starts = np.zeros(len(rows), dtype=np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        level_offset += n_prev
        n_prev = int(starts[-1] + counts[-1])
        idx = np.full(n_prev * w, zero_row, dtype=np.int32)
        idx[_segmented_ranges(starts * w, deg[rows])] = \
            flat[_segmented_ranges(offsets[rows], deg[rows])]
        levels.append(idx)
        widths.append(int(w))
        done = counts == 1
        out_map[rows[done]] = level_offset + starts[done]
    if not levels:  # no entry at all: one empty class keeps the shapes
        levels, widths = [np.empty(0, np.int32)], [w_max]
    n_lvl0 = len(levels)

    # the upper pyramid, over the cut rows alone: their chunk spans start
    # contiguously (``starts``) in the previous level's array
    live = counts > 1
    rows, counts, starts = rows[live], counts[live], starts[live]
    while len(rows):
        nxt = -(-counts // w_upper)
        nxt_starts = np.zeros(len(rows), dtype=np.int64)
        np.cumsum(nxt[:-1], out=nxt_starts[1:])
        n_nxt = int(nxt_starts[-1] + nxt[-1])
        idx = np.full(n_nxt * w_upper, n_prev,
                      dtype=np.int32)  # pad → prev zero row
        idx[_segmented_ranges(nxt_starts * w_upper, counts)] = \
            _segmented_ranges(starts, counts).astype(np.int32)
        levels.append(idx)
        widths.append(w_upper)
        level_offset += n_prev
        n_prev = n_nxt
        done = nxt == 1
        out_map[rows[done]] = level_offset + nxt_starts[done]
        rows, counts, starts = rows[~done], nxt[~done], nxt_starts[~done]

    concat_size = level_offset + n_prev
    out_map = np.where(out_map >= 0, out_map, concat_size)
    return ReducePlan(
        tuple(levels), tuple(widths), n_lvl0, out_map.astype(np.int32),
        n_rows, concat_size,
    )


def _segmented_ranges(starts: np.ndarray, reps: np.ndarray) -> np.ndarray:
    """``concat([arange(s, s + r) for s, r in zip(starts, reps)])`` as two
    cumsums — no ``np.repeat``, which dominated plan-build time at 10M
    scale (VERDICT r4 weak #2). Requires every rep ≥ 1 (both call sites
    filter zero-degree rows first)."""
    total = int(reps.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    starts = np.asarray(starts, dtype=np.int64)
    delta = np.ones(total, dtype=np.int64)
    ends = np.cumsum(reps)
    delta[0] = starts[0]
    if len(starts) > 1:
        delta[ends[:-1]] = starts[1:] - (starts[:-1] + reps[:-1] - 1)
    return np.cumsum(delta)


# ------------------------------------------------------------------ device ops


def _kernel_of(values, red: _Reduction, w: int) -> Optional[str]:
    """Which form of the row-gather kernel serves a class of width ``w``
    over ``values`` where the caller's gathers take the kernel: ``"or"``
    (``hg_gather_or``: ``(S, 128)`` uint32 rows), the reduction's
    ``scalar_op`` (``hg_gather_scalar``: a flat int32 or float32 state), or
    None where the gate declines it (the XLA gather). The one rule
    ``_reduce_classes``, ``_reduce_level`` and ``_scalar_indices`` route
    by; ``values`` needs a shape and a dtype alone."""
    if red is _OR_WORDS:
        return "or" if _pg.declined(w, values.shape[1]) is None else None
    if (red.scalar_op and values.ndim == 1
            and _pg.declined_scalar(w, values.dtype, values.shape[0]) is None):
        return red.scalar_op
    return None


def _reduce_level(
    values: jax.Array,  # (S, Kw) uint32 rows, (S,) int32 or float32
    idx: jax.Array,     # (E,) int32, multiple of w
    w: int,
    chunk: int,
    use_pallas: bool = False,
    red: _Reduction = _OR_WORDS,
) -> jax.Array:
    """gather + fixed-width reduction by ``red``, streamed in ``chunk``-row
    slices to bound the gather transient: returns (E//w,) + a value row's
    shape. A call of ``MIN_INDICES`` or more takes the kernel where
    ``_kernel_of`` names one: the OR's seed words or a flat state's
    scalars, a 128-lane row fetched an index either way."""
    E = idx.shape[0]
    row = values.shape[1:]  # (Kw,) seed words; () a label
    n_out = E // w
    kernel = (_kernel_of(values, red, w)
              if use_pallas and E >= _pg.MIN_INDICES else None)
    if kernel == "or":
        return _pg.gather_or(values, idx, w)
    if kernel:
        return _pg.gather_reduce(values, idx, w, kernel)
    if E <= chunk * w:
        g = values[idx]
        return _fold(g.reshape(n_out, w, *row), red)
    # pad out rows to a multiple of chunk for the scan
    n_blocks = -(-n_out // chunk)
    pad_rows = n_blocks * chunk - n_out
    if pad_rows:
        idx = jnp.concatenate(
            [idx, jnp.zeros((pad_rows * w,), dtype=idx.dtype)]
        )
    idx_b = idx.reshape(n_blocks, chunk * w)

    def body(_, ib):
        g = values[ib]
        return None, _fold(g.reshape(chunk, w, *row), red)

    _, out = jax.lax.scan(body, None, idx_b)
    out = out.reshape(n_blocks * chunk, *row)
    return out[:n_out] if pad_rows else out


def _fold(x: jax.Array, red: _Reduction) -> jax.Array:
    """(R, w, ...) → (R, ...): ``red`` over axis 1 as a log-depth fold; an
    odd width is padded with its identity."""
    w = x.shape[1]
    while w > 1:
        if w % 2:
            x = jnp.concatenate(
                [x, jnp.full_like(x[:, :1], red.identity)], axis=1
            )
            w += 1
        x = red.combine(x[:, 0::2], x[:, 1::2])
        w //= 2
    return x[:, 0]


def _apply_plan(
    values: jax.Array,            # (S, Kw) uint32 rows, (S,) int32 or float32
    levels: Sequence[jax.Array],
    widths: Sequence[int],
    n_lvl0: int,
    chunk: int,
    use_pallas: bool,
    scopes: tuple[str, str],
) -> jax.Array:
    """Run the reduction pyramid; returns the CONCATENATION of every
    level's chunk array plus one global zero row at the end — the address
    space ``ReducePlan.out_map`` (and composed downstream level-0 indices)
    point into.

    The concat buffer is allocated ONCE and level outputs are written into
    their sections by dynamic-update-slice — level 0 class by class, each
    in blocks through a scan whose carry IS the buffer (XLA aliases scan
    carries in place). The old parts-then-concatenate shape held the
    dominant level-0 output alive twice, which at 10M atoms × 4096 seeds
    (512-byte rows) was the difference between ~13 GB peak and
    ResourceExhausted. Upper levels gather FROM the buffer itself with
    host-local indices rebased on device (pad marker ``n_prev`` → the
    global zero row); their outputs are small enough to materialize.

    ``scopes`` names the device operations of (level 0, the upper levels)
    for a profile, as ``jax.named_scope`` components of their ``op_name``.
    The reduction is the values' (``_reduction``, strict): the buffer
    starts as its identity, so the zero row and every padded index read
    it."""
    sizes = [lvl.shape[0] // w for lvl, w in zip(levels, widths)]
    total = sum(sizes) + 1  # + global zero row at index `sum(sizes)`
    lvl0_scope, upper_scope = map(jax.named_scope, scopes)
    red = _reduction(values)
    with lvl0_scope:
        if use_pallas and red.scalar_op and any(
                _kernel_of(values, red, w) for w in widths[:n_lvl0]):
            # the scalar kernel reads a flat table as rows of 128: padded
            # with the identity once here, not at every call, and the
            # buffer (the next stage's table) allocated whole
            values = _pg.scalar_table(values, red.identity)
            total = _ceil_to(total, _pg.G_SCALAR)
        buf = jnp.full((total, *values.shape[1:]), red.identity,
                       dtype=values.dtype)
        buf = _reduce_classes(buf, values, levels[:n_lvl0], widths[:n_lvl0],
                              chunk, use_pallas)
    if n_lvl0 == len(levels):  # no row above the widest class
        return buf
    with upper_scope:
        return _upper_levels(buf, levels[n_lvl0:], widths[n_lvl0:],
                             sizes[n_lvl0 - 1:], sum(sizes[:n_lvl0]), chunk)


def _reduce_classes(buf, values, levels, widths, chunk, use_pallas):
    """Level 0: every width class of ``values`` rows into its section of
    ``buf``, in order from row 0. A scan step moves ``chunk * STEP_WIDTH``
    indices whatever the class — ``chunk`` output rows at width 8, fewer
    wider ones, more narrower ones — so the XLA path's gather transient
    (``chunk * STEP_WIDTH`` rows) does not follow the width; on the kernel
    the whole segments of that (a width that does not divide its segment
    would pad every step otherwise)."""
    off = 0
    for idx, w in zip(levels, widths):
        buf = _reduce_into(buf, off, values, idx, w,
                           _class_rows(values, w, chunk, use_pallas),
                           use_pallas)
        off += idx.shape[0] // w
    return buf


def _class_rows(values, w: int, chunk: int, use_pallas: bool) -> int:
    """Output rows a scan step of a level-0 class of width ``w`` moves (see
    ``_reduce_classes``): ``chunk * STEP_WIDTH`` indices, cut to the whole
    segments of the kernel's form that serves the class."""
    step = chunk * STEP_WIDTH
    kernel = _kernel_of(values, _reduction(values), w) if use_pallas else None
    if kernel == "or":
        step = _pg.whole_segments(step, w)
    elif kernel:
        step = _pg.whole_scalar_steps(step, w)
    return max(1, step // w)


def _scalar_indices(plans: "PullBFSPlans", dtype, chunk: int,
                    use_pallas: bool) -> tuple[int, int]:
    """``(level-0 indices, those the kernel gathers)`` of one program over
    both stages of ``plans`` for a flat ``dtype`` state — what a whole-graph
    operator counts a dispatch (``scalar.gather.indices`` and
    ``.indices_kernel``), from the plan's lengths on the host, by the rule
    the program routes by (``_class_rows``, ``_reduce_into``'s blocks and
    tail, ``_reduce_level``'s ``MIN_INDICES``), over each stage's table:
    the state, then stage 1's buffer."""
    s1 = plans.stage1
    total = kernel = 0
    for table, levels, widths, n in (
            (plans.n_pad, s1.levels, s1.widths, s1.n_lvl0),
            (s1.concat_size + 1, plans.stage2_levels, plans.stage2_widths,
             plans.stage2_n_lvl0)):
        values = jax.ShapeDtypeStruct((table,), dtype)
        red = _reduction(values)
        for idx, w in zip(levels[:n], widths[:n]):
            E = len(idx)
            total += E
            if not (use_pallas and _kernel_of(values, red, w)):
                continue
            rows = _class_rows(values, w, chunk, use_pallas)
            n_out = E // w
            block, tail = rows * w, (n_out - n_out // rows * rows) * w
            kernel += ((n_out // rows) * block if block >= _pg.MIN_INDICES
                       else 0) + (tail if tail >= _pg.MIN_INDICES else 0)
    return total, kernel


def _upper_levels(
    buf: jax.Array,
    levels: Sequence[jax.Array],
    widths: Sequence[int],
    sizes: Sequence[int],
    off: int,
    chunk: int,
) -> jax.Array:
    """Run the upper levels of a pyramid over a concat buffer whose level-0
    sections are already in place. ``sizes`` lists the chunk counts of
    level 0's LAST class (the one the cut rows' chunks lie in) and of
    every upper level; ``off`` is the first upper section's offset; the
    global zero row sits at ``buf.shape[0] - 1``. Prev-level-local indices
    are rebased into buffer space on device (pad marker ``len(prev)`` →
    the global zero row). Upper levels stay on the XLA gather: since level
    0 has width classes they hold the rows above ``W_MAX`` alone — 0.16M
    indices a hop in the untyped 10M-atom cell, none in its stage 1, where
    one width-8 level 0 left them 30.9M and 29% of the traversal
    (``traverse_dev_s.upper`` 2.18 s; PERF.md section 6, PR 30)."""
    total = buf.shape[0]
    for i, (idx, w) in enumerate(zip(levels, widths)):
        n_prev = sizes[i]
        prev_off = off - n_prev
        idx_g = jnp.where(
            idx == n_prev, total - 1, idx + prev_off
        ).astype(idx.dtype)
        # gather FROM and write INTO the one buffer (sections are
        # disjoint: a level reads its predecessor's and writes its own) —
        # a separate level output is a padded scan result plus its trimmed
        # copy, ~2 GB of temps at 10M atoms x 4096 seeds that the hop's
        # widest step has no room for
        buf = _reduce_into(buf, off, None, idx_g, w, chunk, False)
        off += sizes[i + 1]
    return buf


def _reduce_into(
    buf: jax.Array,
    off: int,
    values: jax.Array,
    idx: jax.Array,
    w: int,
    chunk: int,
    use_pallas: bool,
) -> jax.Array:
    """Reduce ``values`` rows over ``idx`` groups of ``w`` by the buffer's
    reduction (``_reduction``, strict), writing the
    ``len(idx)//w`` output rows into ``buf[off:]`` in place: full blocks of
    ``chunk`` outputs stream through a scan (carry = buf, aliased by XLA),
    the ragged tail lands with one final update. ``values=None`` gathers
    from ``buf`` itself (the rows read must lie outside ``buf[off:]``).

    The sum slices a block's indices out of ``idx`` in the loop's body;
    the OR and the min scan over ``idx`` reshaped to ``(blocks, chunk *
    w)``, which the TPU compiler lays out anew in loops of its own that
    carry no ``op_name`` — at the untyped plan's classes 80M indices an
    iteration (PERF.md section 6) — and keep the lowered text their
    programs had."""
    red = _reduction(buf)
    E = idx.shape[0]
    n_out = E // w
    n_full = n_out // chunk
    if n_full:
        step = chunk * w
        if red is _SUM_FLOATS:
            def block(i):
                return jax.lax.dynamic_slice_in_dim(idx, i * step, step), i

            xs = jnp.arange(n_full, dtype=jnp.int32)
        else:
            def block(ib_i):
                return ib_i

            xs = (idx[: n_full * step].reshape(n_full, step),
                  jnp.arange(n_full, dtype=jnp.int32))

        def body(b, x):
            ib, i = block(x)
            out = _reduce_level(b if values is None else values, ib, w,
                                chunk, use_pallas, red)
            return jax.lax.dynamic_update_slice(
                b, out, _at(off + i * chunk, b)
            ), None

        buf, _ = jax.lax.scan(body, buf, xs)
    tail = n_out - n_full * chunk
    if tail:
        out = _reduce_level(
            buf if values is None else values,
            idx[n_full * chunk * w :], w, chunk, use_pallas, red
        )
        buf = jax.lax.dynamic_update_slice(
            buf, out, _at(off + n_full * chunk, buf)
        )
    return buf


class PullBFSResult(NamedTuple):
    visited_t: jax.Array      # (N_pad, Kw) uint32 — TRANSPOSED packed bitmaps
    edges_touched: np.ndarray  # (K,) int64 — summed over hops on host
    reach_counts: jax.Array   # (K,) int32 — |visited| per seed (incl. seed)


@dataclass
class PullBFSPlans:
    """Host-side precompute for :func:`bfs_pull` over one snapshot.

    Expensive to build (two padded index pyramids + a composed link map)
    but reusable across every BFS on the snapshot; cached on the snapshot
    object by :func:`plans_for`.
    """

    n_atoms: int
    n_pad: int
    stage1: ReducePlan  # tgt relation: link rows ← atom value rows
    stage2_levels: tuple[np.ndarray, ...]  # level 0 composed into stage1 chunks
    stage2_widths: tuple[int, ...]
    stage2_n_lvl0: int  # leading stage-2 levels that are level-0 classes
    out_map: np.ndarray
    inc_deg: np.ndarray  # (N_pad,) int32 — incidence degree (edge counting)

    @property
    def total_indices(self) -> int:
        return (
            self.stage1.total_indices
            + int(sum(len(l) for l in self.stage2_levels))
            + len(self.out_map)
        )

    @property
    def upper_indices(self) -> int:
        """Indices of both stages' upper pyramid levels: what a hop still
        gathers for rows that did not finish where they were gathered."""
        return self.stage1.upper_indices + int(
            sum(len(l) for l in self.stage2_levels[self.stage2_n_lvl0:]))


def _n_pad(n_atoms: int) -> int:
    """Rows of a bitmap over ``n_atoms`` atoms and the dummy row."""
    return _ceil_to(n_atoms + 1, 8)


def build_pull_plans(snap: CSRSnapshot, w_upper: int = 8) -> PullBFSPlans:
    N = snap.num_atoms
    n_pad = _n_pad(N)
    e_tgt = snap.n_edges_tgt
    e_inc = snap.n_edges_inc
    # stage 1: link_live = OR of F over target rows (tgt CSR, rows=atoms)
    s1 = build_reduce_plan(
        snap.tgt_offsets[: N + 2], snap.tgt_flat[:e_tgt], N + 1,
        zero_row=N, w_upper=w_upper,
    )
    # stage 2 runs over the incidence CSR; its level-0 entries are LINK ids.
    # Compose them through stage-1's concat-space out_map on host, so the
    # hop consumes stage-1 chunks directly — no per-link destination array
    # is ever materialized.
    s2 = build_reduce_plan(
        snap.inc_offsets[: N + 2], snap.inc_links[:e_inc], N + 1,
        zero_row=N, w_upper=w_upper,
    )
    # level-0 padding used zero_row=N (an atom id); atom N has no targets →
    # its out_map entry is stage-1's zero row. Non-link atoms likewise.
    s2_levels = tuple(s1.out_map[lvl] for lvl in s2.levels[: s2.n_lvl0]) \
        + s2.levels[s2.n_lvl0:]

    out_map = np.full(n_pad, s2.concat_size, dtype=np.int32)
    out_map[: N + 1] = s2.out_map
    out_map[N] = s2.concat_size  # dummy row must stay empty
    inc_deg = np.zeros(n_pad, dtype=np.int32)
    inc_deg[: N + 1] = (
        snap.inc_offsets[1 : N + 2].astype(np.int64)
        - snap.inc_offsets[: N + 1]
    ).astype(np.int32)
    inc_deg[N] = 0
    return PullBFSPlans(
        n_atoms=N,
        n_pad=n_pad,
        stage1=s1,
        stage2_levels=s2_levels,
        stage2_widths=s2.widths,
        stage2_n_lvl0=s2.n_lvl0,
        out_map=out_map,
        inc_deg=inc_deg,
    )


# 2: level 0 in width classes (``n_lvl0``); a format-1 sidecar is stale
PLAN_FORMAT = 2


class StalePlans(ValueError):
    """The sidecar is WELL-FORMED but belongs to a different snapshot or
    plan format — the quiet-rebuild case loaders treat as "no sidecar",
    deliberately distinct from a corrupt/unreadable file (which
    ``load_snapshot`` logs and counts as ``fault.sidecar_corrupt``)."""


def save_plans(plans: PullBFSPlans, path, fingerprint: str = "") -> None:
    """Persist a plan pyramid as an .npz (uncompressed — load speed is the
    point: rebuilding at 10M scale costs ~15 s of host cumsums, loading
    costs one sequential read). ``path`` may be an open binary file
    object (the crash-atomic checkpoint writer hands in its tmp file).
    ``fingerprint`` (see :func:`snapshot_fingerprint`) travels with the
    file so loaders can reject a sidecar that no longer matches its
    snapshot."""
    arrs: dict = {
        "fingerprint": np.frombuffer(
            fingerprint.encode("ascii"), dtype=np.uint8
        ),
        "format": np.int64(PLAN_FORMAT),
        "n_atoms": np.int64(plans.n_atoms),
        "n_pad": np.int64(plans.n_pad),
        "s1_widths": np.asarray(plans.stage1.widths, np.int64),
        "s1_n_lvl0": np.int64(plans.stage1.n_lvl0),
        "s1_out_map": plans.stage1.out_map,
        "s1_n_rows": np.int64(plans.stage1.n_rows),
        "s1_concat": np.int64(plans.stage1.concat_size),
        "s2_widths": np.asarray(plans.stage2_widths, np.int64),
        "s2_n_lvl0": np.int64(plans.stage2_n_lvl0),
        "out_map": plans.out_map,
        "inc_deg": plans.inc_deg,
    }
    for i, lvl in enumerate(plans.stage1.levels):
        arrs[f"s1_l{i}"] = lvl
    for i, lvl in enumerate(plans.stage2_levels):
        arrs[f"s2_l{i}"] = lvl
    np.savez(path, **arrs)


def load_plans(path: str,
               expect_fingerprint: Optional[str] = None) -> PullBFSPlans:
    with np.load(path) as z:
        if int(z["format"]) != PLAN_FORMAT:
            raise StalePlans(
                f"plan file {path}: format {int(z['format'])} != "
                f"{PLAN_FORMAT}"
            )
        if expect_fingerprint is not None:
            got = bytes(z["fingerprint"]).decode("ascii") \
                if "fingerprint" in z else ""
            if got != expect_fingerprint:
                raise StalePlans(
                    f"plan file {path}: fingerprint {got!r} does not match "
                    f"the snapshot ({expect_fingerprint!r}) — stale sidecar"
                )
        s1_levels = tuple(
            z[k] for k in sorted(
                (k for k in z.files if k.startswith("s1_l")),
                key=lambda k: int(k[4:]),
            )
        )
        s2_levels = tuple(
            z[k] for k in sorted(
                (k for k in z.files if k.startswith("s2_l")),
                key=lambda k: int(k[4:]),
            )
        )
        s1 = ReducePlan(
            s1_levels, tuple(int(w) for w in z["s1_widths"]),
            int(z["s1_n_lvl0"]), z["s1_out_map"], int(z["s1_n_rows"]),
            int(z["s1_concat"]),
        )
        return PullBFSPlans(
            n_atoms=int(z["n_atoms"]),
            n_pad=int(z["n_pad"]),
            stage1=s1,
            stage2_levels=s2_levels,
            stage2_widths=tuple(int(w) for w in z["s2_widths"]),
            stage2_n_lvl0=int(z["s2_n_lvl0"]),
            out_map=z["out_map"],
            inc_deg=z["inc_deg"],
        )


def snapshot_fingerprint(snap: CSRSnapshot) -> str:
    """Content key over the structural CSR arrays — two snapshots with the
    same fingerprint have identical plans."""
    import zlib

    h = 0
    for a in (
        snap.tgt_offsets, snap.tgt_flat[: snap.n_edges_tgt],
        snap.inc_offsets, snap.inc_links[: snap.n_edges_inc],
    ):
        h = zlib.crc32(np.ascontiguousarray(a).view(np.uint8), h)
    return (f"{snap.num_atoms}_{snap.n_edges_tgt}_"
            f"{snap.n_edges_inc}_{h:08x}")


def plans_for(snap: CSRSnapshot) -> PullBFSPlans:
    """Plans for a snapshot: memoized on the snapshot object, and — when
    ``HG_PLAN_CACHE`` names a directory — persisted there keyed by the
    snapshot's content fingerprint, so repeated sessions over the same
    graph (the benchmark's warm runs, a reopened store) skip the ~15 s
    10M-scale rebuild entirely."""
    plans = getattr(snap, "_pull_plans", None)
    if plans is None:
        with phase("hg.bfs.plan"):
            plans = _build_or_load_plans(snap)
        object.__setattr__(snap, "_pull_plans", plans)
        # the newest plan's size beside the entries it covers: their ratio
        # is the padding a hop's gathers pay for every entry
        reg = default_registry()
        reg.gauge("bfs.plan.total_indices").set(plans.total_indices)
        # of which the upper pyramid levels': rows that did not finish in
        # the chunk they were gathered in
        reg.gauge("bfs.plan.upper_indices").set(plans.upper_indices)
        reg.gauge("bfs.plan.entries").set(snap.n_edges_inc
                                          + snap.n_edges_tgt)
    return plans


#: Families a parent keeps restricted at once: the least recently used goes
#: first. A constant, not an option: a 3-step match and a typed traversal
#: beside it are four, and never evict each other.
RESTRICT_RESIDENT = 4


def restricted_for(snap: CSRSnapshot, link_types) -> CSRSnapshot:
    """The snapshot a traversal under a link predicate runs over
    (``CSRSnapshot.restrict_links``), with its plan: built under phase
    ``hg.bfs.restrict`` and kept on the parent, as :func:`plans_for` keeps
    a plan — the ``RESTRICT_RESIDENT`` families used last. Past that the
    least recently used one is let go (counter ``bfs.restrict.evictions``):
    its host arrays, its plan and its ``_pull_device`` arrays hang on the
    restricted snapshot alone, so they are freed with the parent's
    reference unless a running traversal still holds them; a family that
    comes back is rebuilt and answers the same. A hit records nothing."""
    family = frozenset(int(t) for t in link_types)
    memo = vars(snap).setdefault("_pull_restricted", {})
    sub = memo.pop(family, None)
    if sub is None:
        with phase("hg.bfs.restrict"):
            sub = snap.restrict_links(family)
            plans_for(sub)
        reg = default_registry()
        while len(memo) >= RESTRICT_RESIDENT:
            del memo[next(iter(memo))]  # insertion order: the oldest use
            reg.counter("bfs.restrict.evictions").inc()
        reg.gauge("bfs.restrict.resident").set(len(memo) + 1)
    memo[family] = sub  # the newest use last
    return sub


def _build_or_load_plans(snap: CSRSnapshot) -> PullBFSPlans:
    cache_dir = os.environ.get("HG_PLAN_CACHE")
    cache_path = None
    fp = None
    plans = None
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        fp = snapshot_fingerprint(snap)
        cache_path = os.path.join(cache_dir, f"pullplans_{fp}.npz")
        if os.path.exists(cache_path):
            try:
                plans = load_plans(cache_path, expect_fingerprint=fp)
            except Exception:
                plans = None  # stale/corrupt cache entry → rebuild
    if plans is None:
        plans = build_pull_plans(snap)
        if cache_path is not None:
            # .npz suffix keeps np.savez from appending another one;
            # write-then-rename = no torn cache entries
            tmp = cache_path[:-4] + ".tmp.npz"
            save_plans(plans, tmp, fingerprint=fp)
            os.replace(tmp, cache_path)
    return plans


# ------------------------------------------------------------------ kernel


def _program(module: str, scope: Optional[str] = None):
    """Names that survive a refactor, for one stage program (the innermost
    decorator, under ``jax.jit``). ``module``: the XLA module is named
    ``jit_<module>`` — JAX takes it from ``__name__``; ``__qualname__``
    stays the Python attribute's, which the hgverify registry keys by — so
    a profile's ``XLA Modules`` line and the compile-cache key read by
    stage. ``scope``: a ``jax.named_scope`` over the whole body, a
    component of every operation's ``op_name``, which is how a profile's
    device operations are told apart by stage whatever the compiler calls
    them. ``PERF.md`` section 3 lists the names and their readers."""

    def deco(fn):
        if scope is not None:
            fn = jax.named_scope(scope)(fn)
        fn.__name__ = module
        return fn

    return deco


# The hop runs as FOUR host-sequenced jits instead of one scan. At 10M
# atoms × 4096 seeds the hop's working set (visited 5.1 GB + stage-1
# buffer 4.1 GB + stage-2 buffer 1.1 GB; 5.9 and 4.6 GB before level 0
# had width classes, which is when this was measured) only fits the 16 GiB
# HBM when buffers are freed/reused the moment they are dead — a lax.scan
# keeps the carry double-buffered and every intermediate alive for the
# compiler's conservative lifetime, which measured 21 GB of temps
# (ResourceExhausted).
# Host sequencing + donate_argnums makes each free explicit; dispatch cost
# is a few RTTs per hop, noise against multi-second hops.
#
# Hops pull from VISITED, not from a separate frontier array: the closure
# is monotone (visited_h ∪ N(visited_h) = visited_h ∪ N(frontier_h) =
# visited_{h+1}), so pulling the superset reaches the identical per-hop
# visited sets while carrying HALF the state. Per-hop frontier edge counts
# fall out as differences of S_h = Σ_v visited_h[v]·deg(v): frontiers
# partition visited, so Σdeg(frontier_h) = S_h − S_{h-1}.


@hgverify.entry(
    shapes=lambda: (hgverify.sds((32,), "int32"),
                    hgverify.sds((), "int32")),
    statics={"n_pad": 64},
)
@partial(jax.jit, static_argnames=("n_pad",))
@_program("hg_bfs_seed_bitmap", "hg.bfs.seed_bitmap")
def _seed_bitmap(seeds: jax.Array, n_atoms: jax.Array, n_pad: int):
    K = seeds.shape[0]
    Kw = K // WORD
    # bit k of V[seeds[k]] — per-k bits are distinct, so scatter-add over
    # (possibly duplicate) seed rows equals bitwise OR
    k = jnp.arange(K, dtype=jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), (k & 31).astype(jnp.uint32))
    onehot = jnp.zeros((K, Kw), dtype=jnp.uint32).at[k, k >> 5].set(bit)
    visited = jnp.zeros((n_pad, Kw), dtype=jnp.uint32).at[seeds].add(onehot)
    return visited.at[n_atoms].set(jnp.uint32(0))  # dummy row stays zero


@hgverify.entry(
    shapes=lambda: (hgverify.sds((64, 1), "uint32"),
                    (hgverify.sds((32,), "int32"),
                     hgverify.sds((64,), "int32"))),
    statics={"widths": (2, 8), "n_lvl0": 2, "chunk": 1 << 19,
             "use_pallas": False},
)
@partial(jax.jit,
         static_argnames=("widths", "n_lvl0", "chunk", "use_pallas"))
@_program("hg_bfs_stage1")
def _stage(values, levels, widths, n_lvl0, chunk, use_pallas):
    return _apply_plan(values, levels, widths, n_lvl0, chunk, use_pallas,
                       scopes=("hg.bfs.stage1.lvl0", "hg.bfs.stage1.upper"))


@partial(jax.jit, static_argnames=("widths", "chunk", "use_pallas"))
@_program("hg_bfs_stage2_lvl0", "hg.bfs.stage2.lvl0")
def _stage_lvl0_consume(values, levels, widths, chunk, use_pallas):
    """Level-0 chunks only (every width class), into an exact-size buffer.
    ``values`` (the previous stage's buffer, ~4.1 GB at benchmark scale)
    is genuinely dead once this jit returns; the caller drops its ref and
    syncs — splitting stage 2 here is what lets that buffer free before
    the full concat buffer allocates. (No donate: the shapes can never
    alias, donation would only warn.)"""
    n0 = sum(idx.shape[0] // w for idx, w in zip(levels, widths))
    buf = jnp.zeros((n0, values.shape[1]), dtype=values.dtype)
    return _reduce_classes(buf, values, levels, widths, chunk, use_pallas)


@partial(jax.jit, static_argnames=("widths", "n_last", "chunk"))
@_program("hg_bfs_stage2_upper", "hg.bfs.stage2.upper")
def _stage_upper(lvl0, levels, widths, n_last, chunk):
    """Assemble the stage's concat buffer from the level-0 chunks, then
    run the (small) upper levels on the XLA gather path. ``levels`` and
    ``widths`` are the upper levels' alone (none where no row is above
    ``W_MAX``: the buffer is then level 0 and the zero row); ``n_last``
    is the chunk count of level 0's last class, whose section the first
    upper level reads."""
    n0, Kw = lvl0.shape
    sizes = [n_last] + [lvl.shape[0] // w for lvl, w in zip(levels, widths)]
    total = n0 + sum(sizes[1:]) + 1
    buf = jnp.zeros((total, Kw), dtype=lvl0.dtype)
    buf = jax.lax.dynamic_update_slice(buf, lvl0, (0, 0))
    return _upper_levels(buf, levels, widths, sizes, n0, chunk)


#: Rows of the bitmap a step of an update's loop folds, and the grain at
#: which a plan says which rows a hop can reach (``_active_blocks``). A
#: constant, not a knob: read on the chip at the 10M-atom cells' shapes
#: (``benchmarks/tests/update_blocks_probe.py``; PERF.md section 6, PR 32).
#: A pass over the cells' listed fifth took 23.3 / 23.2 / 23.1 / 27.5 /
#: 32.3 ms at 2^14 … 2^18 rows a block, and with EVERY block listed 166 /
#: 165 / 165 / 181 / 211 ms where the counted loop of 2^18-row blocks it
#: replaces took 183: flat up to 2^16, the fewest trips among the flat.
UPDATE_ROWS = 1 << 16


class _UpdateRows(NamedTuple):
    """What an update is handed of a plan, as one argument: where each row
    of the bitmap reads the stage buffer, and the row blocks worth folding.
    The list has one slot a block of the bitmap, so a program's shapes
    follow ``n_pad`` alone and never the graph."""

    out_map: jax.Array   # (n_pad,) int32 into reach_chunks, → its zero row
    starts: jax.Array    # (blocks,) int32: the listed blocks' first rows
    n_listed: jax.Array  # () int32: what follows them in starts is unread


def _block_rows(n_pad: int, block_rows: int = UPDATE_ROWS) -> int:
    return min(block_rows, n_pad)


def _active_blocks(plans: PullBFSPlans) -> np.ndarray:
    """``(blocks,) bool``: the row blocks of the bitmap in which a hop over
    ``plans`` can reach a row at all — some ``out_map[v]`` is not the stage
    buffer's zero row, which is where the dummy row always points. A row
    is reached only if its atom has an incidence set (under the plan's
    link predicate), so in a store that lays entities out before links
    these are the entities' blocks, a fifth of DBpedia's shape."""
    reached = plans.out_map != plans.out_map[plans.n_atoms]
    return np.logical_or.reduceat(
        reached, np.arange(0, plans.n_pad, _block_rows(plans.n_pad)))


def _blocks_of(rows: np.ndarray, n_pad: int) -> np.ndarray:
    """``(blocks,) bool``: the row blocks these rows of the bitmap lie in."""
    ub = _block_rows(n_pad)
    blocks = np.zeros(-(-n_pad // ub), dtype=bool)
    blocks[np.asarray(rows, dtype=np.int64) // ub] = True
    return blocks


def _block_starts(
    blocks: np.ndarray, n_pad: int, block_rows: int = UPDATE_ROWS,
) -> tuple[jax.Array, jax.Array]:
    """The row blocks ``blocks`` marks as a loop reads them, on the device:
    ``(blocks,) int32`` with the marked blocks' first rows first, and how
    many they are."""
    first = np.flatnonzero(blocks) * _block_rows(n_pad, block_rows)
    starts = np.zeros(len(blocks), dtype=np.int32)
    starts[: len(first)] = first
    return jnp.asarray(starts), jnp.asarray(np.int32(len(first)))


def _listed(out_map: jax.Array, blocks: np.ndarray,
            block_rows: int = UPDATE_ROWS) -> _UpdateRows:
    """The update's argument for ``out_map`` over the row blocks ``blocks``
    marks."""
    return _UpdateRows(
        out_map, *_block_starts(blocks, out_map.shape[0], block_rows))


class _Gain(NamedTuple):
    """What a fold counts beside the state, from the two operands it holds
    anyway (the state's block and what the stage buffer sends it): no pass
    of its own. ``init(row)`` starts the count, a value row's shape given;
    ``step(count, cur, reached)`` adds a block's."""

    init: Callable
    step: Callable


#: The columns that GREW (``_ball_update``): ``(Kw,) uint32``, the OR over
#: the folded rows of the bits the stage buffer holds and the state did not
#: — bit k of word w says column ``32 w + k`` gained a row.
_GREW = _Gain(lambda row: jnp.zeros(row, jnp.uint32),
              lambda new, cur, reached: new | _or_rows(reached & ~cur))

#: The rows a round LOWERED (``_wcc_round``): ``() int32``, the folded rows
#: whose label the buffer's is below. A row folded twice (the ragged last
#: block) is lowered the first time only.
_LOWERED = _Gain(lambda row: jnp.int32(0),
                 lambda n, cur, reached: n + jnp.sum(reached < cur,
                                                     dtype=jnp.int32))


def _update_on_kernel(state, use_pallas: bool,
                      block_rows: int = UPDATE_ROWS,
                      table=None) -> Optional[str]:
    """Which form of the row-gather kernel ``_fold_rows`` fetches a block's
    rows through at width 1, or None for the XLA gather. Where the
    caller's gathers take the kernel (``use_pallas``: a 4096-seed block or
    a whole-graph operator on a TPU) and a block is as many indices as the
    kernel is worth (``_reduce_level``'s rule), ``_kernel_of`` decides over
    the TABLE the fetch reads — the stage buffer ``table``, the state
    where none is given — by the state's reduction: ``"or"``
    (``hg_gather_or``) for ``(S, 128)`` uint32 rows, the reduction's
    ``scalar_op`` (``hg_gather_scalar``) for a flat int32 or float32 state
    whose table the scalar gate admits. A narrower bitmap, a table past
    ``SCALAR_TABLE_BYTES`` and a short block keep the XLA gather. Only
    shapes and dtypes are read: the host counts by it."""
    if not (use_pallas
            and _block_rows(state.shape[0], block_rows) >= _pg.MIN_INDICES):
        return None
    return _kernel_of(state if table is None else table, _reduction(state),
                      1)


def _fold_rows(state, reach_chunks, rows: _UpdateRows, n_atoms, combine,
               block_rows: int = UPDATE_ROWS, gain: Optional[_Gain] = None,
               use_pallas: bool = False):
    """``state[v] = combine(state[v], reach_chunks[out_map[v]])`` for every
    row of the LISTED row blocks and no other, folded a block at a time so
    no second state array materializes while the stage buffer is alive
    (the loop's carry aliases in place, whatever its trip count); the
    dummy row (``n_atoms``) is set to the state's identity (``_reduction``:
    a bitmap's 0, a label's ``INT32_MAX``, a sum's 0.0) last. The state's
    ragged last block is folded from ``n_pad - block_rows``: the rows it
    shares with the block before are folded twice in one pass at most, so
    ``combine`` must give the same row both times — the OR, the min and a
    replacement (``lambda cur, reached: reached``) do; an accumulating add
    would count those rows twice, so a sum (:func:`pagerank`) is folded by
    replacement into a state that starts at zero. The state is
    ``(n_pad, Kw)`` uint32 words, ``(n_pad,)`` int32 labels or
    ``(n_pad,)`` float32 sums.

    ``gain``: also return what it counts over the folded rows (``_GREW``,
    ``_LOWERED``). Both operands are at hand in the fold, so it costs no
    pass of its own; without it the carry is the state alone and the
    program is what it was.

    A block's rows are fetched by the row-gather kernel at width 1 (one
    call of ``block_rows`` indices) where ``_update_on_kernel`` names a
    form: ``hg_gather_or`` for a bitmap, ``hg_gather_scalar`` for a flat
    state, whose stage buffer it reads as rows of 128 — whole (8, 128)
    tiles where the stage's pyramid ran on the kernel, else padded here
    once, outside the loop; elsewhere through the XLA gather. The combine
    is XLA's either way."""
    n_pad, row = state.shape[0], state.shape[1:]
    identity = _reduction(state).identity
    ub = _block_rows(n_pad, block_rows)
    kernel = _update_on_kernel(state, use_pallas, block_rows, reach_chunks)
    if kernel and kernel != "or":
        reach_chunks = _pg.scalar_table(reach_chunks, identity)

    def fetch(sl):
        if kernel == "or":
            return _pg.gather_or(reach_chunks, sl, 1)
        if kernel:
            return _pg.gather_reduce(reach_chunks, sl, 1, kernel)
        return reach_chunks[sl]

    def fold(i, carry):
        nxt, count = carry
        start = jnp.minimum(rows.starts[i], n_pad - ub)
        cur = jax.lax.dynamic_slice(nxt, _at(start, nxt), (ub, *row))
        sl = jax.lax.dynamic_slice(rows.out_map, (start,), (ub,))
        reached = fetch(sl)
        if gain is not None:
            count = gain.step(count, cur, reached)
        return jax.lax.dynamic_update_slice(
            nxt, combine(cur, reached), _at(start, nxt)
        ), count

    nxt, count = jax.lax.fori_loop(
        0, rows.n_listed, fold,
        (state, None if gain is None else gain.init(row)))
    nxt = nxt.at[n_atoms].set(
        jnp.asarray(identity, state.dtype))
    return nxt if gain is None else (nxt, count)


def _or_rows(x: jax.Array) -> jax.Array:
    """``(R, Kw) → (Kw,)``: the OR of the rows, words still packed."""
    return jax.lax.reduce(x, jnp.uint32(0), jax.lax.bitwise_or, (0,))


def _update_shapes():
    return (hgverify.sds((64, 1), "uint32"), hgverify.sds((9, 1), "uint32"),
            _UpdateRows(hgverify.sds((64,), "int32"),
                        hgverify.sds((1,), "int32"),
                        hgverify.sds((), "int32")),
            hgverify.sds((), "int32"))


@hgverify.entry(shapes=_update_shapes, donate=True)
@partial(jax.jit, static_argnames=("use_pallas",),
         donate_argnums=(0,))  # visited aliases the output
@_program("hg_bfs_visited_update", "hg.bfs.visited_update")
def _visited_update(visited, reach_chunks, rows, n_atoms, use_pallas=False):
    """A traversal's hop ends here: visited | reach_chunks[out_map], over
    the hop's plan's active blocks (``rows``). A row outside them would OR
    in the zero row: what it holds — a seed's own bit, an earlier hop's
    bits — is left where it is. ``use_pallas``: the stage programs'
    (``_fold_rows`` fetches on the kernel where it serves the state)."""
    return _fold_rows(visited, reach_chunks, rows, n_atoms,
                      lambda cur, reached: cur | reached,
                      use_pallas=use_pallas)


@hgverify.entry(shapes=_update_shapes, donate=True)
@partial(jax.jit, static_argnames=("use_pallas",),
         donate_argnums=(0,))  # the old frontier's buffer is reused
@_program("hg_bfs_frontier_replace", "hg.bfs.frontier_replace")
def _frontier_replace(frontier, reach_chunks, rows, n_atoms,
                      use_pallas=False):
    """A match's step ends here: the new state IS reach_chunks[out_map],
    written into the donated old frontier, of which no bit is read.

    Invariant: every row outside the new frontier is zero on the way out,
    PROVIDED ``rows`` lists every block in which the old frontier holds a
    bit beside the step's plan's active blocks (``_bfs_pull_device`` keeps
    that account). Inside a listed block a row nothing reaches reads the
    zero row and is cleared; an unlisted block is neither read nor
    written, and was zero. ``use_pallas`` as ``_visited_update``'s."""
    return _fold_rows(frontier, reach_chunks, rows, n_atoms,
                      lambda cur, reached: reached, use_pallas=use_pallas)


@hgverify.entry(shapes=_update_shapes, donate=True)
@partial(jax.jit, static_argnames=("use_pallas",),
         donate_argnums=(0,))  # the ball aliases the output
@_program("hg_bfs_ball_update", "hg.bfs.visited_update")
def _ball_update(ball, reach_chunks, rows, n_atoms, use_pallas=False):
    """A pair search's expansion ends here: ``_visited_update`` (the same
    fold, under the same scope), and beside the grown ball the columns that
    GREW, ``(Kw,) uint32`` — a column that did not has its whole component
    (:func:`pair_distances`' exhaustion). A program of its own because the
    traversal's update returns the bitmap alone and is left as it is.
    ``use_pallas`` as ``_visited_update``'s."""
    return _fold_rows(ball, reach_chunks, rows, n_atoms,
                      lambda cur, reached: cur | reached, gain=_GREW,
                      use_pallas=use_pallas)


#: Rows of the two bitmaps a step of the meet test's loop folds: one AND
#: and one OR-fold the compiler fuses over the slices, nothing written but
#: a row of words. Blocked as ``_bitdot`` is, for the CPU backend's sake
#: (whole, it would hold ``fwd & bwd``, a third bitmap).
MEET_ROWS = 1 << 16


def _meet_words(fwd: jax.Array, bwd: jax.Array,
                block_rows: int = MEET_ROWS) -> jax.Array:
    n_pad, Kw = fwd.shape
    block_rows = min(block_rows, n_pad)

    # the last block's clamped start overlaps the block before (the
    # pattern of ``_bitdot``): OR takes a row twice and says the same
    def body(i, acc):
        start = jnp.minimum(i * block_rows, n_pad - block_rows)
        f = jax.lax.dynamic_slice(fwd, (start, 0), (block_rows, Kw))
        b = jax.lax.dynamic_slice(bwd, (start, 0), (block_rows, Kw))
        return acc | _or_rows(f & b)

    return jax.lax.fori_loop(0, -(-n_pad // block_rows), body,
                             jnp.zeros((Kw,), jnp.uint32))


@hgverify.entry(shapes=lambda: (hgverify.sds((64, 1), "uint32"),
                                hgverify.sds((64, 1), "uint32")))
@jax.jit
@_program("hg_bfs_meet", "hg.bfs.meet")
def _meet(fwd: jax.Array, bwd: jax.Array) -> jax.Array:
    """The meet test of a two-sided search: ``(n_pad, Kw)`` twice →
    ``(Kw,) uint32``, word w = OR over the rows of ``fwd[r, w] & bwd[r,
    w]`` — bit k of word w says the two balls of column ``32 w + k`` share
    an atom. Bit-exact, nothing unpacked, nothing donated: the host reads
    ``4 Kw`` bytes and looks at bits. Every row is folded (bound by bytes,
    both bitmaps read once); a row outside the plan's active blocks holds
    a seed's own bit at most, so folding those blocks alone would do
    beside the host's ``s == t`` — PERF.md section 7 (PR 33) has why it
    is not taken."""
    return _meet_words(fwd, bwd)


# Pairs a placement dispatch carries: the one shape `_sparse_hop` compiles at
# for a bitmap, whatever the seeds hold — a hub among them runs more blocks.
SPARSE_BLOCK = 1 << 20


@hgverify.entry(
    shapes=lambda: (hgverify.sds((64, 1), "uint32"),
                    hgverify.sds((2, 16), "int32"),
                    hgverify.sds((), "int32")),
    donate=True,
)
@partial(jax.jit, donate_argnums=(0,))  # visited aliases the output
@_program("hg_bfs_sparse_hop", "hg.bfs.sparse_hop")
def _sparse_hop(visited, pairs, n_atoms):
    """OR bit ``k`` into row ``r`` for every column ``(r, k)`` of ``pairs``.
    The pairs are distinct and none is a seed's own bit, so no bit is added
    twice and the scatter-add IS the OR (the trick of ``_seed_bitmap``).
    Pad columns are ``(n_atoms, 0)``: the dummy row, zeroed last.

    On the TPU the whole block in ONE scatter is the fast form: XLA sorts
    the indices and streams the bitmap once (25 ms at 10M atoms x 4096
    seeds whatever the block holds; sixteen narrower scatters measured
    97 ms). It runs inside a loop of one trip that the compiler cannot
    count, for the profile's sake: the TPU's scatter expansion leaves its
    sort and its fusion without an ``op_name``, and a profile charges
    nameless operations to the scope of the loop they run in — bare, they
    stand outside ``hg.bfs.sparse_hop`` (PERF.md section 6, PR 26)."""
    rows, ks = pairs[0], pairs[1]
    bit = jnp.left_shift(jnp.uint32(1), (ks & 31).astype(jnp.uint32))
    visited = jax.lax.fori_loop(
        0, jnp.minimum(n_atoms, 1),
        lambda _, vis: vis.at[rows, ks >> 5].add(bit), visited)
    return visited.at[n_atoms].set(jnp.uint32(0))


def _bitdot(packed_t: jax.Array, vec: jax.Array, starts: jax.Array,
            n_listed: jax.Array, block_rows: int = UPDATE_ROWS) -> jax.Array:
    """Σ_v vec[v] · bit(v, k) for every seed column k, exactly, over the
    rows of the LISTED row blocks and no other: ``starts[:n_listed]`` are
    their first rows (the list an update is handed, ``_UpdateRows``: a slot
    a block of the bitmap, so the program's shapes follow ``R`` alone), and
    the caller lists every block in which the state can hold a bit.

    ``packed_t (R, Kw) uint32``, ``vec (R,) int32`` → ``(K,) int32``; the
    caller bounds the sums below 2^31. The bits are unpacked shift-major,
    ``(block_rows, 32, Kw)`` with the words still in the lanes, so that XLA
    fuses unpack, weight and sum into one loop over the slice and the
    unpacked bits never reach HBM (unpacked word-major and reshaped to
    ``(block_rows, K)`` they did: 0.5 GB a block, PERF.md section 6,
    PR 28); one ``(32, Kw) → K`` transpose after the loop restores the
    column order ``word * 32 + bit``. The chip keeps nothing of a block's
    size (tests/test_tpu_compile.py); the CPU backend does write a block's
    unpacked bits out, block x K x 4 bytes.
    """
    R, Kw = packed_t.shape
    ub = _block_rows(R, block_rows)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)[None, :, None]

    # clamped dynamic slices instead of pad-and-reshape: the pad path
    # CONCATENATED (= copied) the whole packed array, a second bitmap's
    # worth of HBM at 10M atoms × 4096 seeds. The ragged last block is
    # sliced from ``R - ub`` and shares rows with the block before it; a
    # sum is not idempotent as an update's fold is, so the row mask keeps
    # the block to its own rows (all of them, in every other block) and a
    # shared row is counted once when both are listed.
    def body(i, acc):
        first = starts[i]
        start = jnp.minimum(first, R - ub)
        sl = jax.lax.dynamic_slice(packed_t, (start, 0), (ub, Kw))
        w = jax.lax.dynamic_slice(vec, (start,), (ub,))
        own = (start + jnp.arange(ub)) >= first
        w = jnp.where(own, w, 0)
        bits = ((sl[:, None, :] >> shifts) & 1).astype(jnp.int32)
        return acc + jnp.sum(bits * w[:, None, None], axis=0)

    acc = jax.lax.fori_loop(0, n_listed, body,
                            jnp.zeros((WORD, Kw), jnp.int32))
    return acc.T.reshape(Kw * WORD)


@hgverify.entry(shapes=lambda: (hgverify.sds((64, 1), "uint32"),
                                hgverify.sds((64,), "int32"),
                                hgverify.sds((1,), "int32"),
                                hgverify.sds((), "int32")))
@jax.jit
@_program("hg_bfs_deg_sum", "hg.bfs.deg_sum")
def _deg_sum(visited: jax.Array, inc_deg: jax.Array, starts: jax.Array,
             n_listed: jax.Array) -> jax.Array:
    """S = Σ_v visited[v]·deg(v) per seed, exact: bounded by E_inc < 2^31,
    so int32 cannot wrap. Over the plan's active blocks: a row outside
    them has no incidence set under the plan, a degree of 0."""
    return _bitdot(visited, inc_deg, starts, n_listed)


@hgverify.entry(shapes=lambda: (hgverify.sds((64, 1), "uint32"),
                                hgverify.sds((1,), "int32"),
                                hgverify.sds((), "int32")))
@jax.jit
@_program("hg_bfs_reach_counts", "hg.bfs.reach_counts")
def _reach_counts(visited: jax.Array, starts: jax.Array,
                  n_listed: jax.Array) -> jax.Array:
    """The rows that hold bit k, per seed k, over the blocks in which the
    state can hold a bit at all (``_bfs_pull_device`` keeps that account)."""
    return _bitdot(visited, jnp.ones((visited.shape[0],), jnp.int32),
                   starts, n_listed)


# The rule that sends a block's first hop the sparse way (module docstring),
# from the input only: the seeds' (target, seed) pairs, counted before any
# is made, are fewer than one in SPARSE_SHARE of the plan's indices. On the
# chip the two sides met at one in 7.3 to 8.4, by the host's speed (PERF.md
# section 6, PR 26); ten keeps the sparse side ahead at the threshold.
SPARSE_SHARE = 10


class _SeedLinks(NamedTuple):
    """The incident links of a seed block, seed by seed."""

    links: np.ndarray   # (L,) link ids, the seeds' incidence rows in order
    cols: np.ndarray    # (L,) the seed column each link belongs to
    arity: np.ndarray   # (L,) targets of each link (>= 1: it has a seed)
    deg: np.ndarray     # (K,) incidence degree of every seed (S_0)


def _seed_links(snap: CSRSnapshot, seeds: np.ndarray,
                limit: int) -> Optional[_SeedLinks]:
    """The rule, then the first expansion: None where the seeds' first hop
    is not sparse (``limit`` pairs or more), and nothing larger than the
    seeds' link list is built to find that out."""
    s = seeds.astype(np.int64)
    deg = snap.inc_offsets[s + 1].astype(np.int64) - snap.inc_offsets[s]
    if int(deg.sum()) >= limit:  # a link has a target: pairs >= links
        return None
    nz = np.flatnonzero(deg)
    links = snap.inc_links[
        _segmented_ranges(snap.inc_offsets[s[nz]], deg[nz])
    ].astype(np.int64)
    arity = snap.tgt_offsets[links + 1].astype(np.int64) \
        - snap.tgt_offsets[links]
    if int(arity.sum()) >= limit:
        return None
    return _SeedLinks(links, np.repeat(nz, deg[nz]), arity, deg)


class _SeedPairs(NamedTuple):
    """The host's half of a sparse first hop (:func:`_seed_pairs`)."""

    blocks: np.ndarray  # (2, n_blocks, SPARSE_BLOCK) int32: rows, columns
    placed: np.ndarray  # (K,) bool: the columns that hold a pair


def _seed_pairs(snap: CSRSnapshot, seeds: np.ndarray, sl: _SeedLinks,
                own_bits: bool) -> _SeedPairs:
    """Every target of every link incident to seed k is to get bit k: the
    (row, column) pairs of a traversal's ``visited_0`` → ``visited_1``, of a
    match's ``X_1`` onto an empty bitmap — unique, sorted by row, cut into
    SPARSE_BLOCK-wide blocks for the one program that places them.
    ``own_bits``: whether a seed's own bit (among the pairs wherever the
    seed lies in a link) is placed — not on a seed bitmap, which holds it
    (the placement adds, and no bit may be added twice); on an empty one it
    is part of the answer. ``placed`` is the columns a pair falls in —
    without ``own_bits`` the columns that GROW, which the host knows here
    and no pass over the bitmap has to find."""
    rows = snap.tgt_flat[
        _segmented_ranges(snap.tgt_offsets[sl.links], sl.arity)
    ].astype(np.int64)
    ks = np.repeat(sl.cols, sl.arity)
    if not own_bits:
        fresh = rows != seeds[ks]
        rows, ks = rows[fresh], ks[fresh]
    K = len(seeds)
    placed = np.zeros(K, dtype=bool)
    placed[ks] = True
    keys = np.unique(rows * K + ks)
    n_blocks = -(-len(keys) // SPARSE_BLOCK)
    pairs = np.zeros((2, n_blocks * SPARSE_BLOCK), dtype=np.int32)
    pairs[0] = snap.num_atoms  # pad pairs: (dummy row, column 0)
    pairs[:, : len(keys)] = np.divmod(keys, K)
    return _SeedPairs(pairs.reshape(2, n_blocks, SPARSE_BLOCK), placed)


def _sparse_first_hop(visited: jax.Array, pairs: _SeedPairs,
                      n_atoms: jax.Array) -> jax.Array:
    """The device's half: the pairs go up block by block to one program."""
    for b in range(pairs.blocks.shape[1]):
        visited = _sparse_hop(visited, jnp.asarray(pairs.blocks[:, b]),
                              n_atoms)
    return visited


class _Hop(NamedTuple):
    """What one hop of the chain runs over: the snapshot its links come
    from (the parent, or a family's restriction), its plan, and the plan's
    device arrays (:func:`_hop_over`)."""

    snap: CSRSnapshot
    plans: PullBFSPlans
    dev: dict


def _hop_over(snap: CSRSnapshot) -> _Hop:
    plans = plans_for(snap)
    return _Hop(snap, plans, _device_plans(snap, plans))


def _columns(words) -> np.ndarray:
    """``(Kw,) uint32`` read from the device → ``(K,) bool``, column
    ``32 w + k`` from bit k of word w."""
    words = np.asarray(words)
    bits = (words[:, None] >> np.arange(WORD, dtype=np.uint32)) & 1
    return bits.reshape(-1).astype(bool)


def _first_hop_rule(hop: _Hop, seeds: np.ndarray) -> Optional[_SeedLinks]:
    """The seeds' links if their first expansion over ``hop`` is sparse —
    the rule reads the hop's own (restricted) plan — else None."""
    return _seed_links(hop.snap, seeds,
                       hop.plans.total_indices // SPARSE_SHARE)


def _expand(
    state: jax.Array,            # (n_pad, Kw): a visited set, a frontier, a ball
    hop: _Hop,
    sl,                          # the seeds' links, or already their pairs:
                                 # this is their SPARSE first hop
    update,                      # what ends a dense expansion: the operator's
    *,
    seeds: np.ndarray,           # the block's seeds (a sparse hop reads them)
    n_atoms: jax.Array,          # () int32, on the device
    chunk: int,
    use_pallas: bool,
    own_bits: bool = False,      # a sparse hop onto an EMPTY bitmap
    listed: Optional[np.ndarray] = None,
) -> tuple[jax.Array, object]:
    """ONE expansion of ``state`` over ``hop``, the step every operator of
    the chain calls: a traversal's hop, a match's step, one side of a pair
    search. Sparse (``sl``: the rule's answer for a block's first hop, a
    ``_SeedLinks``, or the ``_SeedPairs`` a caller has expanded them to
    ahead of time) it is the seeds' own neighbourhood placed by
    ``_sparse_hop``; else the
    pull chain — stage 1, stage 2's level 0, its upper levels and
    ``update`` — each step synced and the dead stage buffer dropped before
    the next is dispatched.

    ``listed``: the row blocks ``update`` folds where they are not the
    hop's plan's own active blocks (a match's update, which has to clear
    what the frontier it replaces holds). Returns the new state and what
    the expansion knows of the columns that GREW, a host ``(K,) bool``:
    after a sparse hop the columns of the host's own pairs
    (``_seed_pairs``), after a dense one the words ``update`` returns
    beside the state (``_ball_update``), None from an update that returns
    the state alone."""
    # one obs.phase per synced step, one a sparse hop and three a dense
    # one: an expansion's seconds by stage in the default registry, and
    # under a profiler the host span that a device idle gap is charged to
    if sl is not None:
        # around expansion, upload, dispatch and sync: its count beside
        # hg.bfs.hop.stage1's says how often the rule took this side
        with phase("hg.bfs.hop.sparse") as ph:
            with ph.step("expand"):
                pairs = (sl if isinstance(sl, _SeedPairs)
                         else _seed_pairs(hop.snap, seeds, sl, own_bits))
            with ph.step("place"):  # the upload and the dispatches
                state = _sparse_first_hop(state, pairs, n_atoms)
            ph.wait(state)
        return state, pairs.placed
    _, plans, dev = hop
    s1 = plans.stage1
    levels1, levels2 = dev["levels1"], dev["levels2"]
    widths2, n2 = plans.stage2_widths, plans.stage2_n_lvl0
    n2_last = len(plans.stage2_levels[n2 - 1]) // widths2[n2 - 1]
    with phase("hg.bfs.hop.stage1") as ph:
        with ph.step("dispatch"):
            live = _stage(state, levels1, s1.widths, s1.n_lvl0, chunk,
                          use_pallas)
        ph.wait(live)
    with phase("hg.bfs.hop.stage2_lvl0") as ph:
        with ph.step("dispatch"):
            lvl0b = _stage_lvl0_consume(live, levels2[:n2], widths2[:n2],
                                        chunk, use_pallas)
        # the donations can't alias (shapes differ), so the host ref
        # is what keeps each dead buffer resident — drop it AND sync
        # before the next dispatch: async dispatch would let the
        # allocator grab stage-upper's buffers while the consume step
        # (and therefore `live`'s 4.1 GB) is still in flight. The sync
        # costs one RTT per hop against multi-second hops.
        with ph.step("free"):
            del live
        ph.wait(lvl0b)
    with phase("hg.bfs.hop.stage2_upper_update") as ph:
        with ph.step("dispatch"):
            reach_chunks = _stage_upper(lvl0b, levels2[n2:], widths2[n2:],
                                        n2_last, chunk)
            del lvl0b
            if listed is None:
                listed, rows = dev["blocks"], dev["rows"]
            else:
                rows = _listed(dev["out_map"], listed)
            # the XLA route is the four-argument call it always was, which
            # an update standing in for the operator's may be written to
            out = update(state, reach_chunks, rows, n_atoms,
                         **({"use_pallas": True} if use_pallas else {}))
            del reach_chunks
        ph.wait(out)
        # what the update's loop folded beside the whole bitmap, and of
        # it what the kernel fetched: their ratios say how far the plan's
        # block list and the kernel's route engage
        n_pad = state.shape[0]
        visited = int(listed.sum()) * _block_rows(n_pad)
        reg = default_registry()
        reg.counter("bfs.update.rows_visited").inc(visited)
        reg.counter("bfs.update.rows_kernel").inc(
            visited if _update_on_kernel(state, use_pallas) else 0)
        reg.counter("bfs.update.rows_total").inc(n_pad)
    if isinstance(out, tuple):  # the state, and the words of what grew
        return out[0], _columns(out[1])
    return out, None


def _bfs_pull_device(
    hops: Sequence[_Hop],    # one a hop, in order; all over one id space
    n_atoms: int,
    n_pad: int,
    seeds: np.ndarray,       # (K,) int32 — K % 32 == 0
    update,                  # what ends a hop: the operator's
    chunk: int = 1 << 19,
    count_edges: bool = True,
    use_pallas: bool = False,
) -> tuple[jax.Array, list, jax.Array]:
    """The hop chain of one seed block: the state, the first hop's side and
    H calls of :func:`_expand`. ``update`` is ``_visited_update`` (a
    traversal: the state is the visited set, and grows) or
    ``_frontier_replace`` (a match: the state is the newest step's end
    points alone, and ``count_edges`` has no meaning).

    Either update folds the row blocks it is handed and no other. A
    traversal's gets the hop's plan's active blocks. A match's has to leave
    every row outside the new frontier zero, so it gets those AND the
    blocks in which the state it replaces can hold a bit (``held``): the
    seeds' own after no step, step 1's plan's active blocks after a sparse
    first step (its pairs are targets of admitted links, rows with an
    incidence set under ``F_1``), the step's plan's after a dense one.

    The counting passes fold the blocks in which the state they count can
    hold a bit: ``_deg_sum`` the last hop's plan's active blocks,
    ``_reach_counts`` a match's ``held`` as the last step leaves it, a
    traversal's plan's active blocks beside the seeds' own."""
    grows = update is _visited_update
    n_atoms_dev = jnp.int32(n_atoms)
    expand = partial(_expand, update=update, seeds=seeds, n_atoms=n_atoms_dev,
                     chunk=chunk, use_pallas=use_pallas)

    def rule() -> Optional[_SeedLinks]:  # it reads the FIRST hop's plan
        return _first_hop_rule(hops[0], seeds) if hops else None

    if grows:
        # the rule's look at the seeds runs beside the bitmap's zero fill
        visited = _bitmap_of(seeds, n_atoms_dev, n_pad)
        sl = rule()
    else:
        # a match's X_1 holds a seed's bit only where the seed lies in an
        # admitted link: the sparse pairs go onto an EMPTY bitmap — the
        # seed bitmap of pad seeds, whose bits fall on the dummy row
        sl = rule()
        visited = _bitmap_of(seeds if sl is None
                             else np.full_like(seeds, n_atoms),
                             n_atoms_dev, n_pad)
        held = (_blocks_of(seeds[seeds < n_atoms], n_pad) if sl is None
                else hops[0].dev["blocks"])
    # S entering the block's last hop, the one Σ deg that `total_edges`
    # reads (it telescopes over the hops before): one entry, or none where
    # nothing counts edges or no hop runs
    s_ins: list = []
    last = hops[-1] if hops else None  # the chain's last plan, if any
    if sl is not None:
        visited, _ = expand(visited, hops[0], sl, own_bits=not grows)
        hops = hops[1:]
        if count_edges and not hops:
            s_ins.append(sl.deg)  # S_0 = deg(seed), which the host holds
    for i, hop in enumerate(hops):
        if count_edges and i == len(hops) - 1:
            # the degree sum once a block, a phase of its own; a seed
            # outside the plan's active blocks has a degree of 0 under it
            with phase("hg.bfs.hop.deg_sum") as ph:
                with ph.step("dispatch"):
                    s_ins.append(_deg_sum(
                        visited, hop.dev["inc_deg"],
                        *_count_list(hop.dev["blocks"], hop, n_pad)))
                ph.wait(s_ins[-1])
        listed = None
        if not grows:
            listed, held = hop.dev["blocks"] | held, hop.dev["blocks"]
        visited, _ = expand(visited, hop, None, listed=listed)
    with phase("hg.bfs.reach_counts"):  # the dispatch: nothing syncs here
        if grows:
            # a visited set holds what its plan can reach, and every seed's
            # own bit where the seed lies — a link atom, an atom no
            # admitted link touches
            held = _blocks_of(seeds[seeds < n_atoms], n_pad)
            if last is not None:
                held |= last.dev["blocks"]
        reach = _reach_counts(visited, *_count_list(held, last, n_pad))
    return visited, s_ins, reach


def _count_list(blocks: np.ndarray, hop: Optional[_Hop],
                n_pad: int) -> tuple[jax.Array, jax.Array]:
    """What a counting pass is handed beside the bitmap: the row blocks
    ``blocks`` marks, every block in which the state it counts can hold a
    bit. Where they ARE ``hop``'s plan's active blocks — seeds that are
    entities, a match after a step — the list went up with the plan and
    nothing is uploaded. The two counters say how far the list engages,
    once a counting dispatch."""
    reg = default_registry()
    reg.counter("bfs.count.rows_visited").inc(
        int(blocks.sum()) * _block_rows(n_pad))
    reg.counter("bfs.count.rows_total").inc(n_pad)
    if hop is not None and np.array_equal(blocks, hop.dev["blocks"]):
        return hop.dev["rows"].starts, hop.dev["rows"].n_listed
    return _block_starts(blocks, n_pad)


def _bitmap_of(seeds: np.ndarray, n_atoms: jax.Array,
               n_pad: int) -> jax.Array:
    """The seed bitmap of one block (pad seeds: an empty one)."""
    with phase("hg.bfs.seeds_upload"):
        seeds_dev = jnp.asarray(seeds)
    return _seed_bitmap(seeds_dev, n_atoms, n_pad)


# ------------------------------------------------------------------ host API


def block_layout(K: int, k_block: int) -> list[int]:
    """The real seed-block widths :func:`bfs_pull` runs for (K, k_block):
    K is padded to a multiple of WORD (floor WORD), then split into
    k_block-wide blocks with a possibly-ragged tail. Exposed so traffic
    models (bench.py) stay tied to the kernel's actual layout."""
    K_pad = _ceil_to(max(K, WORD), WORD)
    return [min(k_block, K_pad - s) for s in range(0, K_pad, k_block)]


def _check_k_block(k_block: int) -> None:
    """Before any plan is built for the call."""
    if k_block <= 0 or k_block % WORD:
        raise ValueError(
            f"k_block must be a positive multiple of {WORD} (device words "
            f"pack {WORD} seeds); got {k_block}"
        )


def _kernel_gathers(width: int) -> bool:
    """4096-seed blocks (128-lane rows, the one width the kernel compiles
    at) run the Pallas gather on a TPU; everything else keeps the XLA
    gather (no width limits)."""
    return width == _pg.ROW_WORDS * WORD and _pg.pallas_ok()


def _seed_blocks(hops: Sequence[_Hop], snap: CSRSnapshot,
                 seeds: np.ndarray, update, chunk: int, k_block: int,
                 count_edges: bool) -> tuple[list, int]:
    """The chain over every seed block of ``seeds``: the blocks' (bitmap,
    S entering the last hop, counts) in order, and the seeds' real number
    (the last block's columns past it are pad seeds)."""
    seeds = np.asarray(seeds, dtype=np.int32)
    K = len(seeds)
    K_pad = _ceil_to(max(K, WORD), WORD)
    if K_pad != K:
        seeds = np.concatenate(
            [seeds, np.full(K_pad - K, snap.num_atoms, dtype=np.int32)]
        )
    n_pad = _n_pad(snap.num_atoms)
    blocks = []
    for s in range(0, K_pad, k_block):
        block = seeds[s : s + k_block]
        blocks.append(
            _bfs_pull_device(
                hops, snap.num_atoms, n_pad, block, update,
                chunk=chunk, count_edges=count_edges,
                use_pallas=_kernel_gathers(len(block)),
            )
        )
    return blocks, K


# an operation's own phase, once a CALL: every phase below carries its id
# as ``op``, and its self time is its wall less its direct children's
@phase("hg.bfs.pull")
def bfs_pull(
    snap: CSRSnapshot,
    seeds: np.ndarray,
    max_hops: int,
    chunk: int = 1 << 19,
    k_block: int = 1024,
    count_edges: bool = True,
    link_types=None,
) -> PullBFSResult:
    """Pull-mode multi-hop BFS over all seeds at once (blocked past
    ``k_block``; at 10M atoms a 4096-wide block's working set fills most
    of a v5e's HBM, so callers should drop previous results before
    re-running at that width).

    Returns ``PullBFSResult(visited_t, edges_touched, reach_counts)``:
    ``visited_t`` is a device (N_pad, K/32) uint32 transposed bitmap,
    ``edges_touched`` a HOST (K,) int64 ndarray (the telescoped
    Σdeg(visited) of the last hop — frontiers partition visited, so it
    equals the per-hop frontier-degree total; a single int32-bounded
    quantity ≤ E_inc), and ``reach_counts`` a device (K,) int32. Use
    :func:`visited_rows` to extract per-seed reachable sets on host.
    Blocks run sequentially: each hop synchronizes internally so stage
    buffers free before the next allocates (HBM headroom, see
    ``_bfs_pull_device``).

    Per block, the first hop is the seeds' own neighbourhood, expanded on
    the host and placed into the seed bitmap, when its (target, seed)
    pairs are few beside the plan (fewer than ``total_indices //
    SPARSE_SHARE``); hops 2..H, and hop 1 otherwise, run on the dense pull
    chain, whose cost does not depend on what the bitmap holds. The
    answers are the same bit for bit on either side.

    ``link_types`` is the link predicate: a collection of link type atoms,
    and a hop follows a link only if ``snap.type_of[link]`` is among them —
    the reference's ``HGBreadthFirstTraversal(start, DefaultALGenerator(
    graph, linkPredicate = type in family))`` with every other option at
    its default; ``edges_touched`` then counts admitted links only. It IS
    this traversal over the sub-hypergraph the family selects
    (:func:`restricted_for`: built and planned once per family, so a hop
    gathers no entry of an excluded link, and the first hop's rule reads
    the restricted plan's size). ``None`` follows every link (the
    reference's ``SimpleALGenerator``); the family of all link types
    answers the same bit for bit, an empty one returns the seeds, a type
    atom no link has is ignored. A predicate per hop — the path
    ``F1/F2/F3``, which keeps no visited set — is :func:`path_match`.
    Still host-only (``algorithms/traversals.DefaultALGenerator``): the
    sibling predicate and the ordered-link directions
    (``return_preceeding`` / ``return_succeeding``, ``reverse_order``).
    """
    _check_k_block(k_block)
    if link_types is not None:
        snap = restricted_for(snap, link_types)
    if not snap.n_edges_tgt:  # no link to follow: the seeds are the answer
        max_hops = 0
    # one plan for every hop: the chain is handed the same one H times
    blocks, K = _seed_blocks(
        [_hop_over(snap)] * max_hops, snap, seeds, _visited_update, chunk,
        k_block, count_edges)

    # The device emits S_h (Σ deg over visited entering each hop);
    # frontiers partition visited, so the total over all hops telescopes
    # to the LAST emitted S — one (K,) download per block.
    def total_edges(b) -> np.ndarray:
        s_ins = b[1]
        if not len(s_ins):  # zero hops / counting off
            return np.zeros(b[2].shape[0], np.int64)
        with phase("hg.bfs.edges_to_host"):
            return np.asarray(s_ins[-1]).astype(np.int64)

    visited_t, reach = _joined(blocks, K)
    edges = np.concatenate([total_edges(b) for b in blocks])[:K]
    return PullBFSResult(visited_t, edges, reach)


def _joined(blocks: list, K: int) -> tuple[jax.Array, jax.Array]:
    """The seed blocks' bitmaps side by side, and their counts end to end
    without the pad seeds' (past the seeds' real number ``K``); one whole
    block's are handed on as they are, with no device operation."""
    if len(blocks) == 1:
        bitmap, _, counts = blocks[0]
    else:
        bitmap = jnp.concatenate([b[0] for b in blocks], axis=1)
        counts = jnp.concatenate([b[2] for b in blocks])
    return bitmap, (counts if counts.shape[0] == K else counts[:K])


class PathMatchResult(NamedTuple):
    frontier_t: jax.Array    # (N_pad, Kw) uint32 — X_H, TRANSPOSED and packed
    match_counts: jax.Array  # (K,) int32 — |X_H[k]|


@phase("hg.bfs.match")
def path_match(
    snap: CSRSnapshot,
    seeds: np.ndarray,
    steps: Sequence,
    chunk: int = 1 << 19,
    k_block: int = 1024,
) -> PathMatchResult:
    """The end points of the path pattern ``F_1 / F_2 / … / F_H`` from every
    seed at once: ``steps[h]`` is step h's family of link type atoms
    (``None``: every link), a predicate PER HOP — SPARQL 1.1's
    SequencePath, a chain of ``And(type(T), incident(x), incident(y))``
    through the shared variable. ``X_0[k] = {seeds[k]}`` and, step by step,

        live_h[k] = {L : type_of[L] in F_h, targets(L) ∩ X_{h-1}[k] ≠ ∅}
        X_h[k]    = ∪ {targets(L) : L in live_h[k]}

    Returns ``PathMatchResult(frontier_t, match_counts)``: the device
    (N_pad, K/32) uint32 transposed bitmap of ``X_H`` (:func:`visited_rows`
    reads its rows) and the device (K,) int32 ``|X_H[k]|``; the reference
    is ``algorithms/traversals.match_path``. These are the query engine's
    set semantics, a homomorphism — two-stage hypergraph message passing,
    node → hyperedge → node, OR as the aggregate and the node's own
    message included: an atom of ``X_{h-1}`` that lies in an admitted link
    is itself in ``X_h``. It is NOT a traversal: no visited set (an atom
    left at step 1 may be entered again at step 3), nothing accumulates, a
    step whose family admits no link gives the empty answer (not the
    seeds), zero steps give the seeds. Departure from
    ``DefaultALGenerator``: its exclusive step (``t != atom`` per hop) is
    not built — it needs a second reduction per link — and stays
    host-only.

    It is :func:`bfs_pull`'s hop chain over a plan PER STEP
    (:func:`restricted_for` a family: the steps' restricted snapshots,
    plans and device arrays are alive at once), each step ended by
    ``_frontier_replace`` where a traversal's ends in ``_visited_update``.
    Seed blocks, the gather's choice and the first step's rule (the sparse
    side when the seeds' pairs are few beside STEP 1's plan, its pairs
    placed on an empty bitmap; else the dense chain from the seed bitmap)
    are ``bfs_pull``'s.
    """
    _check_k_block(k_block)
    subs = [snap if family is None else restricted_for(snap, family)
            for family in steps]
    if any(not sub.n_edges_tgt for sub in subs):
        # a step no link passes: no end point — no hop, from pad seeds
        # alone, and no plan goes up
        subs, seeds = [], np.full(len(seeds), snap.num_atoms, np.int32)
    blocks, K = _seed_blocks([_hop_over(sub) for sub in subs], snap, seeds,
                             _frontier_replace, chunk, k_block,
                             count_edges=False)
    return PathMatchResult(*_joined(blocks, K))


class PairDistResult(NamedTuple):
    dist: np.ndarray   # HOST (K,) int32 — hops from s_k to t_k, -1: none
    expansions: int    # ball expansions the batch ran, over its blocks


@phase("hg.bfs.pairs")
def pair_distances(
    snap: CSRSnapshot,
    sources: np.ndarray,
    targets: np.ndarray,
    max_hops: int,
    link_types=None,
    chunk: int = 1 << 19,
    k_block: int = 1024,
) -> PairDistResult:
    """How far apart: the shortest-path LENGTH of every pair ``(sources[k],
    targets[k])`` at once — ``GraphClassics.dijkstra`` at unit weights
    (``algorithms/traversals.dijkstra``; the plain reference here is
    ``traversals.shortest_path_length``), LDBC SNB Interactive IC13's
    answer, a length per pair and not a set. With ``N_0(a) = {a}`` and
    ``N_h(a)`` = :func:`bfs_pull`'s visited set after h hops from ``a``
    (``link_types`` its link predicate, ``None`` every link),

        dist[k] = min {h <= max_hops : t_k in N_h(s_k)},  -1 if none

    so ``s_k == t_k`` gives 0, and a pair further apart than the cap gives
    -1 as a pair with no path does (the reference's ``maxDistance``). An
    end that is no atom (``snap.num_atoms``, the pad seed) gives -1. It
    equals ``len(dijkstra(s_k, t_k, generator)) - 1`` wherever that is at
    most ``max_hops``. Returns ``PairDistResult(dist, expansions)``:
    ``dist`` a HOST (K,) int32, ``expansions`` how many ball expansions
    the batch ran over its seed blocks. Departures from ``dijkstra``:
    lengths only — the path itself (its predecessor map) is not built;
    unit weights only; and, as everywhere on the device, the adjacency is
    the symmetric one (two atoms are neighbours iff an admitted link holds
    both among its targets; ``DefaultALGenerator``'s ordered-link
    directions and sibling predicate stay host-only).

    That symmetry is what the search uses: ``t in N_h(s)`` iff ``N_i(s)``
    and ``N_j(t)`` share an atom for any ``i + j = h``, so per seed block a
    ball grows from EACH end over the same plan — TWO bitmaps alive at
    once — one expansion at a time, forward first, through the step
    :func:`bfs_pull` and :func:`path_match` run (:func:`_expand`; each
    side's first by the rule read against the restricted plan, later ones
    dense, ended by ``_ball_update``), and after every expansion one meet
    test (``_meet``: per column, does any row hold the bit in both
    bitmaps). A column's length is the depth ``i + j`` of the first test it
    passes; ``s == t`` is answered on the host. A column is EXHAUSTED
    when an expansion of either ball gained no row in it — that ball is
    its whole component, which the other end is not in: -1. The block ends
    when every real column is met or exhausted, or at depth ``max_hops``
    (an odd cap: the forward side has the extra hop) — the hop count
    follows the data. Pad columns (K not a multiple of 32: both ends on
    the dummy row) are never met.

    Memory: two ``(N_pad, k_block/32)`` bitmaps a block beside the stage
    buffers — at 10M atoms and 4096 pairs a quarter family fits one v5e
    (12.5 of 16.9 GB) and every link does not: lower ``k_block``. Seed
    blocks and the gather's choice are ``bfs_pull``'s.
    """
    _check_k_block(k_block)
    if max_hops < 0:
        raise ValueError(f"max_hops must be >= 0; got {max_hops}")
    sources = np.asarray(sources, dtype=np.int32)
    targets = np.asarray(targets, dtype=np.int32)
    if sources.shape != targets.shape or sources.ndim != 1:
        raise ValueError("sources and targets are two (K,) arrays, an end "
                         f"each of K pairs; got {sources.shape} and "
                         f"{targets.shape}")
    if link_types is not None:
        snap = restricted_for(snap, link_types)
    if not snap.n_edges_tgt:  # no link to follow: s == t or nothing
        max_hops = 0
    K = len(sources)
    K_pad = _ceil_to(max(K, WORD), WORD)
    pad = np.full(K_pad - K, snap.num_atoms, dtype=np.int32)
    sources, targets = (np.concatenate([e, pad]) for e in (sources, targets))
    hop = _hop_over(snap) if max_hops else None
    n_pad = _n_pad(snap.num_atoms)
    default_registry().counter("bfs.pairs.batches").inc()
    dist, expansions = np.empty(K_pad, dtype=np.int32), 0
    for s in range(0, K_pad, k_block):
        ends = sources[s: s + k_block], targets[s: s + k_block]
        dist[s: s + k_block], ran = _pair_block(
            hop, snap.num_atoms, n_pad, ends, max_hops, chunk,
            use_pallas=_kernel_gathers(len(ends[0])))
        expansions += ran
    return PairDistResult(dist[:K], expansions)


def _pair_block(hop: Optional[_Hop], n_atoms: int, n_pad: int, ends: tuple,
                max_hops: int, chunk: int,
                use_pallas: bool) -> tuple[np.ndarray, int]:
    """One seed block of :func:`pair_distances`: ``ends`` its (sources,
    targets), each (K,) with K % 32 == 0. Returns (dist, expansions)."""
    reg = default_registry()
    src, dst = ends
    real = (src < n_atoms) & (dst < n_atoms)
    dist = np.where(real & (src == dst), 0, -1).astype(np.int32)
    # neither met nor exhausted: the columns the search is still for
    wanted = real & (src != dst)
    depth = 0
    if max_hops and wanted.any():
        n_atoms_dev = jnp.int32(n_atoms)
        # both zero fills go out before the rule looks at either end, and
        # BOTH sides' sparse pairs are made beside them: made when its turn
        # comes, the backward side's would keep the device waiting
        balls = [_bitmap_of(e, n_atoms_dev, n_pad) for e in ends]
        first = [_first_hop_rule(hop, e) for e in ends]
        first = [None if sl is None
                 else _seed_pairs(hop.snap, e, sl, own_bits=False)
                 for e, sl in zip(ends, first)]
        while depth < max_hops and wanted.any():
            side = depth % 2  # forward first: an odd cap's extra hop is its
            sl, first[side] = first[side], None
            balls[side], grew = _expand(
                balls[side], hop, sl, _ball_update, seeds=ends[side],
                n_atoms=n_atoms_dev, chunk=chunk, use_pallas=use_pallas)
            depth += 1
            reg.counter("bfs.pairs.expansions."
                        + ("dense" if sl is None else "sparse")).inc()
            # once a test, around the dispatch, the read of 4 Kw bytes
            # and the host's decision: the device waits for it, so a
            # profile charges the gap between two expansions here
            with phase("hg.bfs.pairs.meet") as ph:
                with ph.step("dispatch"):
                    words = _meet(*balls)
                with ph.step("wait"):  # the read of its 4 Kw bytes
                    words = np.asarray(words)
                with ph.step("decide"):
                    met = _columns(words) & wanted
                    reg.counter("bfs.pairs.meet_tests").inc()
                    dist[met] = depth
                    wanted &= ~met & grew
    if depth < max_hops:
        reg.counter("bfs.pairs.early_exits").inc()
    return dist, depth


# ------------------------------------------------- connected components


@hgverify.entry(shapes=lambda: (hgverify.sds((), "int32"),),
                statics={"n_pad": 64})
@partial(jax.jit, static_argnames=("n_pad",))
@_program("hg_wcc_init", "hg.wcc.init")
def _wcc_init(n_atoms: jax.Array, n_pad: int) -> jax.Array:
    """``label[a] = a`` for every atom; the dummy row and the pad rows hold
    ``INT32_MAX``, the min's identity, which no link ever lowers."""
    ids = jnp.arange(n_pad, dtype=jnp.int32)
    return jnp.where(ids < n_atoms, ids, INT32_MAX)


def _wcc_round_shapes():
    i32 = partial(hgverify.sds, dtype="int32")
    return (i32((64,)), (i32((32,)), i32((64,))), (i32((32,)),),
            _UpdateRows(i32((64,)), i32((1,)), i32(())), i32(()))


@hgverify.entry(shapes=_wcc_round_shapes, donate=True,
                statics={"widths1": (2, 8), "n1": 2, "widths2": (2,),
                         "n2": 1, "chunk": 4})
@partial(jax.jit, static_argnames=("widths1", "n1", "widths2", "n2", "chunk",
                                   "use_pallas"),
         donate_argnums=(0,))  # the labels alias the output
@_program("hg_wcc_round")
def _wcc_round(labels, levels1, levels2, rows, n_atoms, widths1, n1,
               widths2, n2, chunk, use_pallas=False):
    """One synchronous min-label round over a pull plan, ONE program: stage
    1 (each link's min over its targets' labels), stage 2 (each atom's min
    over its incident links' — level 0 composed through stage 1's chunks,
    then its upper levels) and the fold ``label[v] = min(label[v],
    buf[out_map[v]])`` over the plan's active row blocks, which returns
    beside the labels the rows it LOWERED, ``() int32``. The pyramids are
    ``bfs_pull``'s with the labels' reduction (``_reduction``: min,
    identity ``INT32_MAX``); their level 0 gathers its 4-byte scalars on
    the kernel's scalar form where ``use_pallas`` (``pallas_ok()``), and
    the fold fetches its rows on it at width 1, else both through the XLA
    gather. The stage buffers are a few tens of MB at 10M atoms, so one
    program holds the round where a 4096-seed hop needs four."""
    live = _apply_plan(labels, levels1, widths1, n1, chunk, use_pallas,
                       scopes=("hg.wcc.stage1", "hg.wcc.stage1"))
    reach = _apply_plan(live, levels2, widths2, n2, chunk, use_pallas,
                        scopes=("hg.wcc.stage2", "hg.wcc.stage2"))
    with jax.named_scope("hg.wcc.fold"):
        return _fold_rows(labels, reach, rows, n_atoms, jnp.minimum,
                          gain=_LOWERED, use_pallas=use_pallas)


@hgverify.entry(shapes=lambda: (hgverify.sds((64,), "int32"),))
@jax.jit
@_program("hg_wcc_count", "hg.wcc.count")
def _wcc_count(labels: jax.Array) -> jax.Array:
    """The atoms that are their own label: one a component. The dummy and
    pad rows hold ``INT32_MAX``, no row's index."""
    return jnp.sum(labels == jnp.arange(labels.shape[0], dtype=jnp.int32),
                   dtype=jnp.int32)


class ComponentsResult(NamedTuple):
    labels: jax.Array    # (N_pad,) int32 on the device; dummy row INT32_MAX
    n_components: int    # host: the atoms whose label is their own id
    rounds: int          # host: rounds run, the last of them quiet


def connected_components(snap: CSRSnapshot, link_types=None, *,
                         chunk: int = 1 << 16) -> ComponentsResult:
    """Which atoms hang together: the weakly connected components of the
    hypergraph under a link family — LDBC Graphalytics' WCC, and what
    ``HGBreadthFirstTraversal(start, DefaultALGenerator(linkPredicate))``
    yields run to exhaustion, ``start`` added (the plain reference is
    ``algorithms/traversals.connected_components``). Two atoms are
    ADJACENT iff some link of a type in ``link_types`` (``None``: every
    link) holds both among its targets — :func:`bfs_pull`'s adjacency: a
    link atom is adjacent to the co-targets of the links that hold it, not
    to its own targets. Then

        label[a] = min {b : b reachable from a} ∪ {a}

    so an atom in no admitted link (a type atom, a link of another family,
    an isolated entity) is its own component, and ``n_components`` counts
    the atoms with ``label[a] == a``. Departure from the reference: its
    link-as-node form (``HyperTraversal``, a link in its targets'
    component) is not built — it would need each link's label kept
    between the stages, where the plan composes them away.

    Synchronous min-label propagation: ``label_0[a] = a``, and a round is
    ``label_{r+1}[a] = min(label_r[a], min over the neighbours b of
    label_r[b])`` — after r rounds ``label_r[a]`` is the least id within r
    hops of ``a``, so the fixpoint is ``label``. A round is ONE program
    (``_wcc_round``: :func:`bfs_pull`'s two pyramids over the family's
    restricted plan, with the min in place of the OR, and the fold over
    the plan's active row blocks, which counts the rows it lowered); the
    host reads that count, 4 bytes, and stops after the first round that
    lowered none. So the rounds follow the data — the longest shortest
    path from an atom to its component's least id, plus the quiet one —
    and none is skipped: a round after the fixpoint's is as dear as the
    first. Nothing is kept between calls; a second call on the same
    inputs runs every round again. A family that admits no link runs no
    round: every atom is its own label.

    Memory: the labels, ``(N_pad,)`` int32 — 4 bytes an atom, 40 MB at 10M
    atoms, flat so that no tile pads a label to a row — donated from
    round to round, beside the plan and two stage buffers of one int32 a
    chunk. Returns ``ComponentsResult(labels, n_components, rounds)``:
    the labels on the device (rows ``0 … n_atoms-1``; the dummy row
    holds ``INT32_MAX``), the other two on the host. ``chunk``: the scan
    grain of the pyramids' level 0 (``chunk * STEP_WIDTH`` indices a
    step)."""
    reg = default_registry()
    with phase("hg.wcc") as op:
        if link_types is not None:
            snap = restricted_for(snap, link_types)
        n_pad = _n_pad(snap.num_atoms)
        n_atoms = jnp.int32(snap.num_atoms)
        reg.counter("wcc.runs").inc()
        labels = _wcc_init(n_atoms, n_pad)
        rounds = 0
        if snap.n_edges_tgt:  # else no link to follow: every atom alone
            _, plans, dev = _hop_over(snap)
            s1, rows = plans.stage1, dev["rows"]
            folded = int(dev["blocks"].sum()) * _block_rows(n_pad)
            kernel = _pg.pallas_ok()
            gathered = _scalar_indices(plans, jnp.int32, chunk, kernel)
            fetched = _scalar_fold_rows(plans, jnp.int32, folded, kernel)
            lowered = 1
            while lowered:
                with phase("hg.wcc.round") as ph:
                    with ph.step("dispatch"):
                        labels, lowered = _wcc_round(
                            labels, dev["levels1"], dev["levels2"], rows,
                            n_atoms, s1.widths, s1.n_lvl0,
                            plans.stage2_widths, plans.stage2_n_lvl0, chunk,
                            **({"use_pallas": True} if kernel else {}))
                    with ph.step("wait"):  # the read of its 4 bytes
                        lowered = int(lowered)
                rounds += 1
                reg.counter("wcc.rounds").inc()
                reg.counter("wcc.rows_lowered").inc(lowered)
                reg.counter("wcc.rows_folded").inc(folded)
                _count_scalar_gathers(reg, gathered, fetched)
        with op.step("count"):
            n_components = int(_wcc_count(labels))
    return ComponentsResult(labels, n_components, rounds)


# ------------------------------------------------------------- pagerank


class _PRWeights(NamedTuple):
    """What a PageRank iteration reads of a plan beside its indices, built
    on the host once a plan (:func:`_pr_weights`), float32 on the device."""

    inv_d: jax.Array  # (n_pad,): 1/d(u); 0 where d(u) = 0, dummy and pad rows
    w: jax.Array      # (stage 1's buffer,): w_e at link e's chunk, else 0
    c: jax.Array      # (n_pad,): Σ w_e over the target slots v holds


@hgverify.entry(shapes=lambda: (hgverify.sds((), "int32"),),
                statics={"n_pad": 64})
@partial(jax.jit, static_argnames=("n_pad",))
@_program("hg_pr_init", "hg.pr.init")
def _pr_init(n_atoms: jax.Array, n_pad: int) -> tuple[jax.Array, jax.Array]:
    """``rank[a] = 1/N`` on every atom, 0 on the dummy and the pad rows;
    and the ranks' sum, ``() float32``."""
    ids = jnp.arange(n_pad, dtype=jnp.int32)
    ranks = jnp.where(ids < n_atoms,
                      jnp.float32(1.0) / n_atoms.astype(jnp.float32),
                      jnp.float32(0.0))
    return ranks, jnp.sum(ranks)


def _pr_iter_shapes():
    i32 = partial(hgverify.sds, dtype="int32")
    f32 = partial(hgverify.sds, dtype="float32")
    # stage 1's buffer: 16 + 8 chunks and the zero row
    return (f32((64,)), (i32((32,)), i32((64,))), (i32((32,)),),
            _PRWeights(f32((64,)), f32((25,)), f32((64,))),
            _UpdateRows(i32((64,)), i32((1,)), i32(())), i32(()), f32(()))


@hgverify.entry(shapes=_pr_iter_shapes, donate=True,
                statics={"widths1": (2, 8), "n1": 2, "widths2": (2,),
                         "n2": 1, "chunk": 4})
@partial(jax.jit, static_argnames=("widths1", "n1", "widths2", "n2", "chunk",
                                   "use_pallas"),
         donate_argnums=(0,))  # the ranks alias the output
@_program("hg_pr_iter")
def _pr_iter(ranks, levels1, levels2, pw, rows, n_atoms, damping, widths1,
             n1, widths2, n2, chunk, use_pallas=False):
    """One PageRank iteration over a pull plan, ONE program, returning the
    new ranks and their sum. Stage 1 sums each link's target shares ``x =
    rank · inv_d`` (a slot a share); ``w`` scales each link's sum where
    stage 2's composed level 0 reads it; stage 2 sums those over each
    atom's incident links; the fold REPLACES ``y[v] = buf[out_map[v]]``
    into zeros over the plan's active row blocks (a row outside them has
    no incident link); the rest is elementwise over the whole vector:

        rank'[v] = (1 - d)/N + d · (y[v] - c[v] · x[v]) + (d/N) · dangling

    ``dangling`` the ranks of the atoms with ``inv_d == 0``. The pyramids
    are :func:`bfs_pull`'s with the sum (``_reduction``: identity 0.0);
    their level 0 gathers on the kernel's scalar form where
    ``use_pallas``, and so does the fold's fetch at width 1, as in
    :func:`_wcc_round`, whose buffers come padded to whole rows of the
    kernel's table (``w``'s pad is 0)."""
    with jax.named_scope("hg.pr.stage1"):
        x = ranks * pw.inv_d
    s = _apply_plan(x, levels1, widths1, n1, chunk, use_pallas,
                    scopes=("hg.pr.stage1", "hg.pr.stage1"))
    with jax.named_scope("hg.pr.stage2"):
        pad = s.shape[0] - pw.w.shape[0]
        s = s * (jnp.pad(pw.w, (0, pad)) if pad else pw.w)
    y = _apply_plan(s, levels2, widths2, n2, chunk, use_pallas,
                    scopes=("hg.pr.stage2", "hg.pr.stage2"))
    with jax.named_scope("hg.pr.update"):
        y = _fold_rows(jnp.zeros_like(ranks), y, rows, n_atoms,
                       lambda cur, reached: reached, use_pallas=use_pallas)
        n = n_atoms.astype(jnp.float32)
        real = jnp.arange(ranks.shape[0], dtype=jnp.int32) < n_atoms
        new = jnp.where(real, (1.0 - damping) / n + damping * (y - pw.c * x)
                        + damping * _dangling(ranks, pw.inv_d) / n, 0.0)
        return new, jnp.sum(new)


def _scalar_fold_rows(plans: "PullBFSPlans", dtype, folded: int,
                      use_pallas: bool) -> tuple[int, int]:
    """``(rows a whole-graph dispatch's fold visits, those whose fetch
    takes the kernel)``: ``folded`` (the plan's listed rows), and all or
    none of them by ``_update_on_kernel``'s rule over a flat ``dtype``
    state and the stage-2 buffer the fold reads, from their shapes on the
    host (the buffer's pad to whole tiles moves no gate)."""
    buf = sum(len(l) // w for l, w in zip(plans.stage2_levels,
                                          plans.stage2_widths)) + 1
    kernel = _update_on_kernel(
        jax.ShapeDtypeStruct((plans.n_pad,), dtype), use_pallas,
        table=jax.ShapeDtypeStruct((buf,), dtype))
    return folded, folded if kernel else 0


def _count_scalar_gathers(reg, gathered: tuple[int, int],
                          fetched: tuple[int, int]) -> None:
    """A whole-graph dispatch's level-0 indices and those the kernel
    gathered (``_scalar_indices``), its fold's rows and those the kernel
    fetched (``_scalar_fold_rows``): readers ``scalar_gather_kernel_share``
    and ``scalar_fold_kernel_share``."""
    reg.counter("scalar.gather.indices").inc(gathered[0])
    reg.counter("scalar.gather.indices_kernel").inc(gathered[1])
    reg.counter("scalar.fold.rows").inc(fetched[0])
    reg.counter("scalar.fold.rows_kernel").inc(fetched[1])


def _dangling(ranks: jax.Array, inv_d: jax.Array) -> jax.Array:
    """The rank the dangling atoms hold, ``() float32``: the walk spreads it
    over every atom. The dummy and pad rows hold 0."""
    return jnp.sum(jnp.where(inv_d == 0, ranks, 0.0))


def _pr_weights(snap: CSRSnapshot, plans: PullBFSPlans) -> _PRWeights:
    """The walk's weights over ``snap``'s relations, on the device; kept on
    the snapshot beside ``_device_plans``' dict, not in it, so that no other
    operator uploads them. A link of ``δ'`` DISTINCT targets (its incidence
    entries: the relation holds a (link, atom) pair once) steps with ``w_e
    = 1/(δ' - 1)``, a link of fewer is no step; ``d(u)`` counts the target
    SLOTS ``u`` holds in links that step, ``c_v`` their ``w_e``, a slot
    each."""
    cache = getattr(snap, "_pull_pagerank", None)
    if cache is None:
        N, n_pad = snap.num_atoms, plans.n_pad
        distinct = np.bincount(snap.inc_links[: snap.n_edges_inc],
                               minlength=N + 1)[: N + 1]
        w_row = np.zeros(N + 1, dtype=np.float64)
        steps = distinct >= 2
        w_row[steps] = 1.0 / (distinct[steps] - 1)
        tgt = snap.tgt_flat[: snap.n_edges_tgt]
        slot_w = w_row[snap.tgt_src[: snap.n_edges_tgt]]
        d = np.bincount(tgt, weights=slot_w > 0, minlength=n_pad)
        c = np.bincount(tgt, weights=slot_w, minlength=n_pad)
        inv_d = np.zeros(n_pad, dtype=np.float64)
        np.divide(1.0, d, out=inv_d, where=d > 0)
        # stage 1's concat space: a row's chunk, empty rows on the zero row
        w = np.zeros(plans.stage1.concat_size + 1, dtype=np.float32)
        w[plans.stage1.out_map] = w_row
        cache = _PRWeights(*(jnp.asarray(a, dtype=jnp.float32)
                             for a in (inv_d, w, c)))
        object.__setattr__(snap, "_pull_pagerank", cache)
    return cache


class PageRankResult(NamedTuple):
    ranks: jax.Array  # (N_pad,) float32 on the device; dummy and pad rows 0
    mass: float       # host: Σ rank, read once after the last iteration
    iterations: int   # host: iterations run


def pagerank(snap: CSRSnapshot, link_types=None, *, damping: float = 0.85,
             iterations: int = 10, chunk: int = 1 << 16) -> PageRankResult:
    """How important each atom is: LDBC Graphalytics' PageRank, every atom
    a vertex, over the hypergraph's walk under a link family
    (``link_types``; None: every link). From ``u`` the walk picks one of
    the target slots ``u`` holds in a link of two or more distinct targets,
    uniformly, then one of that link's OTHER distinct atoms, uniformly — so
    a link atom steps to the co-targets of the links that hold it
    (:func:`bfs_pull`'s adjacency), and on a graph of two-target links it
    is Graphalytics' undirected PR, parallel links counted. ``PR_0 = 1/N``
    and

        PR'(v) = (1 - d)/N + d · Σ_{u→v} P(u, v) · PR(u)
                 + (d/N) · Σ_{w dangling} PR(w)

    an atom with no step out dangling. In pull form (:func:`_pr_iter`): ``x
    = PR/d(u)``, a link's sum of its slots' ``x``, each atom's sum over its
    links of ``w_e`` times that, less its own ``c_v · x_v`` — stage 1 and
    stage 2 of the plan :func:`bfs_pull` runs, summing where it ORs. The
    plain reference is ``algorithms/traversals.pagerank``.

    ``iterations`` programs are dispatched back to back with no host read
    between them, the ranks donated from one to the next; the host reads
    their sum (4 bytes) once, after the last. Nothing is kept between
    calls but the plan and the walk's weights (``_pr_weights``). Memory:
    the ranks, ``(N_pad,)`` float32 — 40 MB at 10M atoms — beside the plan,
    two stage buffers of a float a chunk and the weights. ``chunk``: the
    scan grain of the pyramids' level 0 (``chunk * STEP_WIDTH`` indices a
    step)."""
    reg = default_registry()
    with phase("hg.pr") as op:
        if link_types is not None:
            snap = restricted_for(snap, link_types)
        n_pad = _n_pad(snap.num_atoms)
        n_atoms = jnp.int32(snap.num_atoms)
        reg.counter("pr.runs").inc()
        ranks, mass = _pr_init(n_atoms, n_pad)
        if iterations:
            _, plans, dev = _hop_over(snap)
            s1, pw = plans.stage1, _pr_weights(snap, plans)
            folded = int(dev["blocks"].sum()) * _block_rows(n_pad)
            kernel = _pg.pallas_ok()
            gathered = _scalar_indices(plans, jnp.float32, chunk, kernel)
            fetched = _scalar_fold_rows(plans, jnp.float32, folded, kernel)
            d = jnp.float32(damping)
            for _ in range(iterations):
                with phase("hg.pr.iter") as ph:
                    with ph.step("dispatch"):
                        ranks, mass = _pr_iter(
                            ranks, dev["levels1"], dev["levels2"], pw,
                            dev["rows"], n_atoms, d, s1.widths, s1.n_lvl0,
                            plans.stage2_widths, plans.stage2_n_lvl0, chunk,
                            **({"use_pallas": True} if kernel else {}))
                reg.counter("pr.iterations").inc()
                reg.counter("pr.rows_folded").inc(folded)
                _count_scalar_gathers(reg, gathered, fetched)
        with op.step("mass"):
            mass = float(mass)
    return PageRankResult(ranks, mass, iterations)


def _device_plans(snap: CSRSnapshot, plans: PullBFSPlans) -> dict:
    cache = getattr(snap, "_pull_device", None)
    if cache is None:
        with phase("hg.bfs.plan.upload") as ph:
            with ph.step("upload"):
                cache = {
                    "levels1": tuple(jnp.asarray(l)
                                     for l in plans.stage1.levels),
                    "levels2": tuple(jnp.asarray(l)
                                     for l in plans.stage2_levels),
                    "out_map": jnp.asarray(plans.out_map),
                    "inc_deg": jnp.asarray(plans.inc_deg),
                    # the row blocks a hop over this plan can reach, on
                    # the host: derived here and not kept in the sidecar
                    "blocks": _active_blocks(plans),
                }
                cache["rows"] = _listed(cache["out_map"], cache["blocks"])
            # the first stage needs them all: waiting here moves no work,
            # it puts the upload's seconds under the upload's name
            ph.wait(cache)
        # outside the upload's phase: on a TPU the first call probes the
        # kernel (the first 4096-seed block would otherwise)
        default_registry().gauge("bfs.gather.tile_share").set(
            _tile_share(plans) if _pg.pallas_ok() else 0.0)
        object.__setattr__(snap, "_pull_device", cache)
    return cache


def _tile_share(plans: PullBFSPlans) -> float:
    """Percent of the plan's level-0 indices, both stages', in a class
    whose width ``hg_gather_or`` serves at its one row width (4096-seed
    blocks; a narrower block takes the XLA gather whatever its plan)."""
    lvl0 = [(len(l), w) for levels, widths, n in (
        (plans.stage1.levels, plans.stage1.widths, plans.stage1.n_lvl0),
        (plans.stage2_levels, plans.stage2_widths, plans.stage2_n_lvl0))
        for l, w in zip(levels[:n], widths[:n])]
    served = sum(n for n, w in lvl0
                 if _pg.declined(w, _pg.ROW_WORDS) is None)
    return 100.0 * served / max(1, sum(n for n, _ in lvl0))


def visited_rows(res, n_atoms: int) -> list[np.ndarray]:
    """Per-seed sorted atom arrays from the transposed bitmap a traversal
    (``visited_t``) or a match (``frontier_t``) returns first."""
    vt = np.asarray(res[0])[: n_atoms]  # drop dummy+pad rows
    K = vt.shape[1] * WORD
    out = []
    for k in range(K):
        word = vt[:, k >> 5]
        hit = (word >> np.uint32(k & 31)) & np.uint32(1)
        out.append(np.nonzero(hit)[0].astype(np.int64))
    return out
