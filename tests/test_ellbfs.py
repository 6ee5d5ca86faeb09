"""Differential tests: pull-mode ELL BFS vs the r2 push-scan kernel and a
pure-numpy host BFS, on random hypergraphs (the correctness oracle pattern
from SURVEY §7 M4)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypergraphdb_tpu import obs
from hypergraphdb_tpu.algorithms.traversals import match_path
from hypergraphdb_tpu.ops import ellbfs as eb
from hypergraphdb_tpu.ops.snapshot import CSRSnapshot
from hypergraphdb_tpu.ops.ellbfs import (
    bfs_pull,
    build_reduce_plan,
    plans_for,
    visited_rows,
)


def random_snapshot(n_nodes, n_links, max_arity, seed, zipf=False,
                    n_types=0):
    r = np.random.default_rng(seed)
    N = n_nodes + n_links
    type_of = np.zeros(N, dtype=np.int32)
    if n_types:  # link types 1..n_types (atoms 1.. stand in as type atoms)
        type_of[n_nodes:] = 1 + r.integers(0, n_types, size=n_links)
    is_link = np.zeros(N, dtype=bool)
    is_link[n_nodes:] = True
    arities = r.integers(2, max_arity + 1, size=n_links)
    offsets = np.zeros(N + 1, dtype=np.int64)
    offsets[n_nodes + 1 :] = np.cumsum(arities)
    if zipf:
        flat = (r.zipf(1.3, size=int(arities.sum())) % n_nodes).astype(np.int64)
    else:
        flat = r.integers(0, n_nodes, size=int(arities.sum()))
    return CSRSnapshot.from_tables(type_of, is_link, offsets, flat)


def host_bfs(snap, seed_atom, hops, family=None):
    """Reference semantics: atom → incident links → targets; under a
    ``family`` of link types, the links of those types alone."""
    visited = {int(seed_atom)}
    frontier = {int(seed_atom)}
    edges = 0
    for _ in range(hops):
        nxt = set()
        for a in frontier:
            row = snap.incidence_row(a)
            if family is not None:
                row = row[np.isin(snap.type_of[row], list(family))]
            edges += len(row)
            for l in row.tolist():
                for t in snap.targets_row(int(l)).tolist():
                    if t not in visited:
                        nxt.add(int(t))
        visited |= nxt
        frontier = nxt
    return visited, edges


@pytest.mark.parametrize("zipf", [False, True])
@pytest.mark.parametrize("hops", [1, 2, 3])
def test_pull_matches_host(zipf, hops):
    snap = random_snapshot(400, 300, 4, seed=11 + hops, zipf=zipf)
    r = np.random.default_rng(5)
    seeds = r.integers(0, 400, size=48).astype(np.int32)
    res = bfs_pull(snap, seeds, hops)
    rows = visited_rows(res, snap.num_atoms)
    counts = np.asarray(res.edges_touched)
    reach = np.asarray(res.reach_counts)
    for k, s in enumerate(seeds.tolist()):
        want, edges = host_bfs(snap, s, hops)
        got = set(rows[k].tolist())
        assert got == want, f"seed {s}: {got ^ want}"
        assert counts[k] == edges
        assert reach[k] == len(want)


def test_pull_matches_bitfrontier():
    from hypergraphdb_tpu.ops.bitfrontier import bfs_packed, unpack_visited

    snap = random_snapshot(600, 500, 5, seed=3)
    seeds = np.arange(0, 64, dtype=np.int32) * 7 % 600
    res = bfs_pull(snap, seeds, 2)
    vis_old, cnt_old, _ = bfs_packed(snap, seeds, 2, k_block=64)
    old_bool = unpack_visited(vis_old, snap.num_atoms)
    rows = visited_rows(res, snap.num_atoms)
    for k in range(len(seeds)):
        assert set(rows[k].tolist()) == set(np.nonzero(old_bool[k])[0].tolist())
    assert np.array_equal(np.asarray(res.edges_touched), cnt_old.astype(np.int32))


@pytest.mark.parametrize("seeds_are", ["duplicates", "isolated"])
def test_duplicate_padded_and_isolated_seeds(seeds_are):
    """Duplicates beside K % 32 != 0; and seeds no link touches — an empty
    frontier after hop 1: nothing is placed, the later hops pull an
    unchanged bitmap, and every seed reaches itself alone."""
    snap = random_snapshot(100, 80, 3, seed=9)
    deg = np.diff(snap.inc_offsets[:101].astype(np.int64))
    seeds = (np.asarray([5, 5, 17], dtype=np.int32)
             if seeds_are == "duplicates"
             else np.flatnonzero(deg == 0)[:3].astype(np.int32))
    assert len(seeds) == 3
    res = bfs_pull(snap, seeds, 3)
    rows = visited_rows(res, snap.num_atoms)
    assert res.edges_touched.shape == (3,)
    if seeds_are == "duplicates":
        assert set(rows[0].tolist()) == set(rows[1].tolist())
    else:
        assert [r.tolist() for r in rows[:3]] == [[int(s)] for s in seeds]
        assert not res.edges_touched.any()
    _assert_matches_host(snap, seeds, 3, res)


@pytest.mark.parametrize("first_hop,count_edges",
                         [("by_rule", True), ("dense", False)])
def test_chunked_scan_and_multiblock(first_hop, count_edges, monkeypatch):
    """Exercise the chunk-streamed _reduce_level scan path (E > chunk*w) and
    the multi-block k_block driver — the two paths that otherwise only
    activate at benchmark scale. As the rule sends it (32 seeds a block: a
    sparse first hop), counting edges; and on the dense chain from the
    first hop with ``count_edges=False``: no degree pass runs, and the
    bitmap and the reach counts are the counting run's, bit for bit."""
    snap = random_snapshot(500, 400, 5, seed=21, zipf=True)
    r = np.random.default_rng(17)
    seeds = r.integers(0, 500, size=96).astype(np.int32)
    counted = bfs_pull(snap, seeds, 2, chunk=4, k_block=32)
    if first_hop == "dense":
        monkeypatch.setattr(eb, "SPARSE_SHARE", 1 << 62)
    t0 = _phase_count("hg.bfs.hop.deg_sum")
    with _Sides() as ran:
        res = bfs_pull(snap, seeds, 2, chunk=4, k_block=32,
                       count_edges=count_edges)
    # three blocks, two hops each
    assert (ran.sparse, ran.dense) == ((3, 3) if first_hop == "by_rule"
                                       else (0, 6))
    assert _phase_count("hg.bfs.hop.deg_sum") - t0 == 3 * count_edges
    rows = visited_rows(res, snap.num_atoms)
    counts = np.asarray(res.edges_touched)
    assert counts.dtype == np.int64
    for k in (0, 31, 32, 63, 64, 95):  # spans all three k-blocks
        want, edges = host_bfs(snap, int(seeds[k]), 2)
        assert set(rows[k].tolist()) == want
        assert counts[k] == (edges if count_edges else 0)
    assert np.array_equal(np.asarray(res.visited_t),
                          np.asarray(counted.visited_t))
    assert np.array_equal(np.asarray(res.reach_counts),
                          np.asarray(counted.reach_counts))


@pytest.mark.parametrize("operator", ["bfs_pull", "path_match"])
def test_k_block_validation(operator):
    """Refused before a plan is built for the call."""
    snap = random_snapshot(50, 40, 3, seed=2, n_types=2)
    seeds = np.arange(8, dtype=np.int32)
    for k_block in (48, 0):
        with pytest.raises(ValueError, match="k_block"):
            if operator == "bfs_pull":
                bfs_pull(snap, seeds, 1, k_block=k_block, link_types=(1,))
            else:
                eb.path_match(snap, seeds, [(1,), None], k_block=k_block)
    assert not hasattr(snap, "_pull_plans")
    assert not hasattr(snap, "_pull_restricted")


def test_reduce_plan_shapes():
    offsets = np.asarray([0, 0, 3, 3, 20])  # empty, 3-row, empty, 17-row
    flat = np.arange(20, dtype=np.int64) % 7
    plan = build_reduce_plan(offsets, flat, 4, zero_row=7, classes=(4,),
                             w_upper=4)
    # empty rows address the global zero row at concat_size
    assert plan.out_map[0] == plan.concat_size
    assert plan.out_map[2] == plan.concat_size
    assert all(len(l) % w == 0 for l, w in zip(plan.levels, plan.widths))
    # row 3 has 17 entries → 5 chunks at w=4 → needs 2 levels above level 0
    assert plan.n_lvl0 == 1 and len(plan.levels) >= 3


def test_plans_cached():
    snap = random_snapshot(50, 40, 3, seed=1)
    assert plans_for(snap) is plans_for(snap)


# ------------------------------------------------------ the stage programs
#
# The programs a dense hop is made of, one at a time, against numpy: a
# built ``ReducePlan`` run by ``_stage`` (stage 1's whole pyramid in one
# program) and by ``_stage_lvl0_consume`` + ``_stage_upper`` (stage 2's, in
# two), read through ``out_map``, is the segmented OR of the value rows;
# ``_visited_update`` is ``visited | reach[out_map]``.

STAGE_W = 4


def _stage_rows(rows, r):
    """(row degrees, levels the plan must have) of one case; level 0 and
    the upper levels are 4 wide."""
    if rows == "one_row":      # all empty but one: 6 chunks → 2 → 1
        deg = np.zeros(9, np.int64)
        deg[4] = 23
        return deg, 3
    if rows == "short":        # uniform short rows: level 0 alone
        return np.full(12, 2, np.int64), 1
    if rows == "hub":          # 18 chunks → 5 → 2 → 1: three upper levels
        deg = r.integers(1, 4, size=30)
        deg[11] = 69
        return deg, 4
    deg = r.integers(0, 10, size=23)  # "ragged": empty rows, rows past
    deg[[0, 22]] = (0, 5)             # one chunk, an odd chunk count
    return deg, 2


def _segment_or(values, offsets, flat, deg):
    want = np.zeros((len(deg), values.shape[1]), np.uint32)
    nz = np.flatnonzero(deg)
    want[nz] = np.bitwise_or.reduceat(values[flat], offsets[nz], axis=0)
    return want


def _run_stages(plan, values, chunk):
    """The plan through ``_stage`` (one program) and through
    ``_stage_lvl0_consume`` + ``_stage_upper`` (two): the same buffer."""
    n = plan.n_lvl0
    levels = tuple(np.asarray(l) for l in plan.levels)
    whole = np.asarray(eb._stage(values, levels, plan.widths, n, chunk,
                                 False))
    assert whole.shape == (plan.concat_size + 1, values.shape[1])
    assert not whole[plan.concat_size].any()  # the global zero row
    sizes = [len(l) // w for l, w in zip(plan.levels, plan.widths)]
    lvl0 = eb._stage_lvl0_consume(values, levels[:n], plan.widths[:n],
                                  chunk, False)
    assert lvl0.shape == (sum(sizes[:n]), values.shape[1])
    split = np.asarray(eb._stage_upper(lvl0, levels[n:], plan.widths[n:],
                                       sizes[n - 1], chunk))
    assert np.array_equal(split, whole)
    return whole


@pytest.mark.parametrize("kw", [1, 4])
@pytest.mark.parametrize("chunk", ["steps", "whole"])
@pytest.mark.parametrize("classes", [(STAGE_W,), (2, STAGE_W)],
                         ids=["one_width", "classed"])
@pytest.mark.parametrize("rows", ["one_row", "short", "hub", "ragged"])
def test_stage_matches_numpy_segmented_or(rows, classes, chunk, kw):
    """``chunk`` smaller than level 0 (the scan takes several steps, and
    in the ragged case leaves a tail) and larger than it (one update);
    level 0 as one width-4 array (the plan before it had classes) and as
    classes 2 and 4."""
    r = np.random.default_rng([len(rows), kw])
    deg, n_levels = _stage_rows(rows, r)
    n_rows, S = len(deg), 40
    offsets = np.concatenate([[0], np.cumsum(deg)])
    flat = r.integers(0, S, size=int(offsets[-1]))
    plan = build_reduce_plan(offsets, flat, n_rows, zero_row=S,
                             classes=classes, w_upper=STAGE_W)
    n0 = len(plan.levels[plan.n_lvl0 - 1]) // STAGE_W
    # a level-0 step moves chunk * 8 indices: 2 rows of width 4 at chunk 1
    chunk = 1 if chunk == "steps" else 1 << 10
    assert (n0 > 4) if chunk == 1 else (n0 < chunk)
    assert len(plan.levels) - plan.n_lvl0 == n_levels - 1
    held = np.minimum(np.searchsorted(classes, deg[deg > 0]),
                      len(classes) - 1)  # a class no row falls in has none
    assert plan.widths[: plan.n_lvl0] == tuple(
        classes[c] for c in np.unique(held))
    if rows == "ragged":
        assert n0 % 2 and (deg % STAGE_W).any()

    values = r.integers(0, 1 << 32, size=(S + 1, kw), dtype=np.uint32)
    values[S] = 0  # the zero row level 0 pads with
    whole = _run_stages(plan, values, chunk)
    assert np.array_equal(whole[plan.out_map],
                          _segment_or(values, offsets, flat, deg))


# Level 0 in the module's own width classes: rows of every degree from
# empty to past three chunks of the widest class, and a hub above W_MAX².

def _classed_case(r, top_degree):
    deg = np.arange(0, top_degree + 1)
    r.shuffle(deg)
    S = 300
    offsets = np.concatenate([[0], np.cumsum(deg)])
    flat = r.integers(0, S, size=int(offsets[-1]))
    values = r.integers(0, 1 << 32, size=(S + 1, 2), dtype=np.uint32)
    values[S] = 0
    plan = build_reduce_plan(offsets, flat, len(deg), zero_row=S)
    return deg, offsets, flat, values, plan


@pytest.mark.parametrize("chunk", [16, 1 << 12])
@pytest.mark.parametrize("rows", ["every_degree", "with_hub"])
def test_classed_plan_matches_numpy_segmented_or(rows, chunk):
    """Every row of degree at most ``W_MAX`` is one chunk of the smallest
    class width that holds it, finished in that class's section; a row
    above is cut at ``W_MAX`` and finishes in an upper level; an empty row
    maps to the zero row; and the reduction is numpy's segmented OR."""
    r = np.random.default_rng(len(rows))
    deg, offsets, flat, values, plan = _classed_case(r, 3 * eb.W_MAX + 1)
    if rows == "with_hub":
        deg = np.concatenate([deg, [eb.W_MAX ** 2 + 5]])
        offsets = np.concatenate([[0], np.cumsum(deg)])
        flat = r.integers(0, 300, size=int(offsets[-1]))
        plan = build_reduce_plan(offsets, flat, len(deg), zero_row=300)
    assert plan.widths[: plan.n_lvl0] == eb.CLASS_WIDTHS
    assert all(len(l) % w == 0 for l, w in zip(plan.levels, plan.widths))
    sizes = [len(l) // w for l, w in zip(plan.levels, plan.widths)]
    ends = np.cumsum(sizes)
    assert plan.concat_size == ends[-1]
    # rows above W_MAX: 2-4 chunks each → one upper level of 8; the hub's
    # W_MAX + 1 chunks climb until one is left
    n_upper, chunks = 0, -(-int(deg.max()) // eb.W_MAX)
    while chunks > 1:
        n_upper, chunks = n_upper + 1, -(-chunks // 8)
    assert len(plan.levels) - plan.n_lvl0 == n_upper >= (
        2 if rows == "with_hub" else 1)
    for c, w in enumerate(eb.CLASS_WIDTHS):
        lo = eb.CLASS_WIDTHS[c - 1] if c else 0
        mine = (deg > lo) & (deg <= w)
        assert mine.any()
        assert ((plan.out_map[mine] >= ends[c] - sizes[c])
                & (plan.out_map[mine] < ends[c])).all()
        assert len(np.unique(plan.out_map[mine])) == mine.sum()
    above = deg > eb.W_MAX
    assert ((plan.out_map[above] >= ends[plan.n_lvl0 - 1])
            & (plan.out_map[above] < plan.concat_size)).all()
    assert (plan.out_map[deg == 0] == plan.concat_size).all()
    assert plan.upper_indices == sum(
        len(l) for l in plan.levels[plan.n_lvl0:]) > 0
    # level 0 holds every entry once, the rest pads
    real = sum(int(np.count_nonzero(l != 300))
               for l in plan.levels[: plan.n_lvl0])
    assert real == int(np.count_nonzero(flat != 300))

    whole = _run_stages(plan, values, chunk)
    assert np.array_equal(whole[plan.out_map],
                          _segment_or(values, offsets, flat, deg))


@pytest.mark.parametrize("top_degree", [1, 7, eb.W_MAX])
def test_no_row_above_w_max_no_upper_level(top_degree):
    """A relation whose rows all fit a class runs no upper level: the
    plan has none, ``_stage`` traces none, and the buffer is level 0 and
    the zero row."""
    r = np.random.default_rng(top_degree)
    deg, offsets, flat, values, plan = _classed_case(r, top_degree)
    assert plan.n_lvl0 == len(plan.levels) and plan.upper_indices == 0
    assert plan.widths == tuple(
        w for lo, w in zip((0,) + eb.CLASS_WIDTHS, eb.CLASS_WIDTHS)
        if lo < top_degree)
    assert plan.concat_size == np.count_nonzero(deg)
    text = eb._stage.lower(
        values, tuple(np.asarray(l) for l in plan.levels), plan.widths,
        plan.n_lvl0, 16, False).as_text(debug_info=True)
    assert "hg.bfs.stage1.lvl0" in text
    assert "hg.bfs.stage1.upper" not in text
    whole = _run_stages(plan, values, 16)
    assert np.array_equal(whole[plan.out_map],
                          _segment_or(values, offsets, flat, deg))


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("chunk", [8, 1 << 6, 1 << 16])
def test_a_scan_step_moves_the_same_indices_at_every_class(
        chunk, use_pallas, monkeypatch):
    """``chunk * STEP_WIDTH`` indices a step whatever the class width (less
    than one chunk short where the width does not divide it), so the XLA
    gather's transient does not follow the class; on the kernel, the whole
    segments of that, so that no step pads a segment."""
    steps = []
    monkeypatch.setattr(
        eb, "_reduce_into",
        lambda buf, off, values, idx, w, rows, use_pallas:
        steps.append((off, w, rows * w)) or buf)
    levels = tuple(np.zeros(w * (3 + c), np.int32)
                   for c, w in enumerate(eb.CLASS_WIDTHS))
    eb._reduce_classes(None, np.zeros((1, 128), np.uint32), levels,
                       eb.CLASS_WIDTHS, chunk, use_pallas)
    assert [w for _, w, _ in steps] == list(eb.CLASS_WIDTHS)
    assert [off for off, _, _ in steps] == [
        sum(range(3, 3 + c)) for c in range(len(steps))]
    whole = chunk * eb.STEP_WIDTH
    for _, w, n in steps:
        if use_pallas:
            assert n == eb._pg.whole_segments(whole, w) // w * w
            assert n % eb._pg._seg(w) == 0 or n < eb._pg._seg(w)
        assert whole - max(w, whole // 8) < n <= whole


def _listed_rows(out_map, blocks=None):
    """An update's third argument over ``out_map``: every row block of
    the bitmap listed, or the blocks numbered in ``blocks`` alone."""
    n_blocks = -(-len(out_map) // min(eb.UPDATE_ROWS, len(out_map)))
    listed = (np.ones(n_blocks, bool) if blocks is None
              else np.isin(np.arange(n_blocks), blocks))
    return eb._listed(jnp.asarray(out_map), listed)


@pytest.mark.parametrize("case", ["no_fresh_bit", "every_row_fresh",
                                  "zero_row"])
def test_visited_update_matches_numpy(case):
    """Every block listed — one full block of the row loop and the ragged
    one after it; the dummy row (``n_atoms``) is zero on the way out
    whatever it was given."""
    r = np.random.default_rng(len(case))
    n_pad, kw, n_chunks = eb.UPDATE_ROWS + 40, 1, 50
    n_atoms = n_pad - 3
    reach = r.integers(1, 1 << 32, size=(n_chunks + 1, kw), dtype=np.uint32)
    reach[n_chunks] = 0
    out_map = r.integers(0, n_chunks, size=n_pad).astype(np.int32)
    if case == "no_fresh_bit":      # every bit pulled is already there
        visited = reach[out_map] | np.uint32(1 << 31)
    elif case == "every_row_fresh":
        visited = np.zeros((n_pad, kw), np.uint32)
    else:                           # nothing reached: rows → the zero row
        visited = r.integers(0, 1 << 32, size=(n_pad, kw), dtype=np.uint32)
        out_map[:] = n_chunks
    want = visited | reach[out_map]
    want[n_atoms] = 0
    got = np.asarray(eb._visited_update(
        jnp.asarray(visited), jnp.asarray(reach), _listed_rows(out_map),
        jnp.int32(n_atoms)))
    assert np.array_equal(got, want)
    if case != "every_row_fresh":
        keep = np.arange(n_pad) != n_atoms
        assert np.array_equal(got[keep], visited[keep])


#: block numbers listed, of a bitmap of four whole row blocks and a ragged
#: fifth of 40 rows that holds the dummy row
BLOCK_LISTS = {
    "prefix": [0, 1],
    "scattered": [3, 0],
    "none": [],
    "all": [0, 1, 2, 3, 4],
    "clamped_last": [4],            # folded from n_pad - UPDATE_ROWS
    "dummy_row_unlisted": [1, 2],
}


@pytest.mark.parametrize("blocks", list(BLOCK_LISTS))
@pytest.mark.parametrize("update", ["_visited_update", "_frontier_replace"])
def test_update_folds_the_listed_blocks_and_no_other(update, blocks):
    """Both updates over random rows and a random old state, against
    numpy: a row of a listed block is folded (the ragged last block from
    ``n_pad - UPDATE_ROWS``, so with the rows before it that share its
    slice), every other row is handed back as it came, the dummy row
    zero wherever it lies."""
    ub = eb.UPDATE_ROWS
    r = np.random.default_rng(sorted(BLOCK_LISTS).index(blocks))
    n_pad, kw, n_chunks = 4 * ub + 40, 1, 50
    n_atoms = n_pad - 3
    reach = r.integers(1, 1 << 32, size=(n_chunks + 1, kw), dtype=np.uint32)
    reach[n_chunks] = 0
    out_map = r.integers(0, n_chunks + 1, size=n_pad).astype(np.int32)
    state = r.integers(0, 1 << 32, size=(n_pad, kw), dtype=np.uint32)
    folded = np.zeros(n_pad, bool)
    for b in BLOCK_LISTS[blocks]:
        start = min(b * ub, n_pad - ub)
        folded[start : start + ub] = True
    new = reach[out_map]
    want = np.where(folded[:, None],
                    state | new if update == "_visited_update" else new,
                    state)
    want[n_atoms] = 0
    rows = _listed_rows(out_map, BLOCK_LISTS[blocks])
    assert rows.starts.shape == (5,)  # a slot a block, whatever is listed
    assert int(rows.n_listed) == len(BLOCK_LISTS[blocks])
    got = np.asarray(getattr(eb, update)(
        jnp.asarray(state), jnp.asarray(reach), rows, jnp.int32(n_atoms)))
    assert np.array_equal(got, want)


#: ``_fold_rows``' cases by route: (state words a row — 0 a flat state —,
#: the state's dtype, combine, gain, the kernel form that fetches — None
#: the XLA gather)
FOLD_ROUTES = {
    "or": (128, np.uint32, lambda cur, reached: cur | reached, None, "or"),
    "replace": (128, np.uint32, lambda cur, reached: reached, None, "or"),
    "or_grew": (128, np.uint32, lambda cur, reached: cur | reached,
                eb._GREW, "or"),
    "labels": (0, np.int32, jnp.minimum, eb._LOWERED, "min"),
    "ranks": (0, np.float32, lambda cur, reached: reached, None, "sum"),
    "narrow_rows": (32, np.uint32, lambda cur, reached: cur | reached, None,
                    None),
}


def scalar_kernel_route(monkeypatch, min_indices: int = 64) -> list:
    """Send a whole-graph operator's level-0 gathers through the scalar form
    of the row-gather kernel on the CPU: ``pallas_ok`` says yes, the Pallas
    interpreter stands in for the chip, and ``MIN_INDICES`` comes down so
    that a small graph's classes engage it. Returns the widths of the calls
    the program traced, as ``gather_reduce`` records them."""
    calls, real = [], eb._pg.gather_reduce
    monkeypatch.setattr(eb._pg, "pallas_ok", lambda: True)
    monkeypatch.setattr(eb._pg, "MIN_INDICES", min_indices)
    monkeypatch.setattr(
        eb._pg, "gather_reduce",
        lambda v, i, w, op: calls.append((w, op)) or real(
            v, i, w, op, interpret=True))
    return calls


@pytest.mark.parametrize("chunk", [8, 1 << 16])
def test_wcc_round_on_the_kernel_gives_the_xla_routes_labels(chunk,
                                                              monkeypatch):
    """``connected_components`` with its level-0 gathers on the kernel's
    scalar form against the same rounds on the XLA gather: the labels bit
    for bit, as many rounds, and the counters' share of kernel indices
    over 0 and up to 100% (a class's ragged tail under ``MIN_INDICES``
    keeps the XLA gather; at chunk 8 a scan block is 64 indices)."""
    from hypergraphdb_tpu.ops import connected_components
    from tests.test_pair_distances import linked_snapshot

    snap = linked_snapshot(700, 800, 11, n_types=4)
    want = connected_components(snap, (1, 3), chunk=chunk)
    calls = scalar_kernel_route(monkeypatch)
    reg = obs.default_registry()
    names = ("scalar.gather.indices", "scalar.gather.indices_kernel")
    before = [0 if reg.get(n) is None else reg.get(n).value for n in names]
    got = connected_components(snap, (1, 3), chunk=chunk)
    np.testing.assert_array_equal(np.asarray(got.labels),
                                  np.asarray(want.labels))
    assert (got.rounds, got.n_components) == (want.rounds, want.n_components)
    total, kernel = (reg.get(n).value - b for n, b in zip(names, before))
    sub = eb.restricted_for(snap, (1, 3))
    plans = eb.plans_for(sub)
    assert total == got.rounds * sum(
        len(l) for l in plans.stage1.levels[:plans.stage1.n_lvl0]
        + plans.stage2_levels[:plans.stage2_n_lvl0])
    assert bool(calls) == (kernel > 0) and {op for _, op in calls} <= {"min"}
    assert 0 < kernel <= total


def _kernel_fetches(monkeypatch) -> list:
    """Both forms of the row-gather kernel in the Pallas interpreter, each
    call's (form, width) recorded."""
    calls = []
    gather_or, gather_reduce = eb._pg.gather_or, eb._pg.gather_reduce
    monkeypatch.setattr(
        eb._pg, "gather_or", lambda v, i, w: calls.append(("or", w))
        or gather_or(v, i, w, interpret=True))
    monkeypatch.setattr(
        eb._pg, "gather_reduce", lambda v, i, w, op: calls.append((op, w))
        or gather_reduce(v, i, w, op, interpret=True))
    return calls


def _fold_case(case, r, n_pad, n_chunks):
    """A state of ``n_pad`` rows and a stage buffer of ``n_chunks`` rows and
    its zero row (the reduction's identity) for ``FOLD_ROUTES[case]``."""
    kw, dtype = FOLD_ROUTES[case][:2]
    if kw:
        reach = r.integers(0, 1 << 32, size=(n_chunks + 1, kw), dtype=dtype)
        return r.integers(0, 1 << 32, size=(n_pad, kw), dtype=dtype), reach
    if dtype == np.int32:
        reach = r.integers(0, n_pad, size=n_chunks + 1).astype(dtype)
        reach[n_chunks] = eb.INT32_MAX
        return np.arange(n_pad, dtype=dtype), reach
    reach = r.integers(1, 1 << 20, size=n_chunks + 1).astype(dtype)
    reach[n_chunks] = 0.0
    return np.zeros(n_pad, dtype=dtype), reach


@pytest.mark.parametrize("case", list(FOLD_ROUTES))
def test_fold_rows_fetches_on_the_kernel_where_it_serves_the_state(
        case, monkeypatch):
    """``_fold_rows`` with ``use_pallas`` over four blocks of 256 rows and
    a ragged fifth, the list holding the ragged last block, a block twice
    and the dummy row's block, against the same fold on the XLA gather:
    bit-equal state (the dummy row the identity) and count. A 128-word
    bitmap fetches through ``hg_gather_or`` at width 1, a flat int32 or
    float32 state through ``hg_gather_scalar`` (the Pallas interpreter
    here); a 32-word bitmap traces no ``pallas_call`` — the XLA route
    alone."""
    kw, _, combine, gain, form = FOLD_ROUTES[case]
    calls = _kernel_fetches(monkeypatch)
    ub = 256
    monkeypatch.setattr(eb._pg, "MIN_INDICES", ub)
    r = np.random.default_rng(sorted(FOLD_ROUTES).index(case))
    n_pad, n_chunks = 4 * ub + 40, 300
    n_atoms = n_pad - 3
    state, reach = _fold_case(case, r, n_pad, n_chunks)
    out_map = r.integers(0, n_chunks + 1, size=n_pad).astype(np.int32)
    listed = [4 * ub, 0, 2 * ub, 2 * ub, 3 * ub]  # ragged last, 2 twice
    rows = eb._UpdateRows(jnp.asarray(out_map),
                          jnp.asarray(np.asarray(listed + [0], np.int32)),
                          jnp.int32(len(listed)))
    args = (jnp.asarray(state), jnp.asarray(reach), rows, jnp.int32(n_atoms))
    fold = {k: jax.jit(partial(eb._fold_rows, combine=combine, block_rows=ub,
                               gain=gain, use_pallas=k))
            for k in (False, True)}
    assert ("pallas_call" in str(jax.make_jaxpr(fold[True])(*args))) \
        == (form is not None)
    assert "pallas_call" not in str(jax.make_jaxpr(fold[False])(*args))
    assert set(calls) == ({(form, 1)} if form else set())
    want, got = (jax.tree_util.tree_leaves(fold[k](*args))
                 for k in (False, True))
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))
    assert np.asarray(got[0])[n_atoms].tolist() == (
        [0] * kw if kw else reach[n_chunks])
    # the listed rows were folded: the fold did something to compare
    assert not np.array_equal(np.asarray(got[0]), state)


#: block lists of the scalar fold over five blocks of 256 rows, the last
#: ragged: which blocks a round lists (each folded from its clamped start)
SCALAR_FOLD_LISTS = {
    "skips": [1, 3],
    "ragged_twice": [3, 4],
    "all_dummy_last": [0, 1, 2, 3, 4],
}


@pytest.mark.parametrize("blocks", list(SCALAR_FOLD_LISTS))
@pytest.mark.parametrize("case", ["labels", "ranks"])
def test_scalar_fold_on_the_kernel_is_numpy(case, blocks, monkeypatch):
    """The fold's scalar route (``hg_gather_scalar`` at width 1 in the
    Pallas interpreter) against numpy: the min with ``_LOWERED``'s count
    and the replacement into zeros, over a list that skips blocks, the
    ragged last block beside the one it overlaps (those rows folded twice,
    lowered once) and every block; the dummy row set to the identity, the
    unlisted rows left as they were. The stage buffer is not whole (8,
    128) tiles: the fold pads it once, outside its loop."""
    _, _, combine, gain, form = FOLD_ROUTES[case]
    calls = _kernel_fetches(monkeypatch)
    ub = 256
    monkeypatch.setattr(eb._pg, "MIN_INDICES", ub)
    r = np.random.default_rng([len(blocks), case == "ranks"])
    n_pad, n_chunks = 4 * ub + 40, 300
    n_atoms = n_pad - 3
    state, reach = _fold_case(case, r, n_pad, n_chunks)
    out_map = r.integers(0, n_chunks, size=n_pad).astype(np.int32)
    out_map[n_atoms] = n_chunks
    listed = SCALAR_FOLD_LISTS[blocks]
    rows = eb._listed(jnp.asarray(out_map), np.isin(np.arange(5), listed),
                      block_rows=ub)
    fn = jax.jit(partial(eb._fold_rows, combine=combine, block_rows=ub,
                         gain=gain, use_pallas=True))
    out = fn(jnp.asarray(state), jnp.asarray(reach), rows,
             jnp.int32(n_atoms))
    got = np.asarray(out[0] if gain else out)
    assert calls == [(form, 1)]
    folded = np.zeros(n_pad, dtype=bool)
    for b in listed:
        start = min(b * ub, n_pad - ub)
        folded[start:start + ub] = True
    fetched = reach[out_map]
    want = np.where(folded, np.minimum(state, fetched) if gain else fetched,
                    state)
    want[n_atoms] = reach[n_chunks]
    np.testing.assert_array_equal(got, want)
    if gain:
        assert int(out[1]) == int(np.count_nonzero(folded
                                                   & (fetched < state)))


_UPDATE_TABLES = {
    # state, table, use_pallas, block rows: the form, or None (XLA)
    "bitmap": ((70_000, 128), "uint32", (9_000, 128), True, 1 << 16, "or"),
    "narrow_bitmap": ((70_000, 32), "uint32", (9_000, 32), True, 1 << 16,
                      None),
    "labels": ((70_000,), "int32", (9_000,), True, 1 << 16, "min"),
    "ranks": ((70_000,), "float32", (9_000,), True, 1 << 16, "sum"),
    "labels_whole_graph": ((10_000_072,), "int32", (2_108_462,), True,
                           1 << 16, "min"),
    "table_past_the_bound": ((70_000,), "int32",
                             (eb._pg.SCALAR_TABLE_BYTES // 4 + 1,), True,
                             1 << 16, None),
    "short_block": ((70_000,), "float32", (9_000,), True,
                    eb._pg.MIN_INDICES // 2, None),
    "xla_caller": ((70_000,), "int32", (9_000,), False, 1 << 16, None),
    "bitmap_xla_caller": ((70_000, 128), "uint32", (9_000, 128), False,
                          1 << 16, None),
}


@pytest.mark.parametrize("case", list(_UPDATE_TABLES))
def test_update_on_kernel_routes_by_state_and_table(case):
    """``_update_on_kernel``, the fold's one gate, as a table: 128-word
    uint32 rows take ``hg_gather_or`` as before; a flat int32 or float32
    state whose stage buffer the scalar gate admits takes its reduction's
    scalar form; a narrower bitmap, a buffer past ``SCALAR_TABLE_BYTES``
    (the state itself small), a block under ``MIN_INDICES`` or a caller
    whose gathers take XLA keep the XLA gather. It reads shapes alone."""
    shape, dtype, table, use_pallas, block_rows, form = _UPDATE_TABLES[case]
    state = jax.ShapeDtypeStruct(shape, dtype)
    buf = jax.ShapeDtypeStruct(table, dtype)
    assert eb._update_on_kernel(state, use_pallas, block_rows,
                                table=buf) == form
    if len(shape) == 2:  # a bitmap's rows are the table's: the same answer
        assert eb._update_on_kernel(state, use_pallas, block_rows) == form


# ------------------------------------------------------ the sparse first hop
#
# Which side of the rule ran is read off the phase counts, as an operator
# would: ``hg.bfs.hop.sparse`` once per block that took the sparse side,
# ``hg.bfs.hop.stage1`` once per dense hop. The dense chain is reached
# through the input (many seeds on a small graph) or, where the SAME input
# has to take both sides, by setting the rule's constant in the test.


def _phase_count(name):
    h = obs.default_registry().get(f"phase.{name}")
    return h.count if h is not None else 0


class _Sides:
    """(sparse first hops, dense hops) run inside the ``with`` block."""

    NAMES = ("hg.bfs.hop.sparse", "hg.bfs.hop.stage1")

    def __enter__(self):
        self._t0 = [_phase_count(n) for n in self.NAMES]
        return self

    def __exit__(self, *exc):
        self.sparse, self.dense = (
            _phase_count(n) - t for n, t in zip(self.NAMES, self._t0))


def _first_hop_pairs(snap, seeds):
    """(target, seed) pairs of the seeds' first hop, duplicates included:
    what the rule holds against the plan's size."""
    return sum(len(snap.targets_row(int(l)))
               for s in seeds for l in snap.incidence_row(int(s)).tolist())


def _assert_matches_host(snap, seeds, hops, res):
    rows = visited_rows(res, snap.num_atoms)
    for k, s in enumerate(seeds.tolist()):
        want, edges = host_bfs(snap, s, hops)
        if s == snap.num_atoms:  # a pad seed reaches nothing, not itself
            want, edges = set(), 0
        assert set(rows[k].tolist()) == want, f"seed {s} (column {k})"
        assert res.edges_touched[k] == edges, f"seed {s} (column {k})"
        assert int(res.reach_counts[k]) == len(want)


@pytest.mark.parametrize("zipf", [False, True])
@pytest.mark.parametrize("hops", [1, 2, 3])
def test_sparse_first_hop_matches_host_and_dense_chain(zipf, hops,
                                                       monkeypatch):
    """Few seeds on a graph large enough for the rule — the hub among them,
    a duplicate, pad seeds (explicit and from K % 32), a seed nothing
    points at — over two seed blocks: the answers are the host BFS's and,
    bit for bit, the dense chain's."""
    # enough atoms that the zipf hub's pairs stay under the rule's share of
    # the plan (its out_map is an index an atom)
    n = 80000 if zipf else 30000
    snap = random_snapshot(n, 3000, 4, seed=31 + hops, zipf=zipf)
    deg = np.diff(snap.inc_offsets[: n + 1].astype(np.int64))
    hub, lonely = int(np.argmax(deg)), int(np.argmin(deg))
    assert deg[lonely] == 0 and deg[hub] > (200 if zipf else 3)
    r = np.random.default_rng(hops)
    seeds = np.concatenate([
        [hub, lonely, 7, 7, snap.num_atoms],
        r.integers(0, n, size=35),
    ]).astype(np.int32)  # 40 seeds: blocks of 32 and 8 (+ 24 pad columns)
    with _Sides() as ran:
        res = bfs_pull(snap, seeds, hops, k_block=32)
    assert (ran.sparse, ran.dense) == (2, 2 * (hops - 1))
    _assert_matches_host(snap, seeds, hops, res)

    monkeypatch.setattr(eb, "SPARSE_SHARE", 1 << 62)  # no input is sparse
    with _Sides() as ran:
        dense = bfs_pull(snap, seeds, hops, k_block=32)
    assert (ran.sparse, ran.dense) == (0, 2 * hops)
    assert np.array_equal(np.asarray(res.visited_t),
                          np.asarray(dense.visited_t))
    assert np.array_equal(res.edges_touched, dense.edges_touched)
    assert np.array_equal(np.asarray(res.reach_counts),
                          np.asarray(dense.reach_counts))


@pytest.mark.parametrize("side", ["below", "at"])
def test_rule_takes_the_side_the_pair_count_says(side):
    """The threshold is ``total_indices // SPARSE_SHARE`` pairs: the longest
    run of seeds that stays below it takes the sparse side, one seed more
    the dense one."""
    snap = random_snapshot(400, 300, 4, seed=12)
    limit = plans_for(snap).total_indices // eb.SPARSE_SHARE
    order = np.random.default_rng(3).permutation(400).astype(np.int32)
    cum = np.cumsum([_first_hop_pairs(snap, [s]) for s in order])
    m = int(np.searchsorted(cum, limit))  # cum[m-1] < limit <= cum[m]
    assert 8 < m < len(order) and cum[m] > cum[m - 1]
    seeds = order[: m if side == "below" else m + 1]
    with _Sides() as ran:
        res = bfs_pull(snap, seeds, 2)
    assert (ran.sparse, ran.dense) == ((1, 1) if side == "below" else (0, 2))
    _assert_matches_host(snap, seeds, 2, res)


@pytest.mark.parametrize("count_edges", [True, False])
def test_sparse_first_hop_in_several_placement_blocks(count_edges,
                                                      monkeypatch):
    """More pairs than one placement dispatch carries: more dispatches of
    the one program, the last one padded with the dummy row."""
    monkeypatch.setattr(eb, "SPARSE_BLOCK", 64)
    calls = []
    placed = eb._sparse_hop
    monkeypatch.setattr(
        eb, "_sparse_hop",
        lambda v, pairs, n: calls.append(pairs.shape) or placed(v, pairs, n))
    snap = random_snapshot(80000, 3000, 4, seed=8, zipf=True)
    seeds = np.asarray([1, 40, 41, 42, 43, 44], np.int32)  # 1: the hub
    pairs = _first_hop_pairs(snap, seeds)
    with _Sides() as ran:
        res = bfs_pull(snap, seeds, 1, count_edges=count_edges)
    assert (ran.sparse, ran.dense) == (1, 0)
    assert len(calls) > 3 and set(calls) == {(2, 64)}
    assert len(calls) <= -(-pairs // 64)  # unique pairs, seeds' own left out
    rows = visited_rows(res, snap.num_atoms)
    for k, s in enumerate(seeds.tolist()):
        want, edges = host_bfs(snap, s, 1)
        assert set(rows[k].tolist()) == want
        assert res.edges_touched[k] == (edges if count_edges else 0)


# ------------------------------------------------------ a hub above W_MAX²
#
# A zipf graph whose top entity has more than ``W_MAX ** 2`` incident links
# (under the predicate too), so that stage 2 cuts its row at ``W_MAX`` and
# climbs two upper levels while every other row finishes in its class: the
# dense chain from the first hop, at a 64-seed block (the XLA gather) and
# at a 4096-seed block with ``hg_gather_or`` run by the Pallas interpreter.


@pytest.fixture(scope="module")
def hub_graph():
    snap = random_snapshot(20000, 14000, 4, seed=5, zipf=True, n_types=3)
    deg = np.diff(snap.inc_offsets[:20001].astype(np.int64))
    return snap, int(np.argmax(deg))


@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("k", [64, 4096])
def test_hub_above_w_max_squared_matches_host(hub_graph, k, typed,
                                              monkeypatch):
    snap, hub = hub_graph
    family = (1, 3) if typed else None
    sub = eb.restricted_for(snap, family) if typed else snap
    assert sub.inc_offsets[hub + 1] - sub.inc_offsets[hub] > eb.W_MAX ** 2
    plans = plans_for(sub)
    assert plans.stage1.n_lvl0 == len(plans.stage1.levels)  # arity <= 4
    assert len(plans.stage2_levels) - plans.stage2_n_lvl0 >= 2
    assert 0 < plans.upper_indices < plans.total_indices // 50
    monkeypatch.setattr(eb, "SPARSE_SHARE", 1 << 62)  # the chain alone
    r = np.random.default_rng(k)
    seeds = r.integers(0, 20000, size=k).astype(np.int32)
    seeds[3] = hub
    res = bfs_pull(snap, seeds, 2, chunk=1 << 12, k_block=k,
                   link_types=family)
    if k == 4096:  # the same block again, through the kernel
        calls, gather = [], eb._pg.gather_or
        monkeypatch.setattr(eb._pg, "pallas_ok", lambda: True)
        monkeypatch.setattr(
            eb._pg, "gather_or",
            lambda v, i, w: calls.append(w) or gather(v, i, w,
                                                      interpret=True))
        # the long classes alone: each width the interpreter runs is
        # seconds of compile here
        monkeypatch.setattr(eb._pg, "MIN_INDICES", 1 << 13)
        fetched = [_counter(n) for n in ("bfs.update.rows_visited",
                                         "bfs.update.rows_kernel")]
        kernel = bfs_pull(snap, seeds, 2, chunk=1 << 12, k_block=k,
                          link_types=family)
        # the update that ends the dense hops fetches on the kernel too,
        # every row it folds (width 1)
        assert set(calls) >= {1, 4, eb.W_MAX}
        visited, by_kernel = (_counter(n) - t for n, t in zip(
            ("bfs.update.rows_visited", "bfs.update.rows_kernel"), fetched))
        assert by_kernel == visited > 0
        assert np.array_equal(np.asarray(res.visited_t),
                              np.asarray(kernel.visited_t))
        assert np.array_equal(res.edges_touched, kernel.edges_touched)
        assert np.array_equal(np.asarray(res.reach_counts),
                              np.asarray(kernel.reach_counts))
    cols = [3] + r.choice(np.arange(4, k), 7, replace=False).tolist()
    vt = np.asarray(res.visited_t)[: snap.num_atoms]
    for c in cols:
        want, edges = host_bfs(snap, int(seeds[c]), 2, family)
        got = np.flatnonzero((vt[:, c >> 5] >> np.uint32(c & 31)) & 1)
        assert set(got.tolist()) == want, f"seed {seeds[c]} (column {c})"
        assert res.edges_touched[c] == edges
        assert int(res.reach_counts[c]) == len(want)


# ------------------------------------------------------ the link predicate
#
# ``bfs_pull(..., link_types=F)`` against the repo's host oracle,
# ``HGBreadthFirstTraversal`` over ``DefaultALGenerator(link_predicate =
# type in F)``, on a real ``HyperGraph``: links of four types, a link that
# targets a link, a hub, an atom no link of some families touches. Both
# sides of the first-hop rule, chosen through the rule's constant as above.

LINK_TYPES = ("knows", "likes", "cites", "tags")
FAMILIES = {"empty": (), "one": ("likes",), "several": ("knows", "cites"),
            "all": LINK_TYPES}


@pytest.fixture(scope="module")
def typed_graph():
    from hypergraphdb_tpu import HyperGraph
    from hypergraphdb_tpu.types.primitive import StringType

    g = HyperGraph()
    handle = {}
    for name in LINK_TYPES:
        t = type(name, (StringType,), {})()
        t.name = name
        handle[name] = int(g.typesystem.register(t))
    r = np.random.default_rng(27)
    nodes = [int(g.add(f"n{i}")) for i in range(48)]
    hub, lonely = nodes[0], nodes[47]  # lonely: only a "tags" link
    links = []
    for i in range(90):
        name = LINK_TYPES[int(r.integers(0, 4))]
        ends = r.choice(nodes[1:47], int(r.integers(2, 5)), replace=False)
        ends = [hub, *ends] if i % 3 == 0 else list(ends)
        links.append(int(g.add_link(ends, value=f"l{i}", type=name)))
    g.add_link((lonely, nodes[5]), value="t", type="tags")
    # links that target a link, of a type that differs from its target's
    for i, name in enumerate(LINK_TYPES):
        g.add_link((nodes[10 + i], links[i], links[20 + i]),
                   value=f"m{i}", type=name)
    seeds = np.asarray([hub, lonely, links[0], links[21], *nodes[10:14],
                        nodes[30]], dtype=np.int32)
    yield g, g.snapshot(), handle, seeds
    g.close()


def _oracle(g, family, seed, hops):
    """(visited set, admitted links looked at) by the host traversal."""
    from hypergraphdb_tpu.algorithms.traversals import (
        DefaultALGenerator,
        HGBreadthFirstTraversal,
    )

    def admitted(graph, link):
        return int(graph.get_type_handle_of(link)) in family

    def within(h):
        gen = DefaultALGenerator(g, link_predicate=admitted)
        return {seed} | {int(a) for _, a in HGBreadthFirstTraversal(
            g, seed, gen, max_distance=h)}

    edges = sum(admitted(g, int(l)) for a in within(hops - 1)
                for l in g.get_incidence_set(a))
    return within(hops), edges


@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
@pytest.mark.parametrize("hops", [1, 2, 3])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_link_predicate_matches_host_traversal(typed_graph, family, hops,
                                               first_hop, monkeypatch):
    g, snap, handle, seeds = typed_graph
    fam = {handle[n] for n in FAMILIES[family]}
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    with _Sides() as ran:
        res = bfs_pull(snap, seeds, hops, link_types=fam)
    if not fam:  # nothing to follow: no hop runs
        assert (ran.sparse, ran.dense) == (0, 0)
    elif first_hop == "sparse":
        assert (ran.sparse, ran.dense) == (1, hops - 1)
    else:
        assert (ran.sparse, ran.dense) == (0, hops)
    rows = visited_rows(res, snap.num_atoms)
    for k, s in enumerate(seeds.tolist()):
        want, edges = _oracle(g, fam, s, hops)
        assert set(rows[k].tolist()) == want, f"seed {s} (column {k})"
        assert int(res.reach_counts[k]) == len(want)
        assert res.edges_touched[k] == edges
    if family == "empty":
        assert all(set(rows[k].tolist()) == {int(s)}
                   for k, s in enumerate(seeds))
    if family == "one":  # the lonely atom has no admitted link
        assert set(rows[1].tolist()) == {int(seeds[1])}


@pytest.mark.parametrize("hops", [1, 3])
def test_no_predicate_is_the_family_of_all_types_is_todays_answer(
        typed_graph, hops):
    """``None``, every link type, and every link type beside a type atom no
    link has: one answer bit for bit, over one snapshot and one plan."""
    g, snap, handle, seeds = typed_graph
    plain = bfs_pull(snap, seeds, hops)
    _assert_matches_host(snap, seeds, hops, plain)
    every = set(handle.values())
    entity_type = int(snap.type_of[int(seeds[0])])
    for fam in (every, every | {entity_type, 10 ** 6}):
        assert eb.restricted_for(snap, fam) is snap
        typed = bfs_pull(snap, seeds, hops, link_types=fam)
        assert np.array_equal(np.asarray(plain.visited_t),
                              np.asarray(typed.visited_t))
        assert np.array_equal(plain.edges_touched, typed.edges_touched)
        assert np.array_equal(np.asarray(plain.reach_counts),
                              np.asarray(typed.reach_counts))


def test_one_restriction_and_plan_per_family(typed_graph):
    """Phase ``hg.bfs.restrict`` fires once per (snapshot, family), its
    plan's size lands in the two gauges, and the restricted plan gathers
    admitted entries only."""
    g, snap, handle, seeds = typed_graph
    fam = [handle["knows"], handle["tags"]]
    t0 = _phase_count("hg.bfs.restrict")
    sub = eb.restricted_for(snap, fam)
    assert sub is not snap and sub.num_atoms == snap.num_atoms
    assert _phase_count("hg.bfs.restrict") == t0 + 1
    reg = obs.default_registry()
    assert reg.get("bfs.plan.total_indices").value == \
        plans_for(sub).total_indices
    assert reg.get("bfs.plan.entries").value == \
        sub.n_edges_inc + sub.n_edges_tgt
    for _ in range(2):
        bfs_pull(snap, seeds, 2, link_types=reversed(fam))
    assert _phase_count("hg.bfs.restrict") == t0 + 1
    # every entry the restricted relations hold belongs to an admitted link
    admitted = np.isin(snap.type_of, fam)
    assert admitted[sub.tgt_src[: sub.n_edges_tgt]].all()
    assert admitted[sub.inc_links[: sub.n_edges_inc]].all()
    assert sub.n_edges_tgt == int(
        admitted[snap.tgt_src[: snap.n_edges_tgt]].sum())
    assert 0 < sub.n_edges_tgt < snap.n_edges_tgt
    assert plans_for(sub).total_indices < plans_for(snap).total_indices
    assert plans_for(sub) is not plans_for(snap)


# ------------------------------------------------------ the counting passes
#
# ``_bitdot`` is every per-seed number the traversal returns (``_deg_sum``,
# ``_reach_counts``): exact ``int32`` sums against the
# definition in ``int64`` numpy, at every row width the seed blocks take.


def _bitdot_definition(packed, vec):
    """Σ_r vec[r] · bit(r, k), column k = word * 32 + bit, in int64."""
    bits = (packed[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return (bits.reshape(len(packed), 32 * packed.shape[1]).astype(np.int64)
            * vec[:, None].astype(np.int64)).sum(axis=0)


#: which row blocks a pass is handed, from the bitmap's number of blocks
#: (one where the rows are fewer than a block), in the order listed
BITDOT_LISTS = {
    "every": lambda n: list(range(n)),
    "subset": lambda n: [b for b in (2, 0) if b < n - 1],  # never the last
    "last_alone": lambda n: [n - 1],
    "last_then_the_one_before": lambda n: [n - 1] + [n - 2] * (n > 1),
}


@pytest.mark.parametrize("listed", list(BITDOT_LISTS))
@pytest.mark.parametrize("weights", ["ones", "degrees"])
@pytest.mark.parametrize("rows", ["short", "multiple", "ragged"])
@pytest.mark.parametrize("kw", [1, 4, 32, 128])
def test_bitdot_is_exact(kw, rows, weights, listed):
    """Rows fewer than a block (one narrower block), an exact multiple,
    and a ragged last block (its clamped start overlaps the block before;
    the row mask keeps it to its own rows). Degrees run to 800,000 and
    the column sums past 2^24, where a ``float32`` sum rounds.

    The pass counts the rows of the blocks it is handed and no other:
    every block listed it is the definition; a strict subset, the
    definition over those rows alone; the clamped last block alone counts
    none of the rows its slice shares with the block before, and listed
    with that block — in either order — each of them once."""
    block = 64
    R = {"short": 37, "multiple": 4 * block, "ragged": 3 * block + 29}[rows]
    r = np.random.default_rng(kw * 1000 + R)
    packed = r.integers(0, 1 << 32, size=(R, kw), dtype=np.uint32)
    packed[:, 0] |= np.uint32(1)  # a column every row counts in
    vec = (np.ones(R, np.int32) if weights == "ones"
           else r.integers(600_000, 800_001, size=R).astype(np.int32))
    n_blocks = -(-R // min(block, R))
    blocks = np.isin(np.arange(n_blocks), BITDOT_LISTS[listed](n_blocks))
    counted = np.repeat(blocks, block)[:R]
    want = _bitdot_definition(packed[counted], vec[counted])
    if weights == "degrees" and listed == "every":
        assert want[0] > 1 << 24 and want.max() < 1 << 31
    # column 0 holds every row: a strict subset counts fewer of them
    assert (want[0] == vec.sum()) == bool(blocks.all())
    # in the order the case lists them: it is no part of the answer
    order = np.asarray(BITDOT_LISTS[listed](n_blocks), np.int32)
    starts = np.zeros(n_blocks, np.int32)
    starts[: len(order)] = order * min(block, R)
    got = np.asarray(eb._bitdot(packed, vec, jnp.asarray(starts),
                                jnp.int32(len(order)), block))
    assert got.dtype == np.int32 and got.shape == (kw * 32,)
    assert np.array_equal(got, want)
    if listed == "every":
        # nor is the block size: at the module's own the bitmap is one block
        whole = eb._bitdot(packed, vec, *eb._block_starts(blocks[:1], R))
        assert np.array_equal(np.asarray(whole), want)


@pytest.mark.parametrize("count_edges", [True, False])
@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
@pytest.mark.parametrize("hops", [1, 2, 3])
def test_counts_are_exact_under_the_counting_schedule(
        typed_graph, hops, first_hop, typed, count_edges, monkeypatch):
    """``edges_touched`` is Σ deg over everything visited before the last
    hop (admitted links only under a predicate) whichever program or host
    array it is read from — the one ``_deg_sum`` of a block, or the seeds'
    own degrees where the only hop is sparse — and zeros where nothing
    counts edges; ``reach_counts`` is the visited set's size either way."""
    g, snap, handle, seeds = typed_graph
    fam = {handle[n] for n in FAMILIES["several"]} if typed else None
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    dense_hops = hops - (first_hop == "sparse")
    t0 = _phase_count("hg.bfs.hop.deg_sum")
    with _Sides() as ran:
        res = bfs_pull(snap, seeds, hops, link_types=fam,
                       count_edges=count_edges)
    assert ran.dense == dense_hops
    assert _phase_count("hg.bfs.hop.deg_sum") - t0 == \
        int(count_edges and dense_hops > 0)
    assert res.edges_touched.dtype == np.int64
    for k, s in enumerate(seeds.tolist()):
        want, edges = (_oracle(g, fam, s, hops) if typed
                       else host_bfs(snap, s, hops))
        assert int(res.reach_counts[k]) == len(want)
        assert res.edges_touched[k] == (edges if count_edges else 0)


# ------------------------------------------------------ a predicate per hop
#
# ``path_match(snap, seeds, [F1, F2, F3])`` against the plain reference
# ``algorithms/traversals.match_path``: on the ``HyperGraph`` of the link
# predicate's tests, and — through an adapter that hands ``match_path`` a
# snapshot's rows — on seeded random typed snapshots. Both sides of the
# first step's rule, chosen through the rule's constant as above.

PATHS = {
    "disjoint": [("knows",), ("likes",), ("cites",)],
    "overlapping": [("knows", "likes"), ("likes", "cites"),
                    ("cites", "knows")],
    "repeated": [("knows", "cites")] * 3,
    "with_none": [("likes",), None, ("tags", "knows")],
}


def _admits(family):
    """A ``match_path`` link predicate for a family of type handles."""
    if family is None:
        return None
    return lambda graph, link: int(graph.get_type_handle_of(link)) in family


def _families(handle, path):
    return [None if f is None else {handle[n] for n in f} for f in path]


def _assert_matches_reference(graph, n_atoms, seeds, steps, res):
    rows = visited_rows(res, n_atoms)
    counts = np.asarray(res.match_counts)
    assert counts.shape == (len(seeds),) and counts.dtype == np.int32
    for k, s in enumerate(np.asarray(seeds).tolist()):
        # a pad seed is no atom: it matches nothing, itself included
        want = set() if s == n_atoms else match_path(
            graph, s, [_admits(f) for f in steps])
        assert set(rows[k].tolist()) == want, f"seed {s} (column {k})"
        assert counts[k] == len(want)
    for k in range(len(seeds), len(rows)):  # pad columns past the seeds
        assert not len(rows[k])


@pytest.mark.parametrize("first_step", ["sparse", "dense"])
@pytest.mark.parametrize("n_steps", [1, 2, 3])
@pytest.mark.parametrize("path", list(PATHS))
def test_path_match_matches_match_path(typed_graph, path, n_steps,
                                       first_step, monkeypatch):
    g, snap, handle, seeds = typed_graph
    steps = _families(handle, PATHS[path][:n_steps])
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_step == "sparse" else 1 << 62)
    with _Sides() as ran:
        res = eb.path_match(snap, seeds, steps)
    assert (ran.sparse, ran.dense) == (
        (1, n_steps - 1) if first_step == "sparse" else (0, n_steps))
    assert res.frontier_t.shape == (plans_for(snap).n_pad, 1)
    _assert_matches_reference(g, snap.num_atoms, seeds, steps, res)


@pytest.mark.parametrize("first_step", ["sparse", "dense"])
@pytest.mark.parametrize("case", ["zero_steps", "empty_family_first",
                                  "empty_family_last", "type_no_link_has"])
def test_path_match_with_no_step_or_no_admitted_link(typed_graph, case,
                                                     first_step,
                                                     monkeypatch):
    """Zero steps give the seeds; a step whose family admits no link — an
    empty family, a type atom no link has — gives the empty answer (a
    traversal under it gives the seeds), and no hop runs."""
    g, snap, handle, seeds = typed_graph
    entity_type = int(snap.type_of[int(seeds[0])])
    steps = {"zero_steps": [],
             "empty_family_first": [(), {handle["knows"]}],
             "empty_family_last": [{handle["knows"]}, None, ()],
             "type_no_link_has": [None, {entity_type, 10 ** 6}]}[case]
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_step == "sparse" else 1 << 62)
    with _Sides() as ran:
        res = eb.path_match(snap, seeds, steps)
    assert (ran.sparse, ran.dense) == (0, 0)
    _assert_matches_reference(g, snap.num_atoms, seeds, steps, res)
    rows = visited_rows(res, snap.num_atoms)
    for k, s in enumerate(seeds.tolist()):
        assert rows[k].tolist() == ([s] if case == "zero_steps" else [])
    assert np.asarray(res.match_counts).tolist() == \
        [int(case == "zero_steps")] * len(seeds)


@pytest.mark.parametrize("first_step", ["sparse", "dense"])
def test_a_seed_keeps_its_bit_only_where_it_lies_in_an_admitted_link(
        typed_graph, first_step, monkeypatch):
    """The lonely atom lies in one "tags" link: under ``tags`` it is an end
    point of its own step (the chain's variables may bind one atom), under
    ``likes`` it is not, on either side of the rule; the hub is in both."""
    g, snap, handle, seeds = typed_graph
    hub, lonely = int(seeds[0]), int(seeds[1])
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_step == "sparse" else 1 << 62)
    for name, lonely_stays in (("tags", True), ("likes", False)):
        steps = [{handle[name]}]
        res = eb.path_match(snap, seeds, steps)
        _assert_matches_reference(g, snap.num_atoms, seeds, steps, res)
        rows = visited_rows(res, snap.num_atoms)
        assert hub in rows[0]
        assert (lonely in rows[1]) == lonely_stays
        assert len(rows[1]) == (2 if lonely_stays else 0)


class _SnapshotGraph:
    """What ``match_path`` asks of a graph, answered from a snapshot."""

    def __init__(self, snap):
        self.snap = snap

    def get_incidence_set(self, atom):
        return self.snap.incidence_row(int(atom)).tolist()

    def get_targets(self, link):
        return self.snap.targets_row(int(link)).tolist()

    def get_type_handle_of(self, atom):
        return int(self.snap.type_of[int(atom)])


RANDOM_PATHS = {
    "disjoint": [(1,), (2, 3), (4, 5)],
    "overlapping": [(1, 2, 3), (3, 4), (1, 4, 5)],
    "repeated": [(2, 5)] * 3,
    "with_none": [None, (1, 2), None],
}


@pytest.mark.parametrize("zipf", [False, True])
@pytest.mark.parametrize("n_steps", [1, 2, 3])
@pytest.mark.parametrize("path", list(RANDOM_PATHS))
def test_path_match_on_random_typed_graph_in_two_ragged_blocks(
        path, n_steps, zipf, monkeypatch):
    """40 seeds — the hub, an atom no link targets, a duplicate, a pad seed
    — in blocks of 32 and 8 (+ 24 pad columns): the sparse side by the
    rule as it stands, then the dense chain, the same bits."""
    n = 80000 if zipf else 30000
    snap = random_snapshot(n, 3000, 4, seed=41 + n_steps, zipf=zipf,
                           n_types=5)
    deg = np.diff(snap.inc_offsets[: n + 1].astype(np.int64))
    hub, lonely = int(np.argmax(deg)), int(np.argmin(deg))
    seeds = np.concatenate([
        [hub, lonely, 7, 7, snap.num_atoms],
        np.random.default_rng(n_steps).integers(0, n, size=35),
    ]).astype(np.int32)
    steps = RANDOM_PATHS[path][:n_steps]
    with _Sides() as ran:
        res = eb.path_match(snap, seeds, steps, k_block=32)
    assert (ran.sparse, ran.dense) == (2, 2 * (n_steps - 1))
    assert res.frontier_t.shape[1] == 2
    _assert_matches_reference(_SnapshotGraph(snap), snap.num_atoms, seeds,
                              steps, res)

    monkeypatch.setattr(eb, "SPARSE_SHARE", 1 << 62)  # no input is sparse
    with _Sides() as ran:
        dense = eb.path_match(snap, seeds, steps, k_block=32)
    assert (ran.sparse, ran.dense) == (0, 2 * n_steps)
    assert np.array_equal(np.asarray(res.frontier_t),
                          np.asarray(dense.frontier_t))
    assert np.array_equal(np.asarray(res.match_counts),
                          np.asarray(dense.match_counts))


def test_the_first_step_rule_reads_step_ones_plan(monkeypatch):
    """Seeds whose pairs under step 1's family are few beside step 1's
    restricted plan, but not beside a plan a tenth its size: the same
    seeds take the sparse side under one constant and the dense under the
    other, and the threshold is ``restricted plan // SPARSE_SHARE``."""
    snap = random_snapshot(400, 300, 4, seed=12, n_types=3)
    steps = [(1,), (2,)]
    sub = eb.restricted_for(snap, steps[0])
    limit = plans_for(sub).total_indices // eb.SPARSE_SHARE
    assert limit < plans_for(snap).total_indices // eb.SPARSE_SHARE
    order = np.random.default_rng(3).permutation(400).astype(np.int32)
    cum = np.cumsum([_first_hop_pairs(sub, [s]) for s in order])
    m = int(np.searchsorted(cum, limit))  # cum[m-1] < limit <= cum[m]
    assert 8 < m < len(order) and cum[m] > cum[m - 1]
    for seeds, sides in ((order[:m], (1, 1)), (order[: m + 1], (0, 2))):
        with _Sides() as ran:
            res = eb.path_match(snap, seeds, steps)
        assert (ran.sparse, ran.dense) == sides
        _assert_matches_reference(_SnapshotGraph(snap), snap.num_atoms,
                                  seeds, steps, res)


@pytest.mark.parametrize("case", ["no_fresh_bit", "every_row_fresh",
                                  "zero_row"])
def test_frontier_replace_matches_numpy(case):
    """``_visited_update``'s cases, every block listed: the new state is
    the reached rows alone, whatever the old frontier held, and the dummy
    row is zero on the way out."""
    r = np.random.default_rng(len(case))
    n_pad, kw, n_chunks = eb.UPDATE_ROWS + 40, 1, 50
    n_atoms = n_pad - 3
    reach = r.integers(1, 1 << 32, size=(n_chunks + 1, kw), dtype=np.uint32)
    reach[n_chunks] = 0
    out_map = r.integers(0, n_chunks, size=n_pad).astype(np.int32)
    if case == "no_fresh_bit":      # the old frontier holds them, and more
        frontier = reach[out_map] | np.uint32(1 << 31)
    elif case == "every_row_fresh":
        frontier = np.zeros((n_pad, kw), np.uint32)
    else:                           # nothing reached: rows → the zero row
        frontier = r.integers(0, 1 << 32, size=(n_pad, kw), dtype=np.uint32)
        out_map[:] = n_chunks
    want = reach[out_map]
    want[n_atoms] = 0
    got = np.asarray(eb._frontier_replace(
        jnp.asarray(frontier), jnp.asarray(reach), _listed_rows(out_map),
        jnp.int32(n_atoms)))
    assert np.array_equal(got, want)
    if case == "zero_row":
        assert not got.any()


@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("hops", [0, 1, 2, 3])
def test_bfs_pull_is_unchanged_and_is_the_union_of_the_matches(
        typed_graph, hops, typed):
    """The hop loop takes a sequence of plans, and ``bfs_pull`` hands it
    the same one H times: its answers are the host traversal's as before,
    and — one chain, two updates — the visited set after H hops is the
    seeds with the end points of the paths ``F``, ``F/F`` … ``F/…/F``."""
    g, snap, handle, seeds = typed_graph
    fam = {handle[n] for n in FAMILIES["several"]} if typed else None
    res = bfs_pull(snap, seeds, hops, link_types=fam)
    for k, s in enumerate(seeds.tolist()):
        want, edges = (_oracle(g, fam, s, hops) if typed and hops
                       else host_bfs(snap, s, hops))
        assert set(visited_rows(res, snap.num_atoms)[k].tolist()) == want
        assert res.edges_touched[k] == edges
        assert int(res.reach_counts[k]) == len(want)
    union = np.asarray(eb.path_match(snap, seeds, []).frontier_t)
    for h in range(1, hops + 1):
        union = union | np.asarray(
            eb.path_match(snap, seeds, [fam] * h).frontier_t)
    assert np.array_equal(np.asarray(res.visited_t), union)


# ------------------------------------------------------ the memo's bound


def _counter(name):
    c = obs.default_registry().get(name)
    return c.value if c is not None else 0


def test_restricted_for_lets_the_least_recently_used_family_go():
    import gc
    import weakref

    snap = random_snapshot(400, 300, 4, seed=21, n_types=8)
    seeds = np.arange(0, 64, dtype=np.int32)
    assert eb.RESTRICT_RESIDENT >= 4
    fams = [(t,) for t in range(1, eb.RESTRICT_RESIDENT + 2)]
    e0, r0 = (_counter("bfs.restrict.evictions"),
              _phase_count("hg.bfs.restrict"))
    before = bfs_pull(snap, seeds, 2, link_types=fams[1])
    subs = [eb.restricted_for(snap, f) for f in fams[:-1]]
    assert _counter("bfs.restrict.evictions") == e0
    assert obs.default_registry().get("bfs.restrict.resident").value == \
        eb.RESTRICT_RESIDENT
    assert eb.restricted_for(snap, fams[0]) is subs[0]  # a use: fams[1] is
    gone = weakref.ref(subs[1])                         # now the oldest
    gone_plan = weakref.ref(plans_for(subs[1]))
    gone_dev = weakref.ref(subs[1]._pull_device["out_map"])
    del subs
    assert gone() is not None

    eb.restricted_for(snap, fams[-1])  # one past the bound
    gc.collect()
    assert _counter("bfs.restrict.evictions") == e0 + 1
    assert obs.default_registry().get("bfs.restrict.resident").value == \
        eb.RESTRICT_RESIDENT
    assert gone() is None and gone_plan() is None and gone_dev() is None
    assert set(snap._pull_restricted) == \
        {frozenset(f) for f in fams if f != fams[1]}
    assert _phase_count("hg.bfs.restrict") == r0 + len(fams)

    # it comes back: rebuilt, and the same answer
    after = bfs_pull(snap, seeds, 2, link_types=fams[1])
    assert _phase_count("hg.bfs.restrict") == r0 + len(fams) + 1
    assert _counter("bfs.restrict.evictions") == e0 + 2
    assert np.array_equal(np.asarray(before.visited_t),
                          np.asarray(after.visited_t))
    assert np.array_equal(before.edges_touched, after.edges_touched)
    assert np.array_equal(np.asarray(before.reach_counts),
                          np.asarray(after.reach_counts))


def test_a_three_step_match_beside_a_typed_traversal_evicts_nothing():
    snap = random_snapshot(400, 300, 4, seed=22, n_types=8)
    seeds = np.arange(0, 32, dtype=np.int32)
    e0, r0 = (_counter("bfs.restrict.evictions"),
              _phase_count("hg.bfs.restrict"))
    for _ in range(3):
        bfs_pull(snap, seeds, 3, link_types=(1, 2))
        eb.path_match(snap, seeds, [(3,), (4, 5), (6,)])
    assert _counter("bfs.restrict.evictions") == e0
    assert _phase_count("hg.bfs.restrict") == r0 + 4
    assert len(snap._pull_restricted) == 4


# ------------------------------------------- the update's active row blocks
#
# An update folds the row blocks it is handed and no other
# (``UPDATE_ROWS`` rows a block). The graphs here are wide enough to have
# several: entities in two ranges more than a block apart, atoms nothing
# touches between them, the links — which nothing targets — last, in the
# ragged block that also holds the dummy row.


def spread_snapshot(seed, n_links=3000):
    """(snapshot, range A, range B, links): a link's type says where its
    targets lie — 1: in A alone, 2: in B alone, 3: in both ranges."""
    ub = eb.UPDATE_ROWS
    r = np.random.default_rng(seed)
    a = np.arange(100, 1100)
    b = np.arange(2 * ub + 50, 2 * ub + 1050)
    n = 3 * ub + 5000
    links = np.arange(n - n_links, n)
    type_of = np.zeros(n, dtype=np.int32)
    is_link = np.zeros(n, dtype=bool)
    is_link[links] = True
    type_of[links] = 1 + r.integers(0, 3, size=n_links)
    arity = r.integers(2, 5, size=n_links)
    offsets = np.zeros(n + 1, dtype=np.int64)
    offsets[links[0] + 1 :] = np.cumsum(arity)
    kind = np.repeat(type_of[links], arity)
    pick = r.integers(0, len(a), size=len(kind))
    from_b = (kind == 2) | ((kind == 3) & (r.random(len(kind)) < 0.5))
    flat = np.where(from_b, b[pick], a[pick])
    snap = CSRSnapshot.from_tables(type_of, is_link, offsets, flat)
    return snap, a, b, links


@pytest.fixture(scope="module")
def spread():
    return spread_snapshot(61)


def _blocks_with(rows):
    return sorted({int(v) // eb.UPDATE_ROWS for v in rows})


@pytest.mark.parametrize("graph", ["random", "two_ranges", "one_family"])
def test_plan_block_list_covers_every_row_a_hop_can_reach(graph, spread):
    """The list derived from a built plan holds a block if and only if
    some row of it reads another row of the stage buffer than the zero
    row; what goes up beside ``out_map`` is that list, a slot a block."""
    if graph == "random":
        snap = random_snapshot(2 * eb.UPDATE_ROWS + 999, 5000, 4, seed=62)
    else:
        snap = spread[0]
        if graph == "one_family":  # type-2 links: targets in B alone
            snap = eb.restricted_for(snap, (2,))
    plans = plans_for(snap)
    ub = eb.UPDATE_ROWS
    zero_row = plans.out_map[plans.n_atoms]
    assert zero_row == plans.out_map.max()
    reached = np.flatnonzero(plans.out_map != zero_row)
    has_incidence = np.flatnonzero(
        np.diff(snap.inc_offsets[: snap.num_atoms + 1].astype(np.int64)))
    assert np.array_equal(reached, has_incidence)
    blocks = eb._active_blocks(plans)
    assert blocks.shape == (-(-plans.n_pad // ub),) and blocks.dtype == bool
    assert np.flatnonzero(blocks).tolist() == _blocks_with(reached)
    if graph == "two_ranges":
        assert blocks.tolist() == [True, False, True, False]
    if graph == "one_family":
        assert blocks.tolist() == [False, False, True, False]
    # a row of an unlisted block has no incidence set under the plan: the
    # degree sum may skip it, a seed's own bit there weighs nothing
    assert not plans.inc_deg[np.repeat(~blocks, ub)[: plans.n_pad]].any()
    dev = eb._device_plans(snap, plans)
    assert np.array_equal(dev["blocks"], blocks)
    n = int(dev["rows"].n_listed)
    assert dev["rows"].out_map is dev["out_map"]
    assert dev["rows"].starts.shape == blocks.shape
    assert np.asarray(dev["rows"].starts)[:n].tolist() == \
        (np.flatnonzero(blocks) * ub).tolist()


class _UpdateRowsCounted:
    """(rows folded, rows of the bitmaps) by the updates dispatched inside
    the ``with`` block, from the program's two counters."""

    NAMES = ("bfs.update.rows_visited", "bfs.update.rows_total")

    def __enter__(self):
        self._t0 = [_counter(n) for n in self.NAMES]
        return self

    def __exit__(self, *exc):
        self.visited, self.total = (
            _counter(n) - t for n, t in zip(self.NAMES, self._t0))


@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
def test_bfs_pull_where_seeds_lie_outside_every_active_block(
        spread, first_hop, typed, monkeypatch):
    """Seeds that are links, atoms nothing touches and the last atom
    before the dummy row, beside entities of both ranges: a row outside
    the plan's active blocks is never folded, keeps its own bit and is
    counted; the answers are the host's, and the counters say two of the
    bitmap's four blocks were folded a dense hop (one under the family
    whose links lie in B alone)."""
    snap, a, b, links = spread
    fam = (2,) if typed else None
    seeds = np.concatenate([
        a[:9], b[:9], links[:6], [5, eb.UPDATE_ROWS + 7, links[-1]],
        [snap.num_atoms] * 5]).astype(np.int32)
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    hops = 3
    with _Sides() as ran, _UpdateRowsCounted() as rows:
        res = bfs_pull(snap, seeds, hops, link_types=fam)
    assert ran.dense == (hops - 1 if first_hop == "sparse" else hops)
    n_pad = plans_for(snap).n_pad
    assert rows.total == ran.dense * n_pad
    assert rows.visited == ran.dense * (1 if typed else 2) * eb.UPDATE_ROWS
    got = visited_rows(res, snap.num_atoms)
    for k, s in enumerate(seeds.tolist()):
        want, edges = host_bfs(snap, s, hops, family=fam)
        if s == snap.num_atoms:
            want, edges = set(), 0
        assert set(got[k].tolist()) == want, f"seed {s} (column {k})"
        assert res.edges_touched[k] == edges
        assert int(res.reach_counts[k]) == len(want)
    for k in range(18, 27):  # the links and the untouched atoms: themselves
        assert got[k].tolist() == [int(seeds[k])]


#: paths over ``spread_snapshot``'s types, by what the active blocks do
#: from step to step: block 0 holds A, block 2 holds B
SPREAD_PATHS = {
    "moves": [(1,), (2,)],            # A, then B alone: nothing matches
    "moves_back": [(2,), (1,)],
    "grows": [(1,), (3,)],            # A, then A and B
    "shrinks": [(3,), (1,)],          # A and B, then A: B is cleared
    "there_and_back": [(1,), (3,), (2,)],
    "back_and_there": [(2,), (3,), (1,)],
    "every_link_then_b": [None, (2,), (3,)],
}


@pytest.mark.parametrize("first_step", ["sparse", "dense"])
@pytest.mark.parametrize("path", list(SPREAD_PATHS))
def test_path_match_where_the_active_blocks_differ_by_step(
        spread, path, first_step, monkeypatch):
    """A match's update gets its step's active blocks AND those in which
    the frontier it replaces can hold a bit — the seeds' own before a
    dense first step (links, untouched atoms: zero in ``X_1``), step 1's
    plan's after a sparse one, the previous step's after a dense one — so
    every row outside the new frontier is zero, whichever way the blocks
    move; the counters say which blocks were folded."""
    snap, a, b, links = spread
    steps = SPREAD_PATHS[path]
    seeds = np.concatenate([
        a[:12], b[:12], links[:4], [5, eb.UPDATE_ROWS + 7, links[-1]],
        [snap.num_atoms]]).astype(np.int32)
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_step == "sparse" else 1 << 62)
    with _Sides() as ran, _UpdateRowsCounted() as rows:
        res = eb.path_match(snap, seeds, steps)
    n_dense = len(steps) - (first_step == "sparse")
    assert (ran.sparse, ran.dense) == (len(steps) - n_dense, n_dense)
    _assert_matches_reference(_SnapshotGraph(snap), snap.num_atoms, seeds,
                              steps, res)

    def active(family):
        sub = snap if family is None else eb.restricted_for(snap, family)
        return eb._active_blocks(plans_for(sub))

    # the seeds lie in blocks 0 (A, atom 5), 1, 2 (B) and 3 (the links)
    held = (active(steps[0]) if first_step == "sparse"
            else np.ones(4, dtype=bool))
    folded = 0
    for family in steps[len(steps) - n_dense:]:
        folded += int((active(family) | held).sum())
        held = active(family)
    assert rows.visited == folded * eb.UPDATE_ROWS
    assert rows.total == n_dense * plans_for(snap).n_pad
    bitmap = np.asarray(res.frontier_t)
    assert not bitmap[np.repeat(~held, eb.UPDATE_ROWS)[: len(bitmap)]].any()


# ------------------------------------------ the counting passes' row blocks
#
# ``_deg_sum`` and ``_reach_counts`` fold the row blocks in which the state
# they count can hold a bit (``_bitdot``'s list) and no other: the plan's
# active blocks, beside them the blocks of a traversal's seeds — a seed
# keeps its own bit where nothing reaches it — and after a match's last
# step what ``_frontier_replace`` leaves non-zero. ``spread_snapshot``'s
# blocks: 0 holds A, 1 atoms nothing touches, 2 holds B, 3 the links.


class _CountRowsCounted(_UpdateRowsCounted):
    """The same of the counting passes dispatched inside the block."""

    NAMES = ("bfs.count.rows_visited", "bfs.count.rows_total")


def _count_seeds(spread, kind):
    snap, a, b, links = spread
    pad = [snap.num_atoms] * 3
    return np.concatenate({
        "entities_of_b": [b[:20], pad],
        "a_link_too": [b[:20], links[5:6], pad],
        # a link atom, an atom in a block no plan lists, the last atom
        # before the dummy row, entities of both ranges, pad seeds
        "every_kind": [a[:9], b[:9], links[:2], pad,
                       [5, eb.UPDATE_ROWS + 7, links[-1]]],
    }[kind]).astype(np.int32)


COUNT_SEED_BLOCKS = {"entities_of_b": [2], "a_link_too": [2, 3],
                     "every_kind": [0, 1, 2, 3]}


@pytest.mark.parametrize("count_edges", [True, False])
@pytest.mark.parametrize("kind", list(COUNT_SEED_BLOCKS))
@pytest.mark.parametrize("typed", [False, True])
@pytest.mark.parametrize("first_hop", ["sparse", "dense"])
@pytest.mark.parametrize("hops", [0, 1, 3])
def test_traversal_counts_fold_the_blocks_that_can_hold_a_bit(
        spread, hops, first_hop, typed, kind, count_edges, monkeypatch):
    """``reach_counts`` and ``edges_touched`` are the host's whatever the
    seeds are, and the counters say which blocks each pass folded: the
    degree sum the plan's active blocks (a seed elsewhere has no admitted
    link), the reach count those and the seeds' own, the seeds' own alone
    where no hop runs."""
    snap = spread[0]
    fam = (2,) if typed else None
    seeds = _count_seeds(spread, kind)
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_hop == "sparse" else 1 << 62)
    with _Sides() as ran, _CountRowsCounted() as rows:
        res = bfs_pull(snap, seeds, hops, link_types=fam,
                       count_edges=count_edges)
    assert ran.dense == max(0, hops - (first_hop == "sparse"))
    active = np.zeros(4, dtype=bool)
    if hops:
        active[[2] if typed else [0, 2]] = True
    held = active.copy()
    held[COUNT_SEED_BLOCKS[kind]] = True
    deg_sums = int(count_edges and ran.dense > 0)
    assert rows.visited == \
        (deg_sums * int(active.sum()) + int(held.sum())) * eb.UPDATE_ROWS
    assert rows.total == (deg_sums + 1) * plans_for(snap).n_pad
    reach = np.asarray(res.reach_counts)
    assert reach.dtype == np.int32 and reach.shape == (len(seeds),)
    for k, s in enumerate(seeds.tolist()):
        want, edges = host_bfs(snap, s, hops, family=fam)
        if s == snap.num_atoms:
            want, edges = set(), 0
        assert reach[k] == len(want), f"seed {s} (column {k})"
        assert res.edges_touched[k] == (edges if count_edges else 0)


@pytest.mark.parametrize("kind", list(COUNT_SEED_BLOCKS))
@pytest.mark.parametrize("first_step", ["sparse", "dense"])
@pytest.mark.parametrize("path", ["no_step", "moves", "shrinks", "grows",
                                  "every_link_then_b"])
def test_match_counts_fold_the_blocks_the_last_step_leaves(
        spread, path, first_step, kind, monkeypatch):
    """``match_counts`` is ``match_path``'s, and its one pass folds what
    the state holds after the last step: that step's plan's active blocks
    — whatever blocks the seeds lie in, the replaced frontier is zero
    there — and the seeds' own where no step runs."""
    snap = spread[0]
    steps = SPREAD_PATHS.get(path, [])
    seeds = _count_seeds(spread, kind)
    monkeypatch.setattr(eb, "SPARSE_SHARE",
                        1 if first_step == "sparse" else 1 << 62)
    with _CountRowsCounted() as rows:
        res = eb.path_match(snap, seeds, steps)
    _assert_matches_reference(_SnapshotGraph(snap), snap.num_atoms, seeds,
                              steps, res)
    if steps:
        last = steps[-1]
        sub = snap if last is None else eb.restricted_for(snap, last)
        held = eb._active_blocks(plans_for(sub))
    else:
        held = np.isin(np.arange(4), COUNT_SEED_BLOCKS[kind])
    assert rows.visited == int(held.sum()) * eb.UPDATE_ROWS
    assert rows.total == plans_for(snap).n_pad
