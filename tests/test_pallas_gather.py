"""Pallas gather+OR kernel: interpret-mode semantics vs the XLA reference.

Real Mosaic compiles need a TPU; CPU CI runs the kernel through the Pallas
interpreter, which exercises the same grid/DMA/semaphore program.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypergraphdb_tpu.ops import pallas_gather as pg
from hypergraphdb_tpu.ops.ellbfs import CLASS_WIDTHS


def _ref(values, idx, w):
    g = np.asarray(values)[np.asarray(idx)]
    return np.bitwise_or.reduce(g.reshape(-1, w, values.shape[1]), axis=1)


# every level-0 class width of the pull plan, and one that does not divide
# SEG / G (its segments are the whole grid steps that fit)
WIDTHS = sorted({*CLASS_WIDTHS, 4, 8, 24})


@pytest.mark.parametrize("w,n_out", [(4, pg.G), (8, pg.G)] + [
    (w, pg.G * 3 + 17) for w in WIDTHS]  # whole grid steps; a ragged tail
    + [(1, pg.G_W1 + 17)])  # the update's row fetch: a step and a tail
def test_gather_or_matches_xla(w, n_out):
    r = np.random.default_rng(0)
    S = 500
    values = jnp.asarray(
        r.integers(0, 2**32, size=(S, 128), dtype=np.uint64).astype(np.uint32)
    )
    idx = jnp.asarray(r.integers(0, S, size=n_out * w).astype(np.int32))
    out = pg.gather_or(values, idx, w, interpret=True)
    assert out.shape == (n_out, 128)
    assert np.array_equal(np.asarray(out), _ref(values, idx, w))


#: ``dma_start`` equations the traced chunk kernel held at each class
#: width before the kernel reduced a tile a step — ``(slots + 1) * w`` with
#: the slots of ``w`` copies it kept then (16 at w = 2 … 4 from w = 8): the
#: trace budget, written as numbers
CHUNK_KERNEL_STARTS = {2: 34, 4: 36, 6: 54, 8: 40, 10: 50, 14: 70, 20: 100,
                       28: 140, 40: 200, 56: 280}
#: and every equation of that traced call, nested bodies included: what
#: tracing costs follows it (a traced ``x + 3`` is a millisecond of a
#: loaded host's time, and a stage program traces two calls a class)
CHUNK_KERNEL_EQUATIONS = {2: 175, 4: 165, 6: 227, 8: 181, 10: 219, 14: 295,
                          20: 409, 28: 561, 40: 789, 56: 1093}


def _equations(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _equations(sub)
    return n


@pytest.mark.parametrize("w", CLASS_WIDTHS)
def test_traced_kernel_stays_inside_the_trace_budget(w):
    """What a kernel body's TEXT costs is paid on every run, cache or no
    cache (a run re-traces and re-lowers every program before it can look
    one up, and compiles the stage programs whose shapes follow the
    seed's plan), twice a width class a stage program: the traced kernel
    holds ONE copy-issue loop — a step's ``written_out(w)`` chunks of
    ``w`` copy starts — and never more than the chunk kernel held at that
    width. Needs no chip: the jaxpr is the same whatever compiles it."""
    assert set(CHUNK_KERNEL_STARTS) == set(CLASS_WIDTHS)
    jaxpr = jax.make_jaxpr(lambda v, i: pg.gather_or(v, i, w))(
        jax.ShapeDtypeStruct((1 << 12, 128), jnp.uint32),
        jax.ShapeDtypeStruct((pg._seg(w),), jnp.int32))
    text = str(jaxpr)
    assert text.count("pallas_call") == 1
    starts = text.count("dma_start")
    assert starts == pg.written_out(w) * w <= CHUNK_KERNEL_STARTS[w]
    assert text.count("dma_wait") == 1  # one wait a tile, in the text once
    assert _equations(jaxpr.jaxpr) <= CHUNK_KERNEL_EQUATIONS[w]


def _tile_cases():
    """Index layouts a tile of eight chunks must get right: every chunk of
    a tile reading the zero row (a plan's pads: rows of a class that end a
    section); one row fetched by every copy of a tile, and by two chunks
    of it (the same HBM row in flight into eight sublanes at once); a tile
    whose chunks differ only in their LAST index (each sublane keeps its
    own chunk's rows)."""
    S = 300

    def zero_tiles(r, n, w):
        idx = r.integers(0, S - 1, size=(n, w))
        idx[8:16] = S - 1          # the second tile of the first grid step
        idx[n - 8:] = S - 1        # and the last tile of the last
        return idx

    def repeats(r, n, w):
        idx = r.integers(0, S - 1, size=(n, w))
        idx[0:8] = 7               # a whole tile fetches one row
        idx[16:24] = idx[16]       # eight chunks, the same w rows
        idx[24:32, :] = idx[24, 0]  # every copy of a chunk the same row
        return idx

    def last_differs(r, n, w):
        idx = np.broadcast_to(r.integers(0, S - 1, size=(1, w)), (n, w)).copy()
        idx[:, -1] = r.integers(0, S - 1, size=n)
        return idx

    return S, {"zero_tiles": zero_tiles, "repeats": repeats,
               "last_differs": last_differs}


@pytest.mark.parametrize("w", [2, 8, 14, 56])
@pytest.mark.parametrize("layout", sorted(_tile_cases()[1]))
def test_gather_or_tile_layouts(layout, w):
    S, layouts = _tile_cases()
    r = np.random.default_rng([w, 35])
    values = r.integers(0, 2**32, size=(S, 128), dtype=np.uint64) \
        .astype(np.uint32)
    values[S - 1] = 0              # the zero row, last as a plan's is
    values = jnp.asarray(values)
    n_out = pg.G                   # one grid step exactly: no pad chunk
    idx = jnp.asarray(layouts[layout](r, n_out, w).reshape(-1)
                      .astype(np.int32))
    out = np.asarray(pg.gather_or(values, idx, w, interpret=True))
    assert out.shape == (n_out, 128)
    assert np.array_equal(out, _ref(values, idx, w))
    if layout == "zero_tiles":
        assert not out[8:16].any() and not out[-8:].any()


@pytest.mark.parametrize("w", [4, 20])
def test_gather_or_holds_indices_to_the_table(w):
    """The kernel is compiled without Mosaic's per-copy bounds checks, so
    the call clamps what it is handed: an index outside the table reads
    the table's first or last row, as the XLA gather's does."""
    r = np.random.default_rng([w, 37])
    S = 100
    values = jnp.asarray(
        r.integers(0, 2**32, size=(S, 128), dtype=np.uint64).astype(np.uint32)
    )
    idx = r.integers(0, S, size=pg.G * w).astype(np.int32)
    idx[::7] = S + r.integers(0, 1 << 20, size=len(idx[::7]))
    idx[3::11] = -1 - r.integers(0, 1 << 20, size=len(idx[3::11]))
    out = pg.gather_or(values, jnp.asarray(idx), w, interpret=True)
    assert np.array_equal(np.asarray(out),
                          _ref(values, np.clip(idx, 0, S - 1), w))


@pytest.mark.parametrize("w", [6, 10, 20, 28, 40])
def test_gather_or_one_grid_step_exactly(w):
    """``n_out`` of one grid step at the widths the first case leaves out
    (it holds 4 and 8): 32 tiles, no pad chunk, no scan."""
    r = np.random.default_rng([w, 36])
    S = 200
    values = jnp.asarray(
        r.integers(0, 2**32, size=(S, 128), dtype=np.uint64).astype(np.uint32)
    )
    idx = jnp.asarray(r.integers(0, S, size=pg.G * w).astype(np.int32))
    out = pg.gather_or(values, idx, w, interpret=True)
    assert out.shape == (pg.G, 128)
    assert np.array_equal(np.asarray(out), _ref(values, idx, w))


@pytest.mark.parametrize("w,seg", [(8, pg.G * 8 * 2), (24, pg.G * 8 * 7)])
def test_gather_or_multi_segment(w, seg, monkeypatch):
    # shrink SEG so the lax.scan path runs in-test: two grid steps a
    # segment at either width (24 leaves a remainder of SEG unused)
    monkeypatch.setattr(pg, "SEG", seg)
    assert pg._seg(w) == pg.G * w * 2
    r = np.random.default_rng(1)
    S = 300
    values = jnp.asarray(
        r.integers(0, 2**32, size=(S, 128), dtype=np.uint64).astype(np.uint32)
    )
    n_out = pg.G * 2 * 3 + 5  # 3 full segments + ragged tail
    idx = jnp.asarray(r.integers(0, S, size=n_out * w).astype(np.int32))
    out = pg.gather_or(values, idx, w, interpret=True)
    assert np.array_equal(np.asarray(out), _ref(values, idx, w))


@pytest.mark.parametrize("w", [1, *WIDTHS])
def test_kernel_geometry_follows_the_width(w):
    """At every width the gate admits: a slot holds a TILE of eight chunks
    (``TILE * w`` copies); the power of two of slots that keeps at least
    ``IN_FLIGHT`` copies outstanding and no more than twice that, never
    fewer than ``MIN_SLOTS`` (a tile lands while others are reduced: two
    slots read 8-60% slower than four at every width up to 14), never
    more than a grid step's tiles; a step of the issue loop writes out
    the power of two of chunks whose copies number at most
    ``STEP_COPIES`` — one chunk where a chunk alone is more, never more
    than a tile; whole grid steps a segment (``G`` chunks a step, ``G_W1``
    at w = 1, whose copies in flight are a whole ``G``-chunk step); a
    working set inside the VMEM budget."""
    assert pg.declined(w, pg.ROW_WORDS) is None
    d = pg.slots(w)
    g = pg.grid_chunks(w)
    assert g == (pg.G_W1 if w == 1 else pg.G)
    assert pg.MIN_SLOTS <= d <= g // pg.TILE and d & (d - 1) == 0
    assert d * pg.TILE * w >= pg.IN_FLIGHT
    assert d * pg.TILE * w < 2 * pg.IN_FLIGHT or d == pg.MIN_SLOTS
    assert [pg.slots(x) for x in (2, 4, 6, 8, 56)] == [16, 8, 8, 4, 4]
    p = pg.written_out(w)
    assert 1 <= p <= pg.TILE and pg.TILE % p == 0
    assert p * w <= pg.STEP_COPIES or p == 1
    assert 2 * p * w > pg.STEP_COPIES or p == pg.TILE
    assert [pg.written_out(x) for x in (2, 4, 6, 8, 10, 14, 20, 28, 56)] \
        == [8, 8, 8, 4, 4, 2, 2, 1, 1]
    seg = pg._seg(w)
    assert seg % (g * w) == 0 and pg.SEG - g * w < seg <= pg.SEG
    assert pg.whole_segments(4 * pg.SEG, w) == 4 * pg.SEG // seg * seg
    assert pg.whole_segments(seg - 1, w) == seg - 1
    assert pg._vmem_bytes(w, pg.ROW_WORDS) == \
        4 * pg.ROW_WORDS * (2 * g + d * w * pg.TILE) <= pg.VMEM_BUDGET


def test_gate_declines_a_chunk_wider_than_a_segment():
    assert "must fit SEG" in pg.declined(pg.SEG // pg.G + 1, pg.ROW_WORDS)
    assert pg.declined(pg.SEG // pg.G, pg.ROW_WORDS) is None \
        or "VMEM" in pg.declined(pg.SEG // pg.G, pg.ROW_WORDS)
    with pytest.raises(ValueError, match="must fit SEG"):
        pg.gather_or(jnp.zeros((8, 128), jnp.uint32),
                     jnp.zeros((1024,), jnp.int32), 1024)


def test_gather_or_rejects_bad_shapes():
    values = jnp.zeros((8, 64), jnp.uint32)  # 64 lanes unsupported
    with pytest.raises(ValueError):
        pg.gather_or(values, jnp.zeros((16,), jnp.int32), 8)
    values = jnp.zeros((8, 128), jnp.uint32)
    with pytest.raises(ValueError):
        pg.gather_or(values, jnp.zeros((15,), jnp.int32), 8)  # not %w


def test_pallas_ok_false_on_cpu():
    assert jax.default_backend() == "cpu"
    assert pg.pallas_ok() is False


def test_probe_failure_on_tpu_raises(monkeypatch):
    """On a TPU backend a kernel the chip refuses must RAISE out of the
    gate (and keep raising: a failed probe is never cached as a quiet
    False); off-TPU the gate is False from the platform alone."""
    assert pg.pallas_ok() is False              # cpu: platform says no
    monkeypatch.setattr(pg.jax, "default_backend", lambda: "tpu")

    def refused(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    # the real kernel entry cannot run here; make it fail the way a
    # refusing compiler does
    monkeypatch.setattr(pg, "gather_or", refused)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="Mosaic"):
            pg.pallas_ok()
    monkeypatch.setenv("HG_PALLAS_GATHER", "0")
    assert pg.pallas_ok() is False              # the veto still wins


def test_bfs_pull_wide_block_cpu_fallback(graph):
    """k_block=4096 on CPU: pallas preflight fails → XLA path, results must
    equal the narrow-block run."""
    from tests.conftest import make_random_hypergraph
    from hypergraphdb_tpu.ops.ellbfs import bfs_pull, visited_rows

    make_random_hypergraph(graph, n_nodes=300, n_links=600, seed=3)
    snap = graph.snapshot()
    seeds = np.arange(40, dtype=np.int32)
    wide = bfs_pull(snap, seeds, 3, k_block=4096)
    narrow = bfs_pull(snap, seeds, 3, k_block=32)
    assert np.array_equal(wide.edges_touched, narrow.edges_touched)
    rw = visited_rows(wide, snap.num_atoms)
    rn = visited_rows(narrow, snap.num_atoms)
    for a, b in zip(rw[: len(seeds)], rn[: len(seeds)]):
        assert np.array_equal(a, b)


def test_tile_share_gauge_says_which_gather_serves(graph, monkeypatch):
    """``bfs.gather.tile_share``, set where a plan's device arrays are
    made, from a 4096-seed block's route: 0 where the XLA gather serves
    (this CPU), the share of the plan's level-0 indices in a class whose
    form is ``hg_gather_or``'s where the kernel does."""
    from tests.conftest import make_random_hypergraph
    from hypergraphdb_tpu.obs.registry import default_registry
    from hypergraphdb_tpu.ops import ellbfs as eb

    make_random_hypergraph(graph, n_nodes=200, n_links=500, seed=5)
    gauge = default_registry().gauge("bfs.gather.tile_share")
    gauge.set(-1.0)
    snap = graph.snapshot()
    eb._device_plans(snap, eb.plans_for(snap))
    assert gauge.value == 0.0

    monkeypatch.setattr(pg, "pallas_ok", lambda: True)
    object.__setattr__(snap, "_pull_device", None)   # upload again
    plans = eb.plans_for(snap)
    eb._device_plans(snap, plans)
    assert gauge.value == 100.0
    eb._device_plans(snap, plans)       # a memo hit sets nothing
    gauge.set(-1.0)
    eb._device_plans(snap, plans)
    assert gauge.value == -1.0

    # a class the gate declines leaves its indices to the XLA gather
    s1 = plans.stage1
    w0, n0 = s1.widths[0], len(s1.levels[0])
    lvl0 = sum(len(l) for l in s1.levels[:s1.n_lvl0]) + sum(
        len(l) for l in plans.stage2_levels[:plans.stage2_n_lvl0])
    in_w0 = n0 + sum(len(l) for l, w in zip(
        plans.stage2_levels[:plans.stage2_n_lvl0], plans.stage2_widths)
        if w == w0)
    admits = pg.declined
    monkeypatch.setattr(
        pg, "declined",
        lambda w, Kw: "not this one" if w == w0 else admits(w, Kw))
    object.__setattr__(snap, "_pull_device", None)   # upload and route
    object.__setattr__(snap, "_pull_routes", {})     # anew
    eb._device_plans(snap, plans)
    assert gauge.value == pytest.approx(100.0 * (lvl0 - in_w0) / lvl0)
    assert 0.0 < gauge.value < 100.0


# ------------------------------------------------------ the scalar form

#: a table whose length is no multiple of 128: the last row is padded
SCALAR_S = 3005


def _scalar_values(r, op, S=SCALAR_S):
    """Small whole numbers as float32 (every sum exact, in any order) or
    int32 labels over the whole range."""
    if op == "sum":
        return r.integers(0, 64, size=S).astype(np.float32)
    return r.integers(-2**31, 2**31 - 1, size=S, dtype=np.int64) \
        .astype(np.int32)


def _scalar_ref(values, idx, w, op):
    g = np.asarray(values)[np.asarray(idx)].reshape(-1, w)
    return g.sum(axis=1) if op == "sum" else g.min(axis=1)


@pytest.mark.parametrize("op", ["sum", "min"])
@pytest.mark.parametrize("w", CLASS_WIDTHS)
def test_gather_reduce_matches_numpy(w, op):
    """Every class width, a float32 sum and an int32 min: one whole grid
    step of ``G_SCALAR`` chunks and a ragged tail of 17 (padded to a
    second step and sliced off), over a table of 3005 values, every one
    of them reachable."""
    r = np.random.default_rng([w, 41, op == "sum"])
    values = _scalar_values(r, op)
    n_out = pg.G_SCALAR + 17
    idx = r.integers(0, SCALAR_S, size=n_out * w).astype(np.int32)
    out = np.asarray(pg.gather_reduce(jnp.asarray(values), jnp.asarray(idx),
                                      w, op, interpret=True))
    assert out.shape == (n_out,) and out.dtype == values.dtype
    assert np.array_equal(out, _scalar_ref(values, idx, w, op))


@pytest.mark.parametrize("op", ["sum", "min"])
def test_gather_reduce_holds_indices_to_the_table(op):
    """The last value is read where the table's last row is a pad, and an
    index outside the table reads its nearest end, as the XLA gather's
    does — the pad past the last value is never read."""
    r = np.random.default_rng([op == "sum", 42])
    values = _scalar_values(r, op)
    w = 6
    idx = r.integers(0, SCALAR_S, size=pg.G_SCALAR * w).astype(np.int32)
    idx[::5] = SCALAR_S - 1
    idx[1::7] = SCALAR_S + r.integers(0, 1 << 20, size=len(idx[1::7]))
    idx[3::11] = -1 - r.integers(0, 1 << 20, size=len(idx[3::11]))
    out = pg.gather_reduce(jnp.asarray(values), jnp.asarray(idx), w, op,
                           interpret=True)
    assert np.array_equal(np.asarray(out), _scalar_ref(
        values, np.clip(idx, 0, SCALAR_S - 1), w, op))


@pytest.mark.parametrize("op", ["sum", "min"])
def test_gather_reduce_pad_indices_read_the_identity(op):
    """A plan pads a chunk with the index of its zero row, which holds the
    reduction's identity: a chunk of pads alone reads the identity, and a
    chunk of one value and pads reads that value, exactly."""
    r = np.random.default_rng([op == "sum", 43])
    values = _scalar_values(r, op)
    ident = pg.scalar_identity(op, values.dtype)
    zero = SCALAR_S - 1
    values[zero] = ident
    w = 8
    idx = np.full((pg.G_SCALAR, w), zero, dtype=np.int32)
    idx[1::2, 0] = r.integers(0, zero, size=pg.G_SCALAR // 2)
    out = np.asarray(pg.gather_reduce(jnp.asarray(values),
                                      jnp.asarray(idx.reshape(-1)), w, op,
                                      interpret=True))
    assert (out[0::2] == ident).all()
    assert np.array_equal(out[1::2], values[idx[1::2, 0]])


def test_scalar_gate_says_what_the_form_serves():
    """4-byte float32 and int32 states, a grid step's indices inside half
    the SMEM, a table inside ``SCALAR_TABLE_BYTES``; anything else is
    declined with its reason, and ``gather_reduce`` raises it."""
    widest = pg.SMEM_BUDGET // 2 // (8 * pg.G_SCALAR)  # two steps' indices
    for w in (*CLASS_WIDTHS, 1, widest):
        for dt in ("float32", "int32"):
            assert pg.declined_scalar(w, dt, 10_000_072) is None
    assert "4-byte" in pg.declined_scalar(8, "uint32", 100)
    assert "4-byte" in pg.declined_scalar(8, "float16", 100)
    assert "SMEM" in pg.declined_scalar(widest + 1, "int32", 100)
    most = pg.SCALAR_TABLE_BYTES // 4
    assert pg.declined_scalar(8, "int32", most) is None
    assert "VMEM" in pg.declined_scalar(8, "int32", most + 1)
    with pytest.raises(ValueError, match="4-byte"):
        pg.gather_reduce(jnp.zeros((8,), jnp.uint32),
                         jnp.zeros((64,), jnp.int32), 8, "min")
    with pytest.raises(ValueError, match="% 8"):
        pg.gather_reduce(jnp.zeros((8,), jnp.int32),
                         jnp.zeros((63,), jnp.int32), 8, "min")
    with pytest.raises(ValueError, match="flat"):
        pg.gather_reduce(jnp.zeros((8, 128), jnp.int32),
                         jnp.zeros((64,), jnp.int32), 8, "min")
    with pytest.raises(ValueError, match="flat"):
        pg.gather_reduce(jnp.zeros((8,), jnp.int32),
                         jnp.zeros((64,), jnp.int32), 8, "max")


def test_scalar_table_pads_to_whole_tiles_only_where_it_must():
    x = jnp.arange(pg.G_SCALAR * 3, dtype=jnp.float32)
    assert pg.scalar_table(x, 0) is x
    y = np.asarray(pg.scalar_table(jnp.arange(5, dtype=jnp.int32), 7))
    assert y.shape == (pg.G_SCALAR,) and (y[5:] == 7).all()
    assert y[:5].tolist() == list(range(5))


@pytest.mark.parametrize("w", CLASS_WIDTHS)
def test_traced_scalar_kernel_text_does_not_follow_the_width(w):
    """The scalar form's text is one loop over the tiles around one loop
    over a chunk's indices, two of them a step: the same equations at
    every even width (every class width is even), one pallas_call, no
    copies of its own."""
    def equations(width):
        jaxpr = jax.make_jaxpr(lambda v, i: pg.gather_reduce(
            v, i, width, "sum"))(
            jax.ShapeDtypeStruct((1 << 12,), jnp.float32),
            jax.ShapeDtypeStruct((pg.G_SCALAR * width,), jnp.int32))
        text = str(jaxpr)
        assert text.count("pallas_call") == 1 and "dma_start" not in text
        return _equations(jaxpr.jaxpr)

    assert equations(w) == equations(2) <= 300
