"""What the TPU v5e's compiler says about the main path, kept as tests.

The TPU compiler is installed here and compiles for a chip that is
*described*, not attached (``jax.experimental.topologies``). These tests
lower the kernels and jitted programs the served path and the 10M-atom
kernels run, at the widths and sizes they run them, and ``compile()`` —
which raises what the chip's compiler would raise: a Mosaic tiling
refusal, a scoped-VMEM overflow, a program that does not fit 16 GB of HBM.
Nothing runs, so nothing here says a word about results or speed.

Rules this file keeps (the ``on-chip-measurement`` guide, section 2): the
topology is described inside a module-scoped fixture that skips where it
cannot be — never at import, never in a ``parametrize`` argument, never in
``conftest.py`` — because only one process may load the TPU library and
every xdist worker imports every test file; all such tests live in THIS
file so one worker owns the library; the persistent compile cache is off
around them (a compile for a described chip cannot be read back).
"""

from __future__ import annotations

import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import chip_smoke
from hypergraphdb_tpu import verify as hgverify
from hypergraphdb_tpu.ops.ellbfs import CLASS_WIDTHS

#: chip_smoke.py's sizes, read from it: the serve phase's padded id space
#: and edge count (1M entities + 2M binary links through the store: every
#: valued atom takes a second handle, the type system a few thousand, times
#: the default headroom of 2, rounded up to the pad — SnapshotManager's
#: arithmetic).
_FULL = chip_smoke.SCALES["full"]
_PAD = _FULL["pad_multiple"]
SERVE_ATOMS = -(-2 * (2 * (_FULL["serve_entities"] + _FULL["serve_links"])
                      + 4096) // _PAD) * _PAD
SERVE_EDGES = -(-2 * _FULL["serve_links"] // _PAD) * _PAD
#: what one v5e chip leaves a program of its 16 GiB (15.75 GiB, in bytes)
HBM_USABLE = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _place(tree, sharding):
    """The exemplar pytree with every leaf pinned to ``sharding``."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _rescaled(entry_name: str, dims: dict):
    """A registered hgverify entry's own exemplar with its toy dimensions
    mapped to real ones (``dims``: toy size -> real size)."""
    import hypergraphdb_tpu.ops.join  # noqa: F401 - registers its entries
    import hypergraphdb_tpu.ops.value_index  # noqa: F401

    entry = hgverify.REGISTRY.get(entry_name)
    raw = entry.shapes()
    args, kwargs = ((raw[0], raw[1])
                    if (len(raw) == 2 and isinstance(raw[1], dict)
                        and isinstance(raw[0], (tuple, list)))
                    else (raw, {}))
    grow = partial(jax.tree.map, lambda s: _sds(
        [dims.get(d, d) for d in s.shape], s.dtype))
    return entry, grow(tuple(args)), grow(kwargs)


# ------------------------------------------------------------------ cases
#
# name -> builder(place) returning (jitted_fn, args, kwargs). ``place``
# pins an exemplar pytree to the described chip. Builders run inside the
# test, after the fixture described the topology.


def _case_gather_or(place, kw=128, w=8):
    from hypergraphdb_tpu.ops import pallas_gather as pg

    fn = jax.jit(partial(pg.gather_or, w=w))
    # one whole segment of indices at the width (2^17 at a power of two)
    return fn, place((_sds((1 << 20, kw), "uint32"),
                      _sds((pg._seg(w),), "int32"))), {}


def _case_membership(place):
    from hypergraphdb_tpu.ops.pallas_kernels import _membership_call

    return _membership_call, place((_sds((1024, 128), "int32"),
                                    _sds((3, 65536), "int32"))), {}


def _case_serve_bfs(place, bucket, hops, atoms=SERVE_ATOMS,
                    edges=SERVE_EDGES):
    from hypergraphdb_tpu.ops.serving import bfs_serve_batch
    from hypergraphdb_tpu.serve import ServeConfig

    return bfs_serve_batch, place((
        hgverify.dev_snapshot_exemplar(atoms, edges, edges),
        hgverify.device_delta_exemplar(atoms, 1 << 15),
        _sds((bucket,), "int32"),
    )), dict(max_hops=hops, top_r=ServeConfig().top_r + 1)


def _case_serve_pattern(place, bucket=1024):
    from hypergraphdb_tpu.ops.serving import pattern_serve_batch
    from hypergraphdb_tpu.serve import ServeConfig

    cfg = ServeConfig()
    return pattern_serve_batch, place((
        hgverify.dev_snapshot_exemplar(SERVE_ATOMS, SERVE_EDGES,
                                       SERVE_EDGES),
        _sds((SERVE_ATOMS + 1, 2), "int32"),      # ELL targets, arity 2
        _sds((bucket, 2), "int32"), _sds((bucket,), "int32"),
    )), dict(pad_len=cfg.pattern_pad, top_r=cfg.top_r)


def _case_range_probe(place):
    entry, args, kwargs = _rescaled(
        "ops.value_index.range_probe_batch", {64: 1 << 21, 8: 1024})
    return entry.fn, place(args), place(kwargs)


def _case_join_hub_expand(place):
    entry, args, kwargs = _rescaled(
        "ops.join.join_hub_expand",
        {33: SERVE_ATOMS + 2, 64: SERVE_EDGES, 32: SERVE_ATOMS + 1,
         8: 4096, 4: 64},
    )
    statics = dict(entry.statics, rows_out=8192, n_lanes=64)
    return entry.fn, place(args), dict(place(kwargs), **statics)


CASES = {
    # the two kernels the served path and the kernels phase cannot do
    # without
    "gather_or[1Mx128,128K]": _case_gather_or,
    "membership[1024x128,3x65536]": _case_membership,
    # the dense served BFS at the serve phase's graph and the widest
    # bucket the executor admits there (past ~270K atoms a single top_k
    # over the row was refused — scoped VMEM — hence first_r_dense). slow:
    # each of these dense programs keeps every core busy for ~25 s, and
    # the smoke compiles and runs this one on the chip
    "bfs_serve_batch[K=256,hops=3]": partial(_case_serve_bfs, bucket=256,
                                             hops=3),
    "pattern_serve_batch[K=1024]": _case_serve_pattern,
    "range_probe_batch[2M,K=1024]": _case_range_probe,
    "join_hub_expand[R=4096]": _case_join_hub_expand,
}


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=pytest.mark.slow) if c.startswith("bfs_serve_batch[")
    else c for c in sorted(CASES)])
def test_main_path_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, args, kwargs = CASES[case](partial(_place, sharding=one_chip))
    # compile() is the verdict: a program that does not fit the chip is
    # refused here (RESOURCE_EXHAUSTED) — one program at a time, not what
    # else the process holds. (memory_analysis() is not held to the chip's
    # size: it overstates what the temporaries take — PERF.md, PR 22.)
    compiled = fn.lower(*args, **kwargs).compile()
    if "gather_or" in case or "membership" in case:
        assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not interpret


def test_the_compiler_refuses_the_bfs_bucket_that_does_not_fit(
        one_chip, no_compile_cache):
    """``DeviceExecutor.bfs_bucket_cap`` rests on this: a dense BFS program
    too wide for the chip is REFUSED by its compiler, not accepted and
    left to fail when it runs. At the serve phase's graph the v5e compiler
    refuses 1024 seeds ("Ran out of memory in memory space hbm") — and
    accepts 256 (the slow case above; the smoke runs it on the chip), so
    the executor caps BFS batches at 256 there. (``memory_analysis()`` is
    no such oracle: it reports 20.4 GB of temporaries for a program that
    runs on the chip — PERF.md, PR 22.)"""
    fn, args, kw = _case_serve_bfs(partial(_place, sharding=one_chip),
                                   1024, 2)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        fn.lower(*args, **kw).compile()


# ------------------------------------------------- widths: gate == compiler


def test_wide_rows_gate_agrees_with_compiler(one_chip, no_compile_cache):
    """No row width exists that the gate admits and the compiler refuses:
    128 words compiles (above) and is admitted; 256 words — which Mosaic
    refuses ('aligned to tiling (8), but is 1') — is declined by the
    gate, with the reason, at trace time, before the compiler is asked."""
    from hypergraphdb_tpu.ops import pallas_gather as pg

    fn, args, kwargs = _case_gather_or(partial(_place, sharding=one_chip),
                                       kw=256)
    with pytest.raises(ValueError, match="128"):
        fn.lower(*args, **kwargs)
    assert pg.declined(8, 128) is None and pg.declined(8, 256)


@pytest.mark.parametrize("w", CLASS_WIDTHS)
def test_every_class_width_compiles_for_v5e(w, one_chip, no_compile_cache):
    """Each level-0 class width of the pull plan (``ellbfs.CLASS_WIDTHS``,
    held at import to what the gate admits) is a kernel the v5e compiler
    accepts at 128-word rows: a sublane tile of eight chunks a loop step,
    with the slots that width holds in flight and the chunks its issue
    loop writes out a step (seconds to lower and compile live in
    ``benchmarks/tests/gather_tile_probe.py --describe``, the length of
    the traced text in ``tests/test_pallas_gather.py``: no wall clock is
    asserted under ``-n 6``)."""
    from hypergraphdb_tpu.ops import pallas_gather as pg

    assert pg.declined(w, 128) is None
    fn, args, kwargs = _case_gather_or(partial(_place, sharding=one_chip),
                                       w=w)
    compiled = fn.lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _case_gather_reduce(place, w, op, n_values=None):
    from hypergraphdb_tpu.ops import pallas_gather as pg

    n_values = _N_PAD if n_values is None else n_values
    fn = jax.jit(partial(pg.gather_reduce, w=w, op=op))
    dtype = "float32" if op == "sum" else "int32"
    # a scan block of the pyramid's level 0 (_class_rows: chunk * 8
    # indices, whole grid steps of the scalar form)
    n = pg.whole_scalar_steps(_CHUNK * 8, w)
    return fn, place((_sds((n_values,), dtype), _sds((n,), "int32"))), {}


@pytest.mark.parametrize("op", ["sum", "min"])
@pytest.mark.parametrize("w", CLASS_WIDTHS)
def test_scalar_gate_agrees_with_compiler_at_every_class_width(
        w, op, one_chip, no_compile_cache):
    """The scalar form at each level-0 class width, a float32 sum and an
    int32 min over the cells' 10,000,072-value state (padded to whole
    tiles in the call): the gate admits it and the v5e compiler accepts
    it, the table whole in VMEM beside a block of indices in SMEM; and a
    table past ``SCALAR_TABLE_BYTES`` is declined at trace time, with
    the reason, before the compiler is asked."""
    from hypergraphdb_tpu.ops import pallas_gather as pg

    dtype = "float32" if op == "sum" else "int32"
    assert pg.declined_scalar(w, dtype, _N_PAD) is None
    place = partial(_place, sharding=one_chip)
    fn, args, kwargs = _case_gather_reduce(place, w, op)
    compiled = fn.lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    most = pg.SCALAR_TABLE_BYTES // 4
    fn, args, kwargs = _case_gather_reduce(place, w, op, most + 1)
    with pytest.raises(ValueError, match="VMEM"):
        fn.lower(*args, **kwargs)


def test_scalar_gate_largest_table_compiles(one_chip, no_compile_cache):
    """The largest table the gate admits, ``SCALAR_TABLE_BYTES`` of int32,
    compiles at the narrowest class and at the widest chunk the gate
    admits (two grid steps of its indices in half the SMEM)."""
    from hypergraphdb_tpu.ops import pallas_gather as pg

    widest = pg.SMEM_BUDGET // 2 // (8 * pg.G_SCALAR)
    assert pg.declined_scalar(widest, "int32", 1) is None
    assert pg.declined_scalar(widest + 1, "int32", 1)
    for w in (CLASS_WIDTHS[0], widest):
        fn, args, kwargs = _case_gather_reduce(
            partial(_place, sharding=one_chip), w, "min",
            pg.SCALAR_TABLE_BYTES // 4)
        assert "tpu_custom_call" in fn.lower(*args, **kwargs) \
            .compile().as_text()


@pytest.mark.slow  # ~25 s to be refused; the fix is guarded above
def test_whole_row_top_k_is_what_the_compiler_refuses(one_chip,
                                                      no_compile_cache):
    """Why ``first_r_dense`` sweeps in blocks: ONE ``top_k`` over a
    300K-column row — the served BFS compaction as it was — runs
    ``TopKBatchMajorSmallK`` out of scoped VMEM on this compiler."""
    x = _place(_sds((8, 300_001), "int32"), one_chip)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(lambda m: jax.lax.top_k(m, 17)[0]).lower(x).compile()


# ------------------------------------------------- four devices: the mesh


def test_sharded_bfs_compiles_for_four_chips_with_a_collective(
        topo, no_compile_cache):
    """``bfs_serve_batch_sharded`` on a Mesh over the described 2x2: the
    program partitions, and the compiler put collectives in."""
    from hypergraphdb_tpu.ops.sharded_serving import bfs_serve_batch_sharded
    from hypergraphdb_tpu.parallel.sharded import (
        AXIS,
        ShardedDelta,
        ShardedSnapshot,
    )
    from hypergraphdb_tpu.serve import ServeConfig

    devices = np.asarray(topo.devices)
    assert devices.size == 4
    mesh = Mesh(devices.reshape(-1), (AXIS,))
    shard = NamedSharding(mesh, P(AXIS))
    n_dev = 4
    n_loc = -(-(SERVE_ATOMS + 1) // (n_dev * 128)) * 128
    chunk = 1 << 16                  # per-device edges: whole scan chunks
    e_loc, d_loc = -(-SERVE_EDGES // (n_dev * chunk)) * chunk, 1 << 13
    sh = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.dtype(dt), sharding=shard)
    sdev = ShardedSnapshot(
        mesh=mesh, num_atoms=SERVE_ATOMS, n_loc=n_loc, edge_chunk=chunk,
        inc_src=sh((n_dev * e_loc,), "int32"),
        inc_dst=sh((n_dev * e_loc,), "int32"),
        tgt_src=sh((n_dev * e_loc,), "int32"),
        tgt_dst=sh((n_dev * e_loc,), "int32"),
        type_of=sh((n_dev * n_loc,), "int32"),
        is_link=sh((n_dev * n_loc,), "bool"),
        arity=sh((n_dev * n_loc,), "int32"),
        value_rank_hi=sh((n_dev * n_loc,), "uint32"),
        value_rank_lo=sh((n_dev * n_loc,), "uint32"),
    )
    sdelta = ShardedDelta(
        epoch=0, edge_chunk=d_loc,
        inc_src=sh((n_dev * d_loc,), "int32"),
        inc_dst=sh((n_dev * d_loc,), "int32"),
        tgt_src=sh((n_dev * d_loc,), "int32"),
        tgt_dst=sh((n_dev * d_loc,), "int32"),
        dead=sh((n_dev * (n_loc // 32),), "uint32"),
    )
    seeds = jax.ShapeDtypeStruct(
        (256,), jnp.int32, sharding=NamedSharding(mesh, P()))
    compiled = bfs_serve_batch_sharded.lower(
        sdev, sdelta, seeds, max_hops=2, top_r=ServeConfig().top_r + 1,
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_USABLE


# --------------------- the staged hop at the benchmark graph's real shapes


#: level lengths and widths of ``plans_for(models.dbpedia_snapshot(2M,
#: 8M))`` — the index plans of the 10,000,065-atom benchmark graph: level 0
#: in width classes (stage 1's five hold every link, arity 2-10, and it has
#: no upper level; stage 2's nine, then the pyramid of its 8,813 rows above
#: ``W_MAX``). One width-8 level 0 a stage was (78,239,528, 14,239,528) and
#: (55,021,736, 16,594,768, 120,696, 12,328, 1,432, 160, 16).
_L1 = (1_775_280, 7_106_828, 10_673_640, 14_214_176, 17_799_410)
_W1 = (2, 4, 6, 8, 10)
_L2 = (32, 1_146, 13_696, 90_280, 1_640_828, 14_970_700, 26_817_756,
       5_963_480, 5_884_704, 142_600, 14_256, 1_640, 184, 16)
_W2 = (4, 6, 8, 10, 14, 20, 28, 40, 56, 8, 8, 8, 8, 8)
_N2 = 9  # stage 2's level-0 classes
_N_PAD, _KW, _CHUNK = 10_000_072, 128, 1 << 16


def _staged_step(step: str, kernel: bool = False):
    """(jitted step, exemplar args, statics, bytes resident beside it);
    ``kernel``: an update's fetch on ``hg_gather_or``, as a 4096-seed
    block on a TPU runs it."""
    from hypergraphdb_tpu.ops import ellbfs as eb

    rows = lambda ls, ws: sum(n // w for n, w in zip(ls, ws))  # noqa: E731
    ints = lambda ls: tuple(_sds((n,), "int32") for n in ls)  # noqa: E731
    bitmap = _N_PAD * _KW * 4
    visited = _sds((_N_PAD, _KW), "uint32")
    if step == "_sparse_hop":
        return (eb._sparse_hop,
                (visited, _sds((2, eb.SPARSE_BLOCK), "int32"),
                 _sds((), "int32")),
                {}, 4 * (sum(_L1) + sum(_L2)))
    stages = ((_L1, _W1, len(_L1)), (_L2, _W2, _N2))
    if step in ("_visited_update", "_frontier_replace", "_ball_update"):
        # a hop ends here (a match's step: three steps' plans lie beside
        # it); the block list has a slot a row block of the bitmap, and
        # the route's fetch
        listed = eb._route(*stages, visited, _CHUNK, kernel).listed(
            eb._UpdateRows(_sds((_N_PAD,), "int32"),
                           _sds((-(-_N_PAD // eb.UPDATE_ROWS),), "int32"),
                           _sds((), "int32")))
        return (getattr(eb, step),
                (visited, _sds((rows(_L2, _W2) + 1, _KW), "uint32"),
                 listed, _sds((), "int32")),
                {},
                3 * 4 * (sum(_L1) + sum(_L2))
                + (bitmap if step == "_ball_update" else 0))  # the other ball
    # the stages gather on the kernel, as a 4096-seed block on a TPU does
    route = eb._route(*stages, visited, _CHUNK, kernel=True)
    if step == "_stage":
        return (eb._stage,
                (visited, ints(_L1)),
                {"route": route.stage1},
                4 * sum(_L2))
    if step == "_stage_lvl0_consume":
        return (eb._stage_lvl0_consume,
                (_sds((rows(_L1, _W1) + 1, _KW), "uint32"), ints(_L2[:_N2])),
                {"route": route.stage2},
                bitmap + 4 * (sum(_L1) + sum(_L2[_N2:])))
    return (eb._stage_upper,
            (_sds((rows(_L2[:_N2], _W2), _KW), "uint32"), ints(_L2[_N2:])),
            {"route": route.stage2},
            bitmap + 4 * (sum(_L1) + sum(_L2[:_N2])))


def test_the_cell_shape_is_the_modules_classes():
    """``_L1`` / ``_L2`` describe a plan the module would build: level 0's
    widths are its class constants, ascending, the widest last where a
    pyramid follows; every length a multiple of its width; the stage-1
    buffer (8,000,000 link rows and the zero row) and stage 2's (2.11M
    rows) are what the classes leave of 11.55M and 8.97M rows."""
    from hypergraphdb_tpu.ops import ellbfs as eb

    assert set(_W1) | set(_W2[:_N2]) <= set(eb.CLASS_WIDTHS)
    assert _W2[_N2 - 1] == eb.W_MAX and set(_W2[_N2:]) == {8}
    assert list(_W1) == sorted(_W1) and list(_W2[:_N2]) == sorted(_W2[:_N2])
    assert all(n % w == 0 for n, w in zip(_L1 + _L2, _W1 + _W2))
    assert sum(n // w for n, w in zip(_L1, _W1)) == 8_000_000
    assert sum(n // w for n, w in zip(_L2, _W2)) == 2_108_461
    assert sum(_L2[_N2:]) == 158_696  # the upper pyramid's indices a hop


_UPDATES = ("_visited_update", "_frontier_replace", "_ball_update")


@pytest.mark.parametrize(
    "step,kernel",
    [pytest.param(s, False, id=s)
     for s in ("_sparse_hop", "_stage", "_stage_lvl0_consume",
               "_stage_upper") + _UPDATES]
    + [pytest.param(s, True, id=f"{s}-kernel") for s in _UPDATES])
def test_staged_hop_fits_one_chip_at_10m_atoms_4096_seeds(step, kernel,
                                                          one_chip,
                                                          no_compile_cache):
    """Each host-sequenced step of ``ellbfs._bfs_pull_device`` at the
    benchmark graph, a 4096-seed block (128-word rows — narrower rows are
    lane-padded to 128 on the chip and save nothing) and the chunk
    ``bench.py`` c4 runs: the step's program PLUS what the hop keeps
    resident beside it (the visited bitmap, the other index plans) fits
    the chip, and three quarters of it. The widest is
    ``_stage_lvl0_consume`` at ~11.2 of 16.9 GB (15.5 before level 0 had
    width classes; before the upper levels wrote in place ``_stage_upper``
    planned 17.2). The sparse first hop's placement (``_sparse_hop``, one block of
    pairs) updates the donated bitmap where it lies, and the two updates
    fold the listed row blocks into the donated state — a loop whose trip
    count the compiler cannot see carries the alias as a counted one did:
    no second bitmap, and nothing of a bitmap's size beside it. A pair
    search's ``_ball_update`` is the traversal's update with a row of
    words beside it, and the OTHER ball resident. Each update also with
    its fetch on the kernel (``-kernel``): ``hg_gather_or`` at width 1
    inside the loop, the alias and the temporaries as on the XLA route."""
    fn, args, statics, resident = _staged_step(step, kernel)
    compiled = fn.lower(*_place(args, one_chip), **statics).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes + resident)
    # with level 0 in width classes the widest step holds 11.2 GB
    # (``_stage_lvl0_consume``: the bitmap, stage 1's 8.0M-row buffer and
    # stage 2's 2.1M rows), where one width-8 level 0 a stage held 15.5
    assert total < 0.75 * HBM_USABLE, (step, total)
    if step in ("_sparse_hop",) + _UPDATES:
        assert mem.alias_size_in_bytes >= _N_PAD * _KW * 4
        assert mem.temp_size_in_bytes < 2**30
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["_deg_sum", "_reach_counts"])
def test_counting_pass_keeps_its_unpacked_bits_on_the_chip(
        program, one_chip, no_compile_cache):
    """The two counting programs at the cells' bitmap (``n_pad`` 10,000,072
    rows x 128 words, the list of its 153 row blocks): whatever ``_bitdot``
    holds besides its arguments stays far below a block of unpacked bits.
    Unpacked word-major and reshaped to ``(block_rows, K)`` — across the
    lane tiling, where XLA cannot fuse the unpack into the sum — each kept
    ``u32[32768,128,32]``, 537,000,448 bytes, and wrote and read it once a
    block: 0.98 TB a traversal to count a bitmap of 5.12 GB (PERF.md
    section 6, PR 28). A profile shows that only on the chip; this is the
    guard that runs without one."""
    from hypergraphdb_tpu.ops import ellbfs as eb

    visited = _sds((_N_PAD, _KW), "uint32")
    # the row blocks to fold: a slot a block of the bitmap, and how many
    listed = (_sds((-(-_N_PAD // eb.UPDATE_ROWS),), "int32"),
              _sds((), "int32"))
    args = ((visited, _sds((_N_PAD,), "int32")) if program == "_deg_sum"
            else (visited,)) + listed
    compiled = getattr(eb, program).lower(*_place(args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 64 * 2**20


def test_meet_test_reads_two_bitmaps_and_keeps_nothing(one_chip,
                                                       no_compile_cache):
    """``_meet`` at the pair cell's shapes — two bitmaps of 10,000,072 rows
    x 128 words, 4096 pairs: the AND and the OR-fold are fused over the
    row blocks, so what the program holds besides its arguments stays far
    under 1 GiB (whole, unfused, ``fwd & bwd`` would be a third bitmap of
    5.12 GB), nothing is donated, and the answer is a row of words."""
    from hypergraphdb_tpu.ops import ellbfs as eb

    ball = _sds((_N_PAD, _KW), "uint32")
    compiled = eb._meet.lower(*_place((ball, ball), one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30
    assert mem.alias_size_in_bytes == 0
    assert mem.output_size_in_bytes <= 4096  # (128,) uint32, tiled
    assert compiled.as_text().startswith("HloModule jit_hg_bfs_meet,")


# ------------------- the label round at the connected-components cell's shapes


#: The restricted plan of ``wcc10m.family16`` (``dbpedia10m-wcc`` at seed
#: 4021: 11,992,322 admitted entries; read on the chip by
#: ``benchmarks/tests/wcc_gather_probe.py --plan-seed 4021``, PR 38): stage
#: 1's five classes hold every admitted link, stage 2's ten classes, then
#: the pyramid of its rows above ``W_MAX``; 31 of the 153 row blocks active.
_T1 = (443_484, 1_781_784, 2_658_372, 3_555_640, 4_442_050)
_TW1 = (2, 4, 6, 8, 10)
_T2 = (376_440, 2_249_292, 3_879_924, 3_112_576, 1_457_570, 670_404,
       95_820, 51_184, 46_640, 1_219_456, 29_640, 3_368, 392, 40)
_TW2 = (2, 4, 6, 8, 10, 14, 20, 28, 40, 56, 8, 8, 8, 8)
_TN2 = 10


def _whole_graph_step(program: str, kernel: bool = False):
    """(jitted program, exemplar args, statics) of a whole-graph operator at
    its cell's plan: ``_wcc_round`` at ``wcc10m.family16``'s restricted plan
    (``_T1`` / ``_T2``), ``_pr_iter`` at the untyped plan (``_L1`` /
    ``_L2``: ``pagerank10m.iter10`` runs ``embedded10m.traverse3``'s);
    ``kernel``: the level-0 gathers and the fold's fetch on the scalar
    form, as a TPU runs them."""
    from hypergraphdb_tpu.ops import ellbfs as eb

    ints = lambda ls: tuple(_sds((n,), "int32") for n in ls)  # noqa: E731
    blocks = -(-_N_PAD // eb.UPDATE_ROWS)
    rows = eb._UpdateRows(_sds((_N_PAD,), "int32"),
                          _sds((blocks,), "int32"), _sds((), "int32"))
    if program == "_wcc_round":
        labels = _sds((_N_PAD,), "int32")
        route = eb._route((_T1, _TW1, len(_T1)), (_T2, _TW2, _TN2),
                          labels, _CHUNK, kernel)
        return eb._wcc_round, (
            labels, ints(_T1), ints(_T2), rows, _sds((), "int32")), {
            "route": route}
    chunks1 = sum(n // w for n, w in zip(_L1, _W1)) + 1
    weights = eb._PRWeights(_sds((_N_PAD,), "float32"),
                            _sds((chunks1,), "float32"),
                            _sds((_N_PAD,), "float32"))
    ranks = _sds((_N_PAD,), "float32")
    route = eb._route((_L1, _W1, len(_L1)), (_L2, _W2, _N2), ranks,
                      _CHUNK, kernel)
    return eb._pr_iter, (
        ranks, ints(_L1), ints(_L2), weights, rows, _sds((), "int32"),
        _sds((), "float32")), {"route": route}


def test_label_round_fits_one_chip_with_a_four_byte_label(one_chip,
                                                          no_compile_cache):
    """``_wcc_round`` — both min pyramids and the fold in ONE program — at
    the cell's plan: the labels are ``(n_pad,)`` int32, 4 bytes a row on the
    chip (a ``(n_pad, 1)`` column would be tiled to 512 bytes a row, 5.1 GB
    an argument), donated into the output; what the program holds besides
    its arguments — two stage buffers of an int32 a chunk and the scan's
    gather transients — stays under 1 GiB."""
    fn, args, statics = _whole_graph_step("_wcc_round")
    compiled = fn.lower(*_place(args, one_chip), **statics).compile()
    mem = compiled.memory_analysis()
    assert compiled.as_text().startswith("HloModule jit_hg_wcc_round,")
    assert mem.temp_size_in_bytes < 2**30
    assert mem.alias_size_in_bytes >= 4 * _N_PAD
    # the labels, the plan, out_map and the block list at 4 bytes an entry
    blocks = args[3].starts.shape[0]
    flat = 4 * (2 * _N_PAD + sum(_T1) + sum(_T2) + blocks + 1)
    assert mem.argument_size_in_bytes < 1.01 * flat


def _kernel_calls_under(text: str, scope: str) -> int:
    """The scalar form's calls in a compiled program's text whose op_name
    lies under the named scope ``scope``."""
    return sum(1 for line in text.splitlines()
               if "custom-call" in line and "hg_gather_scalar" in line
               and f"/{scope}/" in line)


def test_label_round_on_the_kernel_fits_one_chip(one_chip,
                                                 no_compile_cache):
    """``_wcc_round`` with its level-0 gathers on the scalar form (what a
    TPU runs: the route's forms where ``pallas_ok()``), at the cell's plan:
    every class of both stages calls the kernel, and so does the fold's
    fetch (width 1, a block of ``UPDATE_ROWS`` a call inside its loop,
    under scope ``hg.wcc.fold``); what the program holds besides its
    arguments stays under 256 MiB — no (chunks, 128) transient of a
    fetched row a chunk (4 bytes a chunk, as on the XLA route), the
    tables padded by under a grid step's 4 KB."""
    fn, args, statics = _whole_graph_step("_wcc_round", kernel=True)
    compiled = fn.lower(*_place(args, one_chip), **statics).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_hg_wcc_round,")
    assert text.count("hg_gather_scalar") >= len(_T1) + _TN2 + 1
    assert _kernel_calls_under(text, "hg.wcc.fold") >= 1
    assert mem.temp_size_in_bytes < 2**28
    assert mem.alias_size_in_bytes >= 4 * _N_PAD


def test_the_new_programs_carry_their_hgverify_entries():
    names = set(hgverify.REGISTRY.names())
    assert {"ops.ellbfs._wcc_init", "ops.ellbfs._wcc_round",
            "ops.ellbfs._wcc_count"} <= names


# -------------------- a PageRank iteration at the untyped cell's plan shapes


def test_pagerank_iteration_fits_one_chip_with_a_four_byte_rank(
        one_chip, no_compile_cache):
    """``_pr_iter`` — both sum pyramids, the link weights between them, the
    replacing fold and the elementwise update in ONE program — at the
    untyped plan (``_L1`` / ``_L2``: ``pagerank10m.iter10`` runs
    ``embedded10m.traverse3``'s plan): the ranks are ``(n_pad,)`` float32,
    4 bytes a row, donated into the output; what the program holds besides
    its arguments — two stage buffers of a float a chunk, the fold's zeros
    and the scan's gather transients — stays under 1 GiB."""
    fn, args, statics = _whole_graph_step("_pr_iter")
    compiled = fn.lower(*_place(args, one_chip), **statics).compile()
    mem = compiled.memory_analysis()
    assert compiled.as_text().startswith("HloModule jit_hg_pr_iter,")
    assert mem.temp_size_in_bytes < 2**30
    assert mem.alias_size_in_bytes >= 4 * _N_PAD
    # the ranks, the plan, the weights, out_map and the block list at 4
    # bytes an entry
    chunks1, blocks = args[3].w.shape[0], args[4].starts.shape[0]
    flat = 4 * (4 * _N_PAD + sum(_L1) + sum(_L2) + chunks1 + blocks + 1)
    assert mem.argument_size_in_bytes < 1.01 * flat


def test_pagerank_iteration_on_the_kernel_fits_one_chip(one_chip,
                                                        no_compile_cache):
    """``_pr_iter`` with its level-0 gathers on the scalar form, at the
    untyped plan: every class of both stages calls the kernel, and so
    does the replacing fold's fetch (under scope ``hg.pr.update``), the
    link weights meet stage 1's padded buffer, and what the program holds
    besides its arguments stays under 256 MiB."""
    fn, args, statics = _whole_graph_step("_pr_iter", kernel=True)
    compiled = fn.lower(*_place(args, one_chip), **statics).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_hg_pr_iter,")
    assert text.count("hg_gather_scalar") >= len(_L1) + _N2 + 1
    assert _kernel_calls_under(text, "hg.pr.update") >= 1
    assert mem.temp_size_in_bytes < 2**28
    assert mem.alias_size_in_bytes >= 4 * _N_PAD


def test_the_pagerank_programs_carry_their_hgverify_entries():
    names = set(hgverify.REGISTRY.names())
    assert {"ops.ellbfs._pr_init", "ops.ellbfs._pr_iter"} <= names


# ------------- the bitmap programs' text, which the whole-graph fold shares


#: sha256 (its first 32 digits) of each bitmap program's
#: ``lower(...).as_text()`` at ``_staged_step``'s exemplars for a described
#: v5e, by (program, fetch on the kernel): the text these programs had
#: before the whole-graph folds took the scalar form — what the compile
#: cache keys on. A kernel route's text is hashed with the Mosaic body
#: stripped (it names the checkout's files and lines). Regenerate from
#: ``_text_hash`` only for a change meant to move them, or a new JAX.
_BITMAP_TEXT = {
    ("_stage", True): "5368e3a966290112d05ca0660fc3268b",
    ("_stage_lvl0_consume", True): "5efb3d21e8a00d405a6841fb29faa52a",
    ("_stage_upper", False): "0e491a9e7a66c0ec56b0657cf0a97cc6",
    ("_visited_update", False): "b751848da9544bb0d5eb07368376c65c",
    ("_visited_update", True): "d3bb4f5619ca1f36951cb85ea452c148",
    ("_frontier_replace", False): "7c40cc4311917d9ad8f6a9a3a6347f4b",
    ("_frontier_replace", True): "684e595274bc80ecc8323715fb313178",
    ("_ball_update", False): "639dfb0a39487baecd70fd6312cbb82b",
    ("_ball_update", True): "0f68b95bba0584178f3d139a3a3bdd16",
}


def _text_hash(fn, args, statics, sharding) -> str:
    text = fn.lower(*_place(args, sharding), **statics).as_text()
    return hashlib.sha256(re.sub(r'backend_config = "[^"]*"',
                                 'backend_config = ""', text)
                          .encode()).hexdigest()[:32]


@pytest.mark.parametrize("step,kernel", list(_BITMAP_TEXT))
def test_the_bitmap_programs_lower_to_the_text_they_had(step, kernel,
                                                        one_chip):
    """``_fold_rows`` routes a flat state's fetch to the scalar form; the
    six bitmap programs — the three stages and the three updates, each
    update by both routes of its fetch — lower to the text they had."""
    assert _text_hash(*_staged_step(step, kernel)[:3], one_chip) \
        == _BITMAP_TEXT[(step, kernel)]


#: The same for the two whole-graph programs at ``_whole_graph_step``'s
#: exemplars, each by both routes (the kernel's text with the Mosaic body
#: stripped): the text they had before the gather's route became their one
#: static.
_WHOLE_GRAPH_TEXT = {
    ("_wcc_round", False): "6580bddf79ce011ccebf2af833768693",
    ("_wcc_round", True): "913c47d687580ac34aa9e1e8ba2adb83",
    ("_pr_iter", False): "f3e33c1ba82393ed7d76225f9b1e90e2",
    ("_pr_iter", True): "23ced0c75ab480f9a4950da1c91f1f6b",
}


@pytest.mark.parametrize("program,kernel", list(_WHOLE_GRAPH_TEXT))
def test_the_whole_graph_programs_lower_to_the_text_they_had(program, kernel,
                                                             one_chip):
    """A WCC round and a PageRank iteration, each by the XLA gather and by
    the kernel's scalar form, lower to the text they had."""
    assert _text_hash(*_whole_graph_step(program, kernel), one_chip) \
        == _WHOLE_GRAPH_TEXT[(program, kernel)]
