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
    (w, pg.G * 3 + 17) for w in WIDTHS])  # whole grid steps; a ragged tail
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


@pytest.mark.parametrize("w", WIDTHS)
def test_kernel_geometry_follows_the_width(w):
    """At every width the gate admits: the power of two of slots that
    keeps at least ``IN_FLIGHT`` copies outstanding and no more than twice
    that, never fewer than ``MIN_SLOTS`` (the kernel is bound by the
    copies it issues: more slots only lengthen a grid step's fill and
    drain, PERF.md section 6, PR 30), never more than a grid step's
    chunks, whole grid steps a segment, and a working set inside the VMEM
    budget."""
    assert pg.declined(w, pg.ROW_WORDS) is None
    d = pg.slots(w)
    assert pg.MIN_SLOTS <= d <= pg.G and d & (d - 1) == 0
    assert d * w >= pg.IN_FLIGHT
    assert d * w < 2 * pg.IN_FLIGHT or d == pg.MIN_SLOTS
    assert (pg.slots(2), pg.slots(8), pg.slots(56)) == (16, 4, 4)
    seg = pg._seg(w)
    assert seg % (pg.G * w) == 0 and pg.SEG - pg.G * w < seg <= pg.SEG
    assert pg.whole_segments(4 * pg.SEG, w) == 4 * pg.SEG // seg * seg
    assert pg.whole_segments(seg - 1, w) == seg - 1
    assert pg._vmem_bytes(w, pg.ROW_WORDS) == \
        4 * pg.ROW_WORDS * (2 * pg.G + d * w) <= pg.VMEM_BUDGET


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
