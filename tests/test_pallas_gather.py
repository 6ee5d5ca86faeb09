"""Pallas gather+OR kernel: interpret-mode semantics vs the XLA reference.

Real Mosaic compiles need a TPU; CPU CI runs the kernel through the Pallas
interpreter, which exercises the same grid/DMA/semaphore program.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypergraphdb_tpu.ops import pallas_gather as pg


def _ref(values, idx, w):
    g = np.asarray(values)[np.asarray(idx)]
    return np.bitwise_or.reduce(g.reshape(-1, w, values.shape[1]), axis=1)


@pytest.mark.parametrize("w", [4, 8])
@pytest.mark.parametrize("n_out", [pg.G, pg.G * 3 + 17])
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


def test_gather_or_multi_segment(monkeypatch):
    # shrink SEG so the lax.scan path runs in-test
    monkeypatch.setattr(pg, "SEG", pg.G * 8 * 2)
    r = np.random.default_rng(1)
    S, w = 300, 8
    values = jnp.asarray(
        r.integers(0, 2**32, size=(S, 128), dtype=np.uint64).astype(np.uint32)
    )
    n_out = pg.G * 2 * 3 + 5  # 3 full segments + ragged tail
    idx = jnp.asarray(r.integers(0, S, size=n_out * w).astype(np.int32))
    out = pg.gather_or(values, idx, w, interpret=True)
    assert np.array_equal(np.asarray(out), _ref(values, idx, w))


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
