"""Whole traversals, one after another, as an embedded caller runs them.

Each traversal draws fresh seeds, calls ``ops.bfs_pull`` and ends when the
visited bitmap is ready on the device and the per-seed counts are on the
host. The previous result is dropped before the next traversal starts (one
4096-seed bitmap at 10M rows is 5.1 GB). The window closes with the first
traversal that ends after ``seconds``.
"""

from __future__ import annotations

import time

import numpy as np

from harness import bytes_model, refs


class Driver:
    def __init__(self, sut, cfg: dict, traffic: dict, seed: int, setup: dict):
        self.sut, self.traffic, self.seed = sut, traffic, seed
        self.rng = np.random.default_rng([seed, 2])
        self.last = None

    def _seeds(self) -> np.ndarray:
        e0, e1 = self.sut.entities
        return self.rng.integers(e0, e1, size=self.traffic["seeds"]
                                 ).astype(np.int32)

    def _traverse(self, seeds: np.ndarray):
        import jax

        from hypergraphdb_tpu.ops import bfs_pull

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.traverse"):
            res = bfs_pull(self.sut.snap, seeds, t["hops"], chunk=t["chunk"],
                           k_block=t["k_block"])
            jax.block_until_ready(res.visited_t)
        with jax.profiler.TraceAnnotation("bench.counts_to_host"):
            counts = np.asarray(res.reach_counts)
        return res, counts

    def warm(self) -> None:
        """One traversal at the window's own shapes; its result is dropped."""
        self._traverse(self._seeds())

    def run(self, seconds: float) -> dict:
        runs = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            # free the bitmap before the next: no other name may hold it
            self.last = None
            seeds = self._seeds()
            self.last, counts = self._traverse(seeds)
            runs.append({"seeds": seeds, "counts": counts,
                         "t_done": time.perf_counter() - t0})
        window_s = time.perf_counter() - t0
        self.runs = runs
        return {
            "window_s": window_s, "attempted": len(runs), "failed": 0,
            "end_to_end": {"traverse_time_s": window_s / len(runs)},
            "traversals": len(runs),
            "bytes_per_traversal": bytes_model.traverse_bytes(
                seeds=self.traffic["seeds"], hops=self.traffic["hops"],
                **self.sut.shapes),
        }

    def sample(self) -> list:
        """(traversal, column) pairs to hold to the reference: half from
        the last traversal (its bitmap is still on the device), the rest
        spread over the earlier ones; drawn from the seed."""
        r = np.random.default_rng([self.seed, 3])
        n = min(self.traffic["check_columns"], 64)
        k = self.traffic["seeds"]
        last = len(self.runs) - 1
        n_last = n if last == 0 else n // 2
        picks = [(last, int(c)) for c in r.choice(k, n_last, replace=False)]
        picks += [(int(r.integers(0, last)), int(r.integers(0, k)))
                  for _ in range(n - n_last)]
        return picks

    def fetch_columns(self, cols: list) -> np.ndarray:
        """Bit ``j`` of ``out[v]``: column ``cols[j]`` of the last
        traversal's bitmap, read from the device one column at a time."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def column(bitmap, word, shift):
            w = jax.lax.dynamic_slice_in_dim(bitmap, word, 1, axis=1)[:, 0]
            return ((w >> shift) & 1).astype(jnp.uint8)

        n = self.sut.n_atoms
        out = np.zeros(n, dtype=np.uint64)
        for j, c in enumerate(cols):
            bit = np.asarray(column(self.last.visited_t, jnp.int32(c // 32),
                                    jnp.uint32(c % 32)))[:n]
            out |= bit.astype(np.uint64) << np.uint64(j)
        return out

    def collect(self) -> dict:
        """What the check needs of the program's answers, taken while the
        bitmap is still on the device; then the program's state goes."""
        picks = self.sample()
        last = len(self.runs) - 1
        last_cols = [c for t, c in picks if t == last]
        got = {"picks": picks, "n_last": len(last_cols),
               "counts": [int(self.runs[t]["counts"][c]) for t, c in picks],
               "bitmap": self.fetch_columns(last_cols)}
        self.last = None
        self.sut.snap = None
        return got

    def reference(self, picks: list, n_last: int,
                  row_cap: int | None = None) -> dict:
        """The sampled seeds' reach counts and, for the first ``n_last``
        (the last traversal's), their visited columns, by a numpy BFS.
        ``row_cap`` makes it the control (see ``refs.host_bfs_bits``)."""
        sut = self.sut
        seeds = np.asarray([self.runs[t]["seeds"][c] for t, c in picks])
        vis = refs.host_bfs_bits(sut.n_atoms, sut.flat, sut.link_of,
                                 sut.n_atoms, seeds, self.traffic["hops"],
                                 row_cap=row_cap)
        keep = np.uint64((1 << n_last) - 1)
        return {"picks": picks, "n_last": n_last, "bitmap": vis & keep,
                "counts": [len(c) for c in
                           refs.bits_columns(vis, len(picks))]}

    def check(self, got: dict) -> dict:
        """Every number compared, beside its limit: all exact."""
        want = self.reference(got["picks"], got["n_last"])
        counts_differ = sum(a != b for a, b in zip(got["counts"],
                                                   want["counts"]))
        rows_differ = int(np.count_nonzero(got["bitmap"] != want["bitmap"]))
        return {"counts_differ": (counts_differ, 0),
                "bitmap_rows_differ": (rows_differ, 0),
                "seeds_compared": (len(got["picks"]), None)}

    def control(self, got: dict) -> dict:
        """The comparison of the CONTROL's answers: the reference in the
        program's place with one stated guarantee broken — an approximate
        traversal that looks at no more than ``control_row_cap`` incident
        links of an atom. It has to come out as not correct."""
        return self.check(self.reference(
            got["picks"], got["n_last"],
            row_cap=self.traffic["control_row_cap"]))
