"""The multi-chip serving executor: serve buckets over the device mesh.

:class:`ShardedExecutor` is :class:`~.runtime.DeviceExecutor` with every
kernel dispatch rerouted through the ``ops/sharded_serving`` shard_map
programs — batches pin the manager's SHARDED (base, delta) twins
(``SnapshotManager.attach_mesh`` + ``pinned_view(sharded=True)``), BFS
frontiers exchange packed words over ICI, pattern candidates split along
the candidate axis, and join lanes split across chips. Everything else —
admission, batching, breakers, retries, AND the host-side memtable
corrections at collect — is inherited unchanged: the sharded kernels keep
the single-chip ``(counts, first_r)`` / ``JoinExecution`` contracts
bit-for-bit, so exactness guarantees are identical.

When it engages (see ``runtime._make_executor``): ``ServeConfig(
sharded=True)`` forces it; ``sharded=None`` + ``hbm_budget_bytes`` set
upgrades automatically once the pinned base snapshot no longer fits one
chip's budget. ``/healthz`` advertises the pod's mesh shape, gid-range
partition map, and per-shard HBM occupancy via :meth:`mesh_report`.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from hypergraphdb_tpu.serve.runtime import DeviceExecutor, ServeConfig
from hypergraphdb_tpu.serve.stats import ServeStats


def snapshot_device_bytes(base) -> int:
    """Estimated single-chip HBM footprint of one packed base snapshot
    (the per-row columns + both CSR relations) — what the AUTO shard
    trigger compares against ``ServeConfig.hbm_budget_bytes``."""
    n1 = base.num_atoms + 1
    per_row = 4 + 1 + 4 + 4 + 4 + 1      # type/is_link/arity/rank hi+lo/kind
    per_rel = 4 * 2                      # flat + src, int32 each
    return int(
        2 * (n1 + 1) * 4                 # the two offset arrays
        + n1 * per_row
        + base.n_edges_inc * per_rel
        + base.n_edges_tgt * per_rel
    )


class ShardedExecutor(DeviceExecutor):
    """Serve-batch execution over a ``jax.sharding.Mesh``.

    Construction attaches the mesh to the graph's snapshot manager; the
    first pinned view pays the one-time base repartition + upload (or
    :meth:`prewarm` does, at deploy time). ``mesh=None`` meshes every
    visible device (capped by ``ServeConfig.mesh_devices``)."""

    #: device-served results count under the mesh lane family
    #: (``serve.lane.<kind>.sharded``)
    device_lane = "sharded"

    def __init__(self, graph, config: ServeConfig,
                 stats: Optional[ServeStats] = None, mesh=None):
        super().__init__(graph, config, stats)
        if mesh is None:
            import jax

            from hypergraphdb_tpu.parallel.sharded import make_mesh

            devices = jax.devices()
            if config.mesh_devices is not None:
                devices = devices[: int(config.mesh_devices)]
            mesh = make_mesh(devices)
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self.mgr.attach_mesh(mesh)

    # -- pinning ---------------------------------------------------------------
    def _pin_view(self, kind: str, host_only: bool = False):
        # BFS reads the sharded (base ∪ delta) twins; pattern/join lanes
        # read the base host-side (assembly) + host corrections — they
        # pay no delta partition on their hot path, exactly as the
        # single-chip pattern path pays no delta upload
        return self.mgr.pinned_view(
            self.config.max_lag_edges,
            sync_delta=False,
            sharded=(kind == "bfs") and not host_only,
        )

    # -- BFS -------------------------------------------------------------------
    def bfs_bucket_cap(self) -> Optional[int]:
        # not modelled: the sharded program holds (K, n_loc) per device,
        # a 1/n_dev share of the single-chip program's rows
        return None

    def _serve_bfs(self, view, seeds_dev, max_hops: int, top_r: int):
        from hypergraphdb_tpu.ops.sharded_serving import (
            bfs_serve_batch_sharded,
        )

        self.stats.record_sharded_dispatch()
        args = (view.sharded_base, view.sharded_delta, seeds_dev)
        statics = {"max_hops": max_hops, "top_r": top_r}
        compiled = self._aot_dispatch(
            "ops.sharded_serving.bfs_serve_batch_sharded",
            bfs_serve_batch_sharded, args, statics,
        )
        if compiled is not None:
            return compiled(*args)
        return bfs_serve_batch_sharded(*args, **statics)

    # -- patterns --------------------------------------------------------------
    def _pattern_gate(self, view):
        from hypergraphdb_tpu.ops.sharded_serving import pattern_sharded_ok

        # truthy sentinel: host-assembled candidate rows need no
        # device-resident ELL matrix, only the arity cap
        return True if pattern_sharded_ok(view.base) else None

    def _serve_pattern(self, view, ell, anchors, type_vec):
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops.sharded_serving import (
            pattern_host_rows,
            pattern_serve_batch_sharded,
        )

        from hypergraphdb_tpu.ops.sharded_serving import mesh_carrier

        rows0, row0_types, tgt = pattern_host_rows(
            view.base, anchors, self.config.pattern_pad, self.n_dev
        )
        sdev = mesh_carrier(self.mesh)
        self.stats.record_sharded_dispatch()
        args = (sdev, jnp.asarray(rows0), jnp.asarray(row0_types),
                jnp.asarray(tgt), jnp.asarray(anchors, dtype=jnp.int32),
                jnp.asarray(type_vec))
        statics = {"top_r": self.config.top_r}
        compiled = self._aot_dispatch(
            "ops.sharded_serving.pattern_serve_batch_sharded",
            pattern_serve_batch_sharded, args, statics,
        )
        if compiled is not None:
            return compiled(*args)
        return pattern_serve_batch_sharded(*args, **statics)

    # -- joins -----------------------------------------------------------------
    def _execute_join(self, view, plan, consts, n_real: int):
        from hypergraphdb_tpu.ops.sharded_serving import (
            execute_join_sharded,
        )

        from hypergraphdb_tpu.ops.sharded_serving import mesh_carrier

        K = int(consts.shape[0])
        if K % self.n_dev or getattr(plan, "bags", None):
            # bucket not splittable over this mesh, or a bushy plan (the
            # sharded lane program runs one flat chain — sharding bag
            # materialization is the ROADMAP follow-up): exact
            # single-chip execution through the BASE executor, so the
            # join-v2 config knobs (caps, hub split, factorized) are
            # honored identically to the non-sharded tier
            return super()._execute_join(view, plan, consts, n_real)
        self.stats.record_sharded_dispatch()
        return execute_join_sharded(
            view.base, mesh_carrier(self.mesh), plan, consts,
            top_r=self.config.top_r, n_real=n_real,
        )

    # -- deploy-time prewarm ---------------------------------------------------
    def prewarm(self, buckets, max_hops: Optional[int] = None) -> int:
        """Compile (or AOT-load) the SHARDED bucket programs before the
        dispatch thread takes traffic — the multi-chip half of the
        cold-start story: the one-time base repartition + upload also
        happens here instead of inside the first request's deadline
        window. Returns executables served from cache."""
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops.sharded_serving import (
            bfs_serve_batch_sharded,
            mesh_carrier,
            pattern_host_rows,
            pattern_serve_batch_sharded,
            pattern_sharded_ok,
        )

        hops_list = ((int(max_hops),) if max_hops is not None
                     else tuple(self.config.prewarm_hops or ())
                     or (self.config.default_max_hops,))
        view = self._pin_view("bfs")
        n = view.base.num_atoms
        top_r = min(self.config.top_r + 1, n + 1)
        arities = (tuple(self.config.prewarm_pattern_arities or ())
                   if self.aot is not None and pattern_sharded_ok(view.base)
                   else ())
        warm = 0
        if self.aot is None:
            return 0
        for b in buckets:
            seeds = jnp.full((int(b),), n, dtype=jnp.int32)
            for hops in hops_list:
                try:
                    warm += self.aot.warm(
                        "ops.sharded_serving.bfs_serve_batch_sharded",
                        bfs_serve_batch_sharded,
                        (view.sharded_base, view.sharded_delta, seeds),
                        {"max_hops": hops, "top_r": top_r},
                    )
                except Exception:  # noqa: BLE001 - never block startup
                    continue
            for P in arities:
                anchors = np.full((int(b), int(P)), n, dtype=np.int32)
                tvec = np.full(int(b), -1, dtype=np.int32)
                rows0, rtypes, tgt = pattern_host_rows(
                    view.base, anchors, self.config.pattern_pad,
                    self.n_dev,
                )
                try:
                    warm += self.aot.warm(
                        "ops.sharded_serving.pattern_serve_batch_sharded",
                        pattern_serve_batch_sharded,
                        (mesh_carrier(self.mesh), jnp.asarray(rows0),
                         jnp.asarray(rtypes), jnp.asarray(tgt),
                         jnp.asarray(anchors), jnp.asarray(tvec)),
                        {"top_r": self.config.top_r},
                    )
                except Exception:  # noqa: BLE001 - never block startup
                    continue
        return warm

    # -- health ----------------------------------------------------------------
    def mesh_report(self) -> dict:
        """The pod topology ``/healthz`` advertises: mesh shape, the
        gid-range partition map (what shard-aware routing places by),
        and MEASURED per-shard HBM occupancy (empty per-device stats on
        backends without allocator stats, e.g. CPU)."""
        from hypergraphdb_tpu.parallel.sharded import (
            AXIS,
            device_memory_stats,
        )
        from hypergraphdb_tpu.storage.partitioned import PartitionMap

        with self.mgr._lock:
            sbase = self.mgr._sharded_base
            base = self.mgr.base
        pmap = (sbase.partition_map if sbase is not None
                else PartitionMap.for_mesh(base.num_atoms + 1, self.n_dev))
        stats = device_memory_stats()
        shards = []
        for part, dev in enumerate(self.mesh.devices.flat):
            lo, hi = pmap.range_of(part)
            rec = {"device": int(dev.id), "gid_lo": int(lo),
                   "gid_hi": int(hi)}
            mem = stats.get(str(dev.id))
            if mem:
                rec["hbm_bytes_in_use"] = mem["bytes_in_use"]
            shards.append(rec)
        return {
            "axis": AXIS,
            "devices": self.n_dev,
            "partition_map": pmap.to_dict(),
            "sharded_epoch": self.mgr._sharded_epoch,
            "shards": shards,
        }
