"""A served graph: entities and binary valued links through the store.

``ServeGraph.__init__`` of ``chip_smoke.py`` (PR 22): integer-valued
entities, then zipf-skewed binary links with distinct integer values, all
through ``HyperGraph.bulk_import``; then incremental snapshots, a
``ServeRuntime`` with the configuration's ``serve_config`` over the
default, and the planner. The arrays the references need are kept as the
data is generated, never read back from the system under test.
"""

from __future__ import annotations

import os
import time

import numpy as np


class Sut:
    def close(self) -> None:
        """Free the program's state: the runtime, then the store."""
        self.rt.close()
        self.g.close()
        self.rt = self.g = self.mgr = None


def build(cfg: dict, seed: int, setup: dict) -> Sut:
    from hypergraphdb_tpu import HyperGraph
    from hypergraphdb_tpu.plan import QueryPlanner
    from hypergraphdb_tpu.serve import ServeConfig, ServeRuntime

    r = np.random.default_rng([seed, 11])
    ne, nl, v0 = cfg["n_entities"], cfg["n_links"], cfg["link_value0"]
    sut = Sut()
    t0 = time.perf_counter()
    sut.g = g = HyperGraph()
    ents = g.bulk_import(values=np.arange(ne).tolist())
    e0 = int(ents[0])
    if len(ents) != ne or int(ents[-1]) != e0 + ne - 1:
        raise RuntimeError("entity handles are not one contiguous range")
    link_h, link_a, link_b = [], [], []
    for s0 in range(0, nl, cfg["load_batch"]):
        m = min(cfg["load_batch"], nl - s0)
        subj = e0 + (r.zipf(cfg["zipf_a"], size=m) % ne)
        obj = e0 + r.integers(0, ne, size=m)
        hs = g.bulk_import(
            values=[v0 + s0 + i for i in range(m)],
            target_lists=np.stack([subj, obj], axis=1).tolist())
        link_h.append(np.arange(int(hs[0]), int(hs[0]) + m))
        link_a.append(subj)
        link_b.append(obj)
    setup["store_load_s"] = time.perf_counter() - t0
    sut.e0, sut.n_entities = e0, ne
    sut.link_h = np.concatenate(link_h).astype(np.int64)
    sut.link_a = np.concatenate(link_a).astype(np.int64)
    sut.link_b = np.concatenate(link_b).astype(np.int64)
    sut.link_val = v0 + np.arange(nl, dtype=np.int64)
    sut.link_type = int(g.get_type_handle_of(int(sut.link_h[0])))
    t0 = time.perf_counter()
    sut.mgr = g.enable_incremental(pack_pad_multiple=cfg["pack_pad_multiple"])
    sut.id_space = int(sut.mgr.base.num_atoms)
    setup["first_pack_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in cfg["serve_config"].items()}
    sut.serve_config = ServeConfig(
        aot_cache_dir=os.path.join(setup["checkout"], ".aot_cache"),
        **overrides)
    sut.rt = ServeRuntime(g, sut.serve_config)
    sut.rt.attach_planner(QueryPlanner(g))
    setup["runtime_start_s"] = time.perf_counter() - t0
    # shapes for the byte model: atoms, and one entry per link end in
    # either relation
    sut.shapes = {"n_rows": ne + nl, "e_inc": 2 * nl, "e_tgt": 2 * nl}
    return sut
