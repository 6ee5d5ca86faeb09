"""A DBpedia-shaped columnar snapshot, as an embedded caller builds it.

The table arithmetic is a copy of ``models.dbpedia_snapshot`` (so that the
benchmark's graph cannot drift with the program's generator);
``CSRSnapshot.from_tables`` stays the program's: it is the load path under
test. Id layout: [0] the entity type, [1..P] property types, then the
entities, then the links. A link's first target is zipf-skewed (hubs), the
rest uniform.
"""

from __future__ import annotations

import time

import numpy as np


class Sut:
    """What the drivers get: the program's snapshot, and the generator's
    own arrays for the references."""


def tables(cfg: dict, seed: int) -> dict:
    r = np.random.default_rng([seed, 13])
    n_ent, n_links, n_prop = cfg["n_entities"], cfg["n_links"], cfg["n_properties"]
    t = 1 + n_prop
    n = t + n_ent + n_links
    e0, l0 = t, t + n_ent
    type_of = np.zeros(n, dtype=np.int32)
    props = r.integers(0, n_prop, size=n_links).astype(np.int32)
    type_of[l0:] = 1 + props
    is_link = np.zeros(n, dtype=bool)
    is_link[l0:] = True
    arities = r.integers(cfg["min_arity"], cfg["max_arity"] + 1,
                         size=n_links).astype(np.int64)
    total = int(arities.sum())
    tgt_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(arities, out=tgt_offsets[l0 + 1:])
    tgt_flat = e0 + r.integers(0, n_ent, size=total).astype(np.int64)
    subj = e0 + (r.zipf(cfg["zipf_a"], size=n_links) % n_ent)
    tgt_flat[tgt_offsets[l0:-1]] = subj
    value_rank = np.zeros(n, dtype=np.uint64)
    value_rank[l0:] = props.astype(np.uint64)
    value_rank[e0:l0] = np.arange(n_ent, dtype=np.uint64)
    return {"type_of": type_of, "is_link": is_link,
            "tgt_offsets": tgt_offsets, "tgt_flat": tgt_flat,
            "value_rank": value_rank, "entities": (e0, l0), "n_atoms": n,
            "arities": arities, "total_arity": total}


def build(cfg: dict, seed: int, setup: dict) -> Sut:
    from hypergraphdb_tpu.ops.ellbfs import plans_for
    from hypergraphdb_tpu.ops.snapshot import CSRSnapshot

    t0 = time.perf_counter()
    tb = tables(cfg, seed)
    sut = Sut()
    sut.snap = CSRSnapshot.from_tables(
        tb["type_of"], tb["is_link"], tb["tgt_offsets"],
        tb["tgt_flat"].astype(np.int32), value_rank=tb["value_rank"])
    setup["graph_build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    plans_for(sut.snap)
    setup["plan_build_s"] = time.perf_counter() - t0
    # for the reference: incidence entries as generated
    l0 = tb["entities"][1]
    sut.flat = tb["tgt_flat"]
    sut.link_of = np.repeat(np.arange(l0, tb["n_atoms"], dtype=np.int64),
                            tb["arities"])
    sut.entities = tb["entities"]
    sut.n_atoms = tb["n_atoms"]
    # shapes for the byte model: every target entry is one incidence entry
    sut.shapes = {"n_rows": tb["n_atoms"], "e_inc": tb["total_arity"],
                  "e_tgt": tb["total_arity"]}
    return sut
