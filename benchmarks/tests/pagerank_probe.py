#!/usr/bin/env python3
"""The lowered text of the programs the PageRank iteration shares code with.

    python3 benchmarks/tests/pagerank_probe.py --hashes [--root <checkout>]

One process, one JSON line a program, no chip needed. ``ops.pagerank`` runs
the pyramids and the fold every other cell runs (``_apply_plan``,
``_fold_rows``) with a third reduction, the sum; the programs of the other
cells must lower to the text they had before it came — the compile cache
keys on that text. Printed: the sha256 of ``lower(...).as_text()`` of the
six bitmap programs ``wcc_gather_probe.py --hashes`` prints (``_stage``,
``_stage_lvl0_consume``, ``_stage_upper``, ``_visited_update``,
``_frontier_replace``, ``_ball_update``) and of ``_wcc_round``, at the
rehearsal shapes of ``dbpedia10m-wcc`` under the run's family, from the
program of ``--root``. Run it on a parent checkout and on the change, and
compare.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def wcc_round_hash(seed: int) -> None:
    """``_wcc_round``'s lowered text over the same graph and family as
    ``wcc_gather_probe.hashes``."""
    import jax

    from builders import columnar_snapshot
    from hypergraphdb_tpu.ops import ellbfs as eb
    from hypergraphdb_tpu.ops.snapshot import CSRSnapshot

    with open(os.path.join(BENCH, "configs", "dbpedia10m-wcc.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    tb = columnar_snapshot.tables(cfg, seed)
    snap = CSRSnapshot.from_tables(
        tb["type_of"], tb["is_link"], tb["tgt_offsets"],
        tb["tgt_flat"].astype(np.int32), value_rank=tb["value_rank"])
    link_types = np.unique(tb["type_of"][tb["entities"][1]:])
    family = np.sort(np.random.default_rng([seed, 5]).choice(
        link_types, cfg["family_types"], replace=False))
    sub = eb.restricted_for(snap, family.tolist())
    plans = eb.plans_for(sub)
    dev = eb._device_plans(sub, plans)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    s1 = plans.stage1
    args = (jax.ShapeDtypeStruct((plans.n_pad,), np.int32),
            tuple(map(sds, dev["levels1"])), tuple(map(sds, dev["levels2"])),
            jax.tree_util.tree_map(sds, dev["rows"]),
            jax.ShapeDtypeStruct((), np.int32))
    text = eb._wcc_round.lower(
        *args, widths1=s1.widths, n1=s1.n_lvl0,
        widths2=plans.stage2_widths, n2=plans.stage2_n_lvl0,
        chunk=1024).as_text()
    print(json.dumps({"part": "hash", "program": "_wcc_round",
                      "sha256": hashlib.sha256(text.encode()).hexdigest(),
                      "chars": len(text)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hashes", action="store_true",
                    help="print the shared programs' lowered-text hashes")
    ap.add_argument("--root", default=os.path.dirname(BENCH),
                    help="the checkout whose program is imported")
    args = ap.parse_args(argv)
    if not args.hashes:
        ap.error("--hashes is the one part")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.abspath(args.root))
    import wcc_gather_probe

    wcc_gather_probe.hashes(args.seed)
    wcc_round_hash(args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
