"""The plain reference of a PageRank run: numpy float64 only, fed by the
generator's own arrays. Nothing here imports the program or reads anything
the program made: the entries are the generator's (``flat``, ``link_of``,
``builders/columnar_snapshot.tables``, a pure function of the seed).

The semantics (``PERF.md`` section 4): every atom is a vertex; from ``u``
the walk picks one of the target slots ``u`` holds in a link of two or more
DISTINCT targets, uniformly, then one of that link's other distinct atoms,
uniformly; an atom with no such slot is dangling. ``PR_0 = 1/N`` and

    PR'(v) = (1 - d)/N + d · Σ_u P(u, v) PR(u) + (d/N) · Σ_{dangling} PR

In pull form an iteration is two weighted ``np.bincount``: each link's sum
of its slots' ``x = PR/d(u)``, then each atom's sum over the distinct
(link, atom) pairs it is in of ``w_e`` times that, less ``c_v · x_v``.

The CONTROLS (never a run's reference) break one guarantee each:
``weighted=False`` drops ``w_e`` (an unnormalised walk), ``dangling=False``
drops the dangling mass, ``bf16=True`` rounds the pyramid's values and sums
through bfloat16, the precision below the configuration's float32.
"""

from __future__ import annotations

import numpy as np


def walk(n_ids: int, flat: np.ndarray, link_of: np.ndarray) -> dict:
    """The walk's weights, once a graph: the distinct (link, atom) pairs,
    ``w_e = 1/(δ' - 1)`` for a link of ``δ' >= 2`` distinct targets (else
    0), ``d(u)`` the slots ``u`` holds in links that step, ``c_v`` the
    ``w_e`` of the slots ``v`` holds."""
    pair_link, pair_atom = np.divmod(
        np.unique(link_of.astype(np.int64) * n_ids + flat), n_ids)
    distinct = np.bincount(pair_link, minlength=n_ids)
    w = np.zeros(n_ids)
    w[distinct >= 2] = 1.0 / (distinct[distinct >= 2] - 1)
    slot_w = w[link_of]
    d = np.bincount(flat, weights=slot_w > 0, minlength=n_ids)
    return {"pair_link": pair_link, "pair_atom": pair_atom, "w": w,
            "d": d, "c": np.bincount(flat, weights=slot_w, minlength=n_ids)}


def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def pagerank(n_ids: int, flat: np.ndarray, link_of: np.ndarray, *,
             damping: float, iterations: int, weights: dict | None = None,
             weighted: bool = True, dangling: bool = True,
             bf16: bool = False) -> np.ndarray:
    """``(n_ids,)`` float64 ranks after ``iterations``; ``weights`` is
    :func:`walk`'s, made here if not given. The last three arguments make
    the CONTROLS (module docstring)."""
    wk = weights if weights is not None else walk(n_ids, flat, link_of)
    w, c = wk["w"], wk["c"]
    if not weighted:
        w, c = (w > 0).astype(np.float64), wk["d"]
    rnd = _bf16 if bf16 else (lambda a: a)
    is_dangling = wk["d"] == 0
    inv_d = np.divide(1.0, wk["d"], out=np.zeros(n_ids), where=~is_dangling)
    rank = np.full(n_ids, 1.0 / n_ids)
    for _ in range(iterations):
        x = rnd(rank * inv_d)
        s = rnd(np.bincount(link_of, weights=x[flat], minlength=n_ids))
        y = rnd(np.bincount(wk["pair_atom"],
                            weights=rnd(w * s)[wk["pair_link"]],
                            minlength=n_ids))
        mass = rank[is_dangling].sum() if dangling else 0.0
        rank = ((1.0 - damping) / n_ids + damping * (y - c * x)
                + damping * mass / n_ids)
    return rank
