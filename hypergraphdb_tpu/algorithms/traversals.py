"""Graph traversals: BFS/DFS iterators + adjacency-list generators.

Re-expression of the reference's ``algorithms/`` package:
``HGTraversal`` — an iterator of (parent-link, atom) pairs
(``algorithms/HGTraversal.java:36``), ``HGBreadthFirstTraversal.java:29``
(queue + examined map, advance :49-66), ``HGDepthFirstTraversal.java:28``,
and the adjacency generators ``HGALGenerator``/``SimpleALGenerator.java:27``/
``DefaultALGenerator.java:73`` (link & sibling predicates, ordered-link
direction options, generate :504-509).

These are the *host-plane* semantics oracle. The device plane runs the same
frontier expansion as batched CSR message passing (``ops/frontier.py``);
``TraversalPlan`` in the query compiler picks between them.
"""

from __future__ import annotations

import heapq
from collections import deque
from typing import Callable, Iterator, Optional

from hypergraphdb_tpu.core.errors import NotFoundError
from hypergraphdb_tpu.core.handles import HGHandle

LinkPredicate = Callable[["HyperGraph", HGHandle], bool]  # noqa: F821
AtomPredicate = Callable[["HyperGraph", HGHandle], bool]  # noqa: F821


class HGALGenerator:
    """Adjacency-list generator: for an atom, yield (link, neighbor) pairs."""

    def generate(self, atom: HGHandle) -> Iterator[tuple[HGHandle, HGHandle]]:
        raise NotImplementedError


class SimpleALGenerator(HGALGenerator):
    """All siblings through all incident links (``SimpleALGenerator.java:27``)."""

    def __init__(self, graph):
        self.graph = graph

    def generate(self, atom):
        atom = int(atom)
        for link in self.graph.get_incidence_set(atom):
            for t in self.graph.get_targets(link):
                if t != atom:
                    yield (int(link), int(t))


class DefaultALGenerator(HGALGenerator):
    """Filtered/directed adjacency (``DefaultALGenerator.java:73``):

    - ``link_predicate`` filters which incident links are followed,
    - ``sibling_predicate`` filters which neighbors are yielded,
    - ``return_preceeding``/``return_succeeding`` restrict, for *ordered*
      links, to targets before/after the source atom's position (the
      directed-hyperedge options),
    - ``reverse_order`` walks a link's targets backwards.
    """

    def __init__(
        self,
        graph,
        link_predicate: Optional[LinkPredicate] = None,
        sibling_predicate: Optional[AtomPredicate] = None,
        return_preceeding: bool = True,
        return_succeeding: bool = True,
        reverse_order: bool = False,
    ):
        self.graph = graph
        self.link_predicate = link_predicate
        self.sibling_predicate = sibling_predicate
        self.return_preceeding = return_preceeding
        self.return_succeeding = return_succeeding
        self.reverse_order = reverse_order

    def generate(self, atom):
        g = self.graph
        atom = int(atom)
        for link in g.get_incidence_set(atom):
            link = int(link)
            if self.link_predicate is not None and not self.link_predicate(g, link):
                continue
            targets = g.get_targets(link)
            # positions of the source atom in the link (may repeat)
            pos = [i for i, t in enumerate(targets) if t == atom]
            if not pos:
                continue
            lo, hi = min(pos), max(pos)
            order = range(len(targets) - 1, -1, -1) if self.reverse_order else range(
                len(targets)
            )
            for i in order:
                t = targets[i]
                if t == atom:
                    continue
                if not self.return_preceeding and i < hi:
                    continue
                if not self.return_succeeding and i > lo:
                    continue
                if self.sibling_predicate is not None and not self.sibling_predicate(
                    g, t
                ):
                    continue
                yield (link, int(t))


class HGTraversal:
    """Base traversal iterator of (parent_link, atom) pairs; the start atom
    itself is not yielded (reference contract)."""

    def __init__(
        self,
        graph,
        start: HGHandle,
        generator: Optional[HGALGenerator] = None,
        max_distance: Optional[int] = None,
    ):
        self.graph = graph
        self.start = int(start)
        self.generator = generator or SimpleALGenerator(graph)
        self.max_distance = max_distance

    def __iter__(self) -> Iterator[tuple[Optional[HGHandle], HGHandle]]:
        raise NotImplementedError


class HGBreadthFirstTraversal(HGTraversal):
    """Queue-based BFS (``HGBreadthFirstTraversal.java:29``)."""

    def __iter__(self):
        visited = {self.start}
        q: deque[tuple[int, int]] = deque([(self.start, 0)])
        while q:
            atom, dist = q.popleft()
            if self.max_distance is not None and dist >= self.max_distance:
                continue
            for link, nbr in self.generator.generate(atom):
                if nbr in visited:
                    continue
                visited.add(nbr)
                yield (link, nbr)
                q.append((nbr, dist + 1))


class HGDepthFirstTraversal(HGTraversal):
    """Stack-based DFS (``HGDepthFirstTraversal.java:28``)."""

    def __iter__(self):
        if self.max_distance is not None and self.max_distance <= 0:
            return
        visited = {self.start}
        # stack of (parent_link, atom, distance); yield on pop = preorder DFS
        stack: list[tuple[int, int, int]] = [
            (link, nbr, 1)
            for link, nbr in reversed(list(self.generator.generate(self.start)))
        ]
        while stack:
            link, atom, dist = stack.pop()
            if atom in visited:
                continue
            visited.add(atom)
            yield (link, atom)
            if self.max_distance is None or dist < self.max_distance:
                nbrs = list(self.generator.generate(atom))
                for l, n in reversed(nbrs):
                    if n not in visited:
                        stack.append((l, n, dist + 1))


class HyperTraversal:
    """Link-as-node flattened traversal (``HyperTraversal.java:33``): yields
    both atoms and the links between them as visited nodes."""

    def __init__(self, graph, start: HGHandle, max_distance: Optional[int] = None):
        self.graph = graph
        self.start = int(start)
        self.max_distance = max_distance

    def __iter__(self):
        visited = {self.start}
        q: deque[tuple[int, int]] = deque([(self.start, 0)])
        while q:
            node, dist = q.popleft()
            if self.max_distance is not None and dist >= self.max_distance:
                continue
            neighbors: list[tuple[int, int]] = []
            for link in self.graph.get_incidence_set(node):
                neighbors.append((int(link), int(link)))
            try:
                for t in self.graph.get_targets(node):
                    neighbors.append((node, int(t)))
            except NotFoundError:
                pass  # a plain atom in the frontier has no targets —
                # anything ELSE (storage fault, evaluation bug) propagates
            for parent, nbr in neighbors:
                if nbr in visited:
                    continue
                visited.add(nbr)
                yield (parent, nbr)
                q.append((nbr, dist + 1))


# ---------------------------------------------------------------- classics


def match_path(graph, start: HGHandle, link_predicates) -> set:
    """The distinct end points of the path pattern ``F_1 / … / F_H`` from
    ``start``: step h follows an incident link only if
    ``link_predicates[h](graph, link)`` holds (``None``: every link) and
    reaches every target of it. ``X_0 = {start}``; ``X_h`` is the union of
    the targets of the admitted links incident to an atom of ``X_{h-1}``;
    the answer is ``X_H``. The query engine's set semantics — a chain of
    ``And(type(T), incident(x), incident(y))`` through the shared variable,
    SPARQL 1.1's SequencePath under ``SELECT DISTINCT`` — so the chain's
    variables may bind the same atom: an atom of ``X_{h-1}`` that lies in
    an admitted link is itself in ``X_h`` (no ``t != atom``, where
    :class:`DefaultALGenerator` has one). Not a traversal: no visited set,
    nothing accumulates; a step that admits no link gives the empty set,
    no step gives ``{start}``. The plain reference of
    ``ops.ellbfs.path_match``, independent of it."""
    ends = {int(start)}
    for admits in link_predicates:
        nxt: set = set()
        for atom in ends:
            for link in graph.get_incidence_set(atom):
                if admits is None or admits(graph, link):
                    nxt.update(int(t) for t in graph.get_targets(link))
        ends = nxt
    return ends


def shortest_path_length(
    graph,
    start: HGHandle,
    goal: HGHandle,
    generator: Optional[HGALGenerator] = None,
    max_distance: Optional[int] = None,
) -> int:
    """How many hops from ``start`` to ``goal``: ``len(dijkstra(start,
    goal, generator)) - 1`` at unit weights, 0 for ``start == goal``, -1
    where there is no path or the shortest is longer than ``max_distance``
    (:class:`HGBreadthFirstTraversal`'s cap). A forward search in that
    traversal's order with a distance per atom — the least h at which the
    traversal with ``max_distance = h`` yields ``goal``. Lengths only, no
    predecessor map. The plain reference of ``ops.ellbfs.pair_distances``,
    independent of it: one ball, from ``start`` alone."""
    gen = generator or SimpleALGenerator(graph)
    start, goal = int(start), int(goal)
    dist = {start: 0}
    q: deque[int] = deque([start])
    while q:
        atom = q.popleft()
        if atom == goal:
            return dist[atom]
        if max_distance is not None and dist[atom] >= max_distance:
            continue
        for _, nbr in gen.generate(atom):
            if nbr not in dist:
                dist[nbr] = dist[atom] + 1
                q.append(nbr)
    return -1


def dijkstra(
    graph,
    start: HGHandle,
    goal: HGHandle,
    generator: Optional[HGALGenerator] = None,
    weight: Optional[Callable[[HGHandle], float]] = None,
) -> Optional[list[HGHandle]]:
    """Shortest path (``GraphClassics.dijkstra`` :80). Returns the atom path
    start..goal or None. ``weight`` maps a link handle to its edge weight."""
    gen = generator or SimpleALGenerator(graph)
    start, goal = int(start), int(goal)
    dist: dict[int, float] = {start: 0.0}
    prev: dict[int, int] = {}
    heap: list[tuple[float, int]] = [(0.0, start)]
    done: set[int] = set()
    while heap:
        d, atom = heapq.heappop(heap)
        if atom in done:
            continue
        done.add(atom)
        if atom == goal:
            path = [goal]
            while path[-1] != start:
                path.append(prev[path[-1]])
            return list(reversed(path))
        for link, nbr in gen.generate(atom):
            w = 1.0 if weight is None else float(weight(link))
            nd = d + w
            if nd < dist.get(nbr, float("inf")):
                dist[nbr] = nd
                prev[nbr] = atom
                heapq.heappush(heap, (nd, nbr))
    return None


def connected_components(graph, generator: Optional[HGALGenerator] = None
                         ) -> dict[int, int]:
    """Which atoms hang together: ``{atom: label}`` for every atom of
    ``graph.atoms()``, the label being the least atom id of the atom's
    component under ``generator``'s adjacency (:class:`SimpleALGenerator`
    when None; a :class:`DefaultALGenerator` with a ``link_predicate`` for
    a link family) — LDBC Graphalytics' WCC. The atoms are taken in id
    order, and from each one not labelled yet a
    :class:`HGBreadthFirstTraversal` runs to exhaustion: the start and
    everything it yields take the start's id, which is the least of its
    component because every smaller atom came first. The adjacency is
    symmetric (two atoms share a link), so the traversal finds the whole
    component. The plain reference of ``ops.ellbfs.connected_components``,
    independent of it."""
    gen = generator or SimpleALGenerator(graph)
    label: dict[int, int] = {}
    for atom in graph.atoms():
        atom = int(atom)
        if atom in label:
            continue
        label[atom] = atom
        for _, nbr in HGBreadthFirstTraversal(graph, atom, gen):
            label[nbr] = atom
    return label


def pagerank(snap, link_types=None, *, damping: float = 0.85,
             iterations: int = 10):
    """How important each atom is: ``(num_atoms,)`` float64 ranks, LDBC
    Graphalytics' PageRank with every atom a vertex, over the walk of
    ``ops.ellbfs.pagerank`` under a link family (``link_types``; None:
    every link): from ``u`` pick one of the target slots ``u`` holds in a
    link of two or more DISTINCT targets, uniformly, then one of that
    link's other distinct atoms, uniformly. ``PR_0 = 1/N`` and each of
    ``iterations`` rounds is

        PR'(v) = (1 - d)/N + d · Σ_u P(u, v) PR(u) + (d/N) · Σ_{dangling} PR

    straight from the snapshot's TARGET relation (``tgt_offsets`` /
    ``tgt_flat``, links filtered by ``type_of``), in numpy: the (link,
    atom) pairs are deduplicated here, never read from the incidence
    relation or a plan. The plain reference of ``ops.ellbfs.pagerank``,
    independent of it."""
    import numpy as np

    n = int(snap.num_atoms)
    lens = np.diff(np.asarray(snap.tgt_offsets[: n + 1], dtype=np.int64))
    link = np.repeat(np.arange(n, dtype=np.int64), lens)
    atom = np.asarray(snap.tgt_flat[: len(link)], dtype=np.int64)
    if link_types is not None:
        family = np.fromiter((int(t) for t in link_types), dtype=np.int64)
        keep = np.isin(np.asarray(snap.type_of[:n])[link], family)
        link, atom = link[keep], atom[keep]
    pair_link, pair_atom = np.divmod(np.unique(link * n + atom), n)
    distinct = np.bincount(pair_link, minlength=n)
    w = np.zeros(n)
    w[distinct >= 2] = 1.0 / (distinct[distinct >= 2] - 1)
    slot_w = w[link]
    d = np.bincount(atom, weights=slot_w > 0, minlength=n)
    c = np.bincount(atom, weights=slot_w, minlength=n)
    dangling = d == 0
    inv_d = np.divide(1.0, d, out=np.zeros(n), where=~dangling)
    rank = np.full(n, 1.0 / n)
    for _ in range(iterations):
        x = rank * inv_d
        s = np.bincount(link, weights=x[atom], minlength=n)
        y = np.bincount(pair_atom, weights=(w * s)[pair_link], minlength=n)
        rank = ((1.0 - damping) / n + damping * (y - c * x)
                + damping * rank[dangling].sum() / n)
    return rank


def has_cycles(graph, start: HGHandle, generator: Optional[HGALGenerator] = None) -> bool:
    """Cycle detection from a start atom (``GraphClassics.hasCycles`` :40),
    treating generated adjacency as directed edges."""
    gen = generator or SimpleALGenerator(graph)
    WHITE, GRAY, BLACK = 0, 1, 2
    color: dict[int, int] = {}

    def visit(a: int) -> bool:
        color[a] = GRAY
        for _, nbr in gen.generate(a):
            st = color.get(nbr, WHITE)
            if st == GRAY:
                return True
            if st == WHITE and visit(nbr):
                return True
        color[a] = BLACK
        return False

    return visit(int(start))
