"""Device plane: CSR snapshots, frontier/set kernels, incremental overlays,
Pallas kernels, and snapshot checkpointing (SURVEY §7 device design)."""

from hypergraphdb_tpu.ops.snapshot import CSRSnapshot, DeviceSnapshot
from hypergraphdb_tpu.ops.frontier import bfs_levels, expand_frontier
from hypergraphdb_tpu.ops.bitfrontier import (
    bfs_memory_bytes,
    bfs_packed,
    unpack_visited,
)
from hypergraphdb_tpu.ops.ellbfs import (
    ComponentsResult,
    PageRankResult,
    PairDistResult,
    PathMatchResult,
    PullBFSResult,
    bfs_pull,
    connected_components,
    pagerank,
    pair_distances,
    path_match,
    visited_rows,
)
from hypergraphdb_tpu.ops.incremental import (
    PinnedView,
    SnapshotManager,
    bfs_levels_delta,
)
from hypergraphdb_tpu.ops.aot_cache import AOTCache
from hypergraphdb_tpu.ops.serving import bfs_serve_batch, pattern_serve_batch
from hypergraphdb_tpu.ops.setops import (
    and_incident_pattern,
    collect_pattern,
    execute_pattern,
    plan_pattern,
)
from hypergraphdb_tpu.ops.checkpoint import (
    copy_subgraph,
    export_graph,
    import_graph,
    load_snapshot,
    save_snapshot,
)

__all__ = [
    "AOTCache",
    "CSRSnapshot",
    "ComponentsResult",
    "DeviceSnapshot",
    "PageRankResult",
    "PairDistResult",
    "PathMatchResult",
    "PinnedView",
    "PullBFSResult",
    "SnapshotManager",
    "bfs_serve_batch",
    "pattern_serve_batch",
    "and_incident_pattern",
    "bfs_levels",
    "bfs_pull",
    "collect_pattern",
    "connected_components",
    "execute_pattern",
    "pagerank",
    "pair_distances",
    "path_match",
    "plan_pattern",
    "visited_rows",
    "bfs_memory_bytes",
    "bfs_packed",
    "unpack_visited",
    "bfs_levels_delta",
    "copy_subgraph",
    "expand_frontier",
    "export_graph",
    "import_graph",
    "load_snapshot",
    "save_snapshot",
]
