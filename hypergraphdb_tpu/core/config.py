"""Configuration tree.

Single dataclass config, JSON-loadable (the reference splits this across
``HGConfiguration.java:32-46``, ``HGQueryConfiguration``, backend config
beans and a JSON peer config; here it is one tree — see SURVEY §5 "Config").
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass
class QueryConfig:
    """Query-compiler knobs (reference: ``query/HGQueryConfiguration.java``)."""

    parallel_or: bool = False          # async union of Or branches
    prefer_device: bool = True         # plan onto TPU snapshot when possible
    #: smallest-child estimate below which ONE-SHOT dispatches stay on
    #: host cursors (planner duality). The value dates from the round-5
    #: set-up and is NOT MEASURED ON THE CURRENT CHIP: re-measuring the
    #: ad-hoc dispatch floor is ROADMAP queue 1 item 3
    #: (tools/calibrate_duality.py is the sweep). Batched serving
    #: (plan_pattern/execute_pattern) is NOT gated by this.
    device_min_batch: int = 262_144
    contract_conjunctions: bool = True
    #: cost cap for range-scan cardinality estimates: counts are exact up
    #: to this many entries, then clamped (HGIndexStats.java:37 analogue)
    range_estimate_cap: int = 4096


@dataclass
class CacheConfig:
    """Host-side cache sizing (reference wires 0.9/0.3 memory fractions at
    ``HyperGraph.java:316-323``; we use explicit entry counts)."""

    atom_cache_size: int = 1 << 20
    incidence_cache_entries: int = 1 << 16
    max_cached_incidence_set_size: int = 1 << 20
    #: RSS threshold (bytes) above which caches shrink; 0 disables the
    #: watcher (MemoryWarningSystem analogue)
    memory_warning_bytes: int = 0
    memory_warning_interval_s: float = 5.0


@dataclass
class SnapshotConfig:
    """Device snapshot build policy."""

    auto_refresh: bool = False         # re-pack CSR on snapshot() if stale
    delta_threshold: float = 0.15      # fraction of dirty atoms triggering full re-pack
    pad_multiple: int = 128            # pad CSR arrays to lane multiples
    dtype: str = "int32"               # device id dtype


@dataclass
class PeerConfig:
    """P2P peer settings (reference: JSON config consumed by
    ``peer/HyperGraphPeer.java:337-353``)."""

    name: str = ""
    transport: str = "loopback"        # "loopback" | "grpc"
    bootstrap: list = field(default_factory=list)
    replicate: bool = False
    listen_address: str = ""


@dataclass
class HGConfiguration:
    """Top-level configuration (reference: ``HGConfiguration.java:32-46``)."""

    transactional: bool = True
    keep_incident_links_on_removal: bool = False
    store_backend: str = "memory"      # "memory" | "native" | "partitioned"
    location: Optional[str] = None     # directory for persistent backends
    n_partitions: int = 4              # partitioned backend: child count
    handle_factory: str = "sequential"  # "sequential" | "uuid"
    query: QueryConfig = field(default_factory=QueryConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    snapshot: SnapshotConfig = field(default_factory=SnapshotConfig)
    peer: PeerConfig = field(default_factory=PeerConfig)

    @staticmethod
    def from_json(text: str) -> "HGConfiguration":
        raw = json.loads(text)
        return HGConfiguration.from_dict(raw)

    @staticmethod
    def from_dict(raw: dict) -> "HGConfiguration":
        kwargs: dict[str, Any] = {}
        for f in dataclasses.fields(HGConfiguration):
            if f.name not in raw:
                continue
            v = raw[f.name]
            if dataclasses.is_dataclass(f.type) or f.name in (
                "query", "cache", "snapshot", "peer",
            ):
                sub = {"query": QueryConfig, "cache": CacheConfig,
                       "snapshot": SnapshotConfig, "peer": PeerConfig}[f.name]
                v = sub(**v)
            kwargs[f.name] = v
        return HGConfiguration(**kwargs)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)
