"""The serving runtime: dispatch loop, device executor, lifecycle.

Request path::

    submit_*() → AdmissionQueue (bounded, deadline-shedding)
        → Batcher (coalesce + pad-to-bucket, flush on full/linger)
            → Executor.launch()  — pin view, assemble, async device dispatch
                → Executor.collect() — sync, LSM-correct, complete futures

The dispatch thread **double-buffers**: ``pump()`` launches batch N+1
BEFORE collecting batch N's results, so host-side assembly of the next
batch (numpy padding, anchor ordering, delta refresh) overlaps device
execution of the current one — JAX dispatch is asynchronous, the
``launch`` never blocks on the device.

Consistency: every batch is assembled from ONE
:class:`~hypergraphdb_tpu.ops.incremental.PinnedView` — base, device
delta, and the host memtable captured under a single manager lock — so a
background compaction swapping mid-batch cannot desync what the kernel
reads from what the host correction compensates. BFS requests see
base ∪ delta directly in the kernel (staleness bounded by
``max_lag_edges``); pattern requests run on the base and the memtable is
merged at collect time (the ``query/compiler.DeviceValueConjPlan`` LSM
read-merge) against candidate records CAPTURED when the batch launched —
never the live graph — so every answer in a batch reflects the pinned
view's single point in the manager's event stream, however long the
device ran.

Deterministic testing: ``ServeConfig(manual=True)`` starts no thread —
tests drive ``step()`` / ``pump()`` with an injected clock and a fake
executor, making deadline shedding, flush policy, and drains exactly
reproducible.
"""

from __future__ import annotations

import random
import threading
import time
from concurrent.futures import Future
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import numpy as np

from hypergraphdb_tpu.fault import (
    OPEN,
    CircuitBreaker,
    global_faults,
    is_transient,
)
from hypergraphdb_tpu.obs import global_tracer
from hypergraphdb_tpu.obs.device import annotate, profiling
from hypergraphdb_tpu.obs.flight import global_flight

#: process flight recorder, bound once (the fault-registry singleton
#: discipline: one attribute read per site when quiet)
_FLIGHT = global_flight()

#: the no-annotation dispatch context — stateless, safe to re-enter, so
#: the common (un-profiled) path allocates nothing per dispatch
_NULL_CM = nullcontext()
from hypergraphdb_tpu.serve.admission import AdmissionQueue
from hypergraphdb_tpu.serve.batcher import BUCKETS, Batcher, MicroBatch
from hypergraphdb_tpu.serve.stats import ServeStats
from hypergraphdb_tpu.serve.types import (
    BFSRequest,
    Clock,
    JoinRequest,
    JoinResult,
    PatternRequest,
    RangeRequest,
    ServeResult,
    Ticket,
    Unservable,
)


def _thread_cm(config: "ServeConfig", name: str):
    """What the one dispatch thread is doing (``hg.serve.launch`` /
    ``.collect`` / ``.host_reserve`` / ``.park``), as a host span on the
    profiler's clock so a trace's idle gaps are charged to it. Gated
    exactly as ``DeviceExecutor._dispatch_cm``: the un-profiled path
    re-enters the shared null context and allocates nothing."""
    if config.device_timing or profiling():
        return annotate(name)
    return _NULL_CM


@dataclass
class ServeConfig:
    """Knobs of one runtime; defaults suit the streaming-bench scale."""

    buckets: Sequence[int] = BUCKETS        # pad-to-bucket request widths
    max_queue: int = 4096                   # admission queue bound
    policy: str = "block"                   # backpressure: "block" | "fail"
    max_linger_s: float = 0.002             # flush latency bound
    default_deadline_s: Optional[float] = None
    max_lag_edges: int = 0                  # delta staleness bound (BFS)
    top_r: int = 128                        # compact result window
    pattern_pad: int = 128                  # base-row budget per pattern
    default_max_hops: int = 2
    clock: Optional[Clock] = None           # injectable time source
    manual: bool = False                    # no thread; tests call step()
    latency_window: int = 4096
    #: pre-admission fitness gate: a callable returning None (admit) or
    #: a reason string (refuse with AdmissionGated). The replica tier
    #: wires its replication-lag bound here, so a lagging replica sheds
    #: to the router instead of answering past its staleness contract.
    admission_gate: Optional[Callable[[], Optional[str]]] = None
    tracer: Optional[object] = None         # hgobs Tracer; None → global
    device_timing: bool = False             # launch→ready deltas per batch
    #: hgperf sentinel (``obs.perf.PerfSentinel``): every completed
    #: request feeds its rolling per-lane digests, and the completion
    #: path drives its rate-limited evaluation (``maybe_tick``). The
    #: device-seconds digest additionally needs ``device_timing=True``
    #: AND an enabled tracer (``block_timed`` measurement rides the
    #: trace clock). Give the sentinel the SAME clock as the runtime —
    #: samples are stamped on it. None disables (zero cost: one
    #: attribute read per completion).
    perf: Optional[object] = None
    # -- self-healing (hgfault) ----------------------------------------------
    max_retries: int = 2                    # transient launch re-attempts
    retry_base_s: float = 0.005             # backoff seed: base * 2^(n-1)
    retry_max_s: float = 0.25               # backoff cap
    retry_jitter: float = 0.5               # multiplicative jitter frac
    retry_seed: int = 0                     # deterministic jitter stream
    breaker_threshold: int = 3              # consecutive failures → OPEN
    breaker_cooldown_s: float = 0.25        # OPEN → HALF_OPEN probe delay
    transient_errors: tuple = ()            # extra types to retry
    sleep: Optional[Callable] = None        # injectable backoff sleeper
    faults: Optional[object] = None         # fault registry; None → global
    # -- raw speed (aot_cache) -----------------------------------------------
    aot_cache_dir: Optional[str] = None     # AOT compile cache; None → env
    prewarm_aot: bool = True                # compile K buckets at startup
    prewarm_hops: Optional[tuple] = None    # hops to warm; None → (default,)
    #: pattern anchor arities P to prewarm per bucket (ROADMAP 4d) —
    #: P is a device shape dim, one compiled program each; () disables
    prewarm_pattern_arities: tuple = (1, 2)
    #: build + upload the co-incidence CSR at startup (deployments that
    #: serve joins): the build is O(Σ arity²) — done lazily it would
    #: land on the dispatch thread inside the first join batch's
    #: deadline window after every compaction. Opt-in: BFS/pattern-only
    #: tiers should not pay it.
    prewarm_join_nbr: bool = False
    # -- join engine v2 (degree-split / factorized / partial correction) -----
    #: build the prefix-grouped (trie) encoding of the co/tgt relations
    #: once per (signature-cache miss, base epoch) at plan time — K
    #: lanes probing equal rows then touch one HBM copy. The build is
    #: O(E log E) host work per epoch; joins-light tiers can switch it
    #: off and keep the flat CSRs.
    join_factorized: bool = True
    #: degree-split plans: lanes whose const-keyed rows exceed the hub
    #: threshold run the chunked dense-frontier chain instead of
    #: truncating onto the host path (``ops/join.join_hub_expand``)
    join_hub_split: bool = True
    #: hub threshold override (row width); None = the executor's pad cap
    join_hub_threshold: Optional[int] = None
    #: executor shape caps for the join lane (``ops/join`` defaults:
    #: 2^15 pooled binding rows, 2^10 expansion pad) — a deployment
    #: serving hub-anchored joins device-exact raises join_row_cap to
    #: hold the hub's full binding set
    join_row_cap: int = 1 << 15
    join_pad_cap: int = 1 << 10
    #: per-lane memtable correction (ROADMAP 2d): while the dirty set —
    #: new links plus their targets — stays at most this many atoms,
    #: join batches keep dispatching on device and collect merges the
    #: host-enumerated tuples touching the dirty set
    #: (``join/host.host_join_touching``); past it (or on any tombstone/
    #: revalue) the whole batch takes the exact host path as before.
    #: 0 disables the partial path.
    join_dirty_max: int = 16
    #: value DIMENSIONS (kind bytes, e.g. ``(ord("i"),)``) whose sorted
    #: index columns build + upload at startup, with the range-lane
    #: executables warmed per bucket when an AOT cache is configured —
    #: the hgindex half of the cold-start story (done lazily, the
    #: O(N log N) column sort + compile land on the dispatch thread
    #: inside the first range batch's deadline window; they still do
    #: after each compaction epoch, the same accepted cost class as the
    #: sharded base re-shard). Opt-in like ``prewarm_join_nbr``.
    prewarm_range_dims: tuple = ()
    # -- multi-chip serving (serve/sharded + ops/sharded_serving) ------------
    #: True routes serve buckets through the mesh-sharded executor;
    #: False pins single-chip; None = AUTO — sharded exactly when more
    #: than one device is visible AND the pinned base's device footprint
    #: exceeds ``hbm_budget_bytes`` (a snapshot one chip can hold serves
    #: faster without collective hops)
    sharded: Optional[bool] = None
    #: per-chip HBM budget the AUTO pick compares the base snapshot's
    #: estimated device bytes against; None disables the auto upgrade
    #: (only ``sharded=True`` shards then)
    hbm_budget_bytes: Optional[int] = None
    #: cap on mesh devices (None = every visible device)
    mesh_devices: Optional[int] = None


def _dummy_inc_csr():
    """The anchor-free range dispatch's stand-in incidence CSR: empty
    segments whatever index the (masked-off) probe clamps to."""
    import jax.numpy as jnp

    return jnp.zeros((2,), jnp.int32), jnp.zeros((8,), jnp.int32)


@dataclass
class LaunchedBatch:
    """An in-flight batch: the async device handles plus everything
    ``collect`` needs to turn them into per-ticket results."""

    batch: MicroBatch
    view: object = None                  # ops.incremental.PinnedView
    dev_out: object = None               # async (counts, first_r) handles
    lane_tickets: list = field(default_factory=list)   # [(lane, Ticket)]
    host_tickets: list = field(default_factory=list)   # exact-fallback path
    #: pattern batches: {handle: (target_set, type_handle)} of memtable
    #: candidates, captured AT LAUNCH (pin time ± µs) so collect-time
    #: corrections never read the live graph mid-ingest
    cand_records: dict = field(default_factory=dict)
    #: (t_launch, t_ready) in the tracer's clock once collect blocked —
    #: the batch's device-execution attribution (ServeConfig.device_timing)
    t_device: object = None
    _t_launch: object = None
    #: join batches: the ``join/planner.JoinPlan`` the lanes executed —
    #: collect needs its column order to permute tuples back into the
    #: request's variable order
    join_plan: object = None
    #: join batches dispatched under a SMALL pure-add dirty memtable:
    #: the sorted touched-atom list (new links + their targets, captured
    #: at launch) the per-lane collect correction enumerates against —
    #: None when the memtable was clean at pin (ROADMAP 2d)
    join_dirty: object = None
    #: join batches: real lanes this dispatch routed through the
    #: degree-split dense-frontier hub chain, and collect-side partial
    #: memtable corrections merged — batch-level EXPLAIN attribution
    #: (the per-request record reports the batch it rode)
    join_hub_lanes: int = 0
    join_partials: int = 0
    #: range batches: how many leading entries of the view's
    #: ``new_atoms`` the dispatched delta column covered — the collect
    #: residual (``new_atoms[covered:]``) the host correction owes
    range_covered: int = 0
    #: double-buffer slot of this dispatch (dispatch sequence mod 2) —
    #: rides the ``device`` span and the profiler annotation so device
    #: time is attributable per pipeline slot
    slot: int = -1


class DeviceExecutor:
    """The real executor: batched kernels over a pinned snapshot view.

    Requests the fixed-shape kernels cannot serve exactly — seeds/anchors
    beyond the base's id space (atoms newer than the last compaction),
    base rows wider than ``pattern_pad``, or a snapshot without ELL
    targets — fall back to exact host execution at collect time, counted
    in ``stats.host_fallbacks``."""

    #: which lane family a device-served result counts under (the
    #: sharded executor overrides with "sharded") — see stats.LANE_PATHS
    device_lane = "device"

    def __init__(self, graph, config: ServeConfig,
                 stats: Optional[ServeStats] = None):
        if graph is None:
            raise ValueError("DeviceExecutor needs a graph")
        self.graph = graph
        self.config = config
        self.stats = stats or ServeStats()
        self.tracer = config.tracer or global_tracer()
        self.faults = config.faults or global_faults()
        # serving implies ingest-concurrent reads: the incremental
        # (base, delta) pair IS the consistency mechanism
        self.mgr = graph.incremental or graph.enable_incremental()
        #: real device dispatches so far — slot = seq mod 2 names which
        #: half of the double buffer a batch rode (span + profiler attr)
        self._dispatch_seq = 0
        #: persistent AOT compile cache (ops/aot_cache): explicit dir from
        #: config, else $HG_AOT_CACHE, else off. content_key pins entries
        #: to this graph generation (quiet rebuild on mismatch).
        self.aot = self._open_aot_cache()
        self._aot_failed = False
        #: ((id space, edges), cap) — bfs_bucket_cap's memo per base shape
        self._bfs_cap: tuple = (None, None)
        #: (epoch, new_atoms scanned, touched set | "full") —
        #: _join_dirty_info's memo
        self._join_dirty_memo: tuple = (-1, 0, frozenset())

    def _open_aot_cache(self):
        import os

        from hypergraphdb_tpu.ops.aot_cache import (
            CACHE_ENV,
            AOTCache,
            default_cache,
        )

        if not self.config.aot_cache_dir and not os.environ.get(CACHE_ENV):
            # no cache configured — decide BEFORE the content fingerprint
            # (an O(E) CRC over the full CSR at benchmark scale)
            return None
        try:
            fp = self._content_key()
            if self.config.aot_cache_dir:
                return AOTCache(root=self.config.aot_cache_dir,
                                content_key=fp)
            return default_cache(content_key=fp)
        except Exception:  # pragma: no cover - unwritable dir etc.
            return None

    def _content_key(self) -> str:
        """Snapshot content fingerprint of the graph at executor birth —
        the ``snapshot_fingerprint`` half of the AOT cache key. The
        executables themselves depend only on shapes, so the fingerprint
        is a conservative pin: restarting over the same data warm-hits,
        restarting over different data rebuilds quietly."""
        from hypergraphdb_tpu.ops.ellbfs import snapshot_fingerprint

        try:
            return snapshot_fingerprint(self.mgr.base)
        except Exception:  # pragma: no cover - exotic base states
            return ""

    # -- AOT-compiled dispatch + prewarm -------------------------------------
    def _aot_dispatch(self, entry: str, jit_fn, args: tuple,
                      statics: dict):
        """The cached executable for one dispatch, or None → the caller
        falls back to plain jit. ONE failure policy for every entry: a
        cache malfunction logs once and disables the cache for this
        executor's lifetime (the cache accelerates, never gates), while
        EXECUTION errors of the returned executable propagate to the
        retry/breaker ladder like any device failure. Dispatch-time
        compiles do not persist (``persist=False``): only the prewarm
        (and ``bfs_bucket_cap``, its part that may run again per base
        shape) writes disk entries, so shape churn (resized delta buckets) cannot mint
        superseded multi-MB files on a serving thread."""
        if self.aot is None or self._aot_failed:
            return None
        try:
            return self.aot.get_or_compile(entry, jit_fn, args, statics,
                                           persist=False)
        except Exception:  # noqa: BLE001 - shapes the AOT path rejects
            import logging

            logging.getLogger("hypergraphdb_tpu.serve").warning(
                "aot dispatch failed for %s; falling back to jit", entry,
                exc_info=True,
            )
            self._aot_failed = True
            return None

    def _serve_bfs(self, view, seeds_dev, max_hops: int, top_r: int):
        """One BFS batch dispatch through the AOT cache when configured
        (first dispatch of a warmed bucket reuses the persisted
        executable instead of recompiling); plain jit otherwise."""
        from hypergraphdb_tpu.ops.serving import bfs_serve_batch

        args = (view.device, view.delta, seeds_dev)
        statics = {"max_hops": max_hops, "top_r": top_r}
        compiled = self._aot_dispatch("ops.serving.bfs_serve_batch",
                                      bfs_serve_batch, args, statics)
        if compiled is not None:
            return compiled(*args)
        return bfs_serve_batch(*args, **statics)

    def _serve_pattern(self, view, ell, anchors, type_vec):
        """One pattern batch dispatch through the AOT cache when
        configured (the prewarmed (bucket, P) executables — ROADMAP 4d:
        join/pattern traffic in a fresh process must not pay
        dispatch-thread compiles); plain jit otherwise. ``anchors`` and
        ``type_vec`` arrive as host numpy (the launch loop builds them);
        subclasses routing to other kernels reassemble from those."""
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops.serving import pattern_serve_batch

        args = (view.device, ell, jnp.asarray(anchors),
                jnp.asarray(type_vec))
        statics = {"pad_len": self.config.pattern_pad,
                   "top_r": self.config.top_r}
        compiled = self._aot_dispatch("ops.serving.pattern_serve_batch",
                                      pattern_serve_batch, args, statics)
        if compiled is not None:
            return compiled(*args)
        return pattern_serve_batch(*args, **statics)

    def _serve_range(self, view, bcol, dcol, bounds: dict):
        """One range batch dispatch (``ops/value_index.ordered_topk_batch``
        over the base + delta value columns), through the AOT cache when
        configured. ``bounds`` carries the per-lane host numpy arrays the
        launch loop assembled."""
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops.value_index import ordered_topk_batch
        from hypergraphdb_tpu.storage.value_index import (
            inc_csr_device,
            type_of_device,
        )

        if (bounds["anchor"] >= 0).any():
            inc_off, inc_links = inc_csr_device(view.base)
        else:
            # anchor-free batch (the steady shape): never materialize the
            # O(E) incidence CSR on device just to satisfy the kernel
            # signature — a tiny dummy CSR yields empty segments, and
            # every anchor_vec<0 lane masks the probe out anyway (a
            # second shape-keyed program, warmed as THE range program)
            inc_off, inc_links = _dummy_inc_csr()
        args = (
            bcol.rank_hi, bcol.rank_lo, bcol.rank2_hi, bcol.rank2_lo,
            bcol.gids, jnp.int32(bcol.n),
            dcol.rank_hi, dcol.rank_lo, dcol.rank2_hi, dcol.rank2_lo,
            dcol.gids, jnp.int32(dcol.n),
            type_of_device(view.base), inc_off, inc_links,
            jnp.asarray(bounds["lo_hi"]), jnp.asarray(bounds["lo_lo"]),
            jnp.asarray(bounds["lo_hi2"]), jnp.asarray(bounds["lo_lo2"]),
            jnp.asarray(bounds["lo_right"]),
            jnp.asarray(bounds["hi_hi"]), jnp.asarray(bounds["hi_lo"]),
            jnp.asarray(bounds["hi_hi2"]), jnp.asarray(bounds["hi_lo2"]),
            jnp.asarray(bounds["hi_right"]),
            jnp.asarray(bounds["type_vec"]), jnp.asarray(bounds["anchor"]),
            jnp.asarray(bounds["desc"]),
        )
        statics = {"win_pad": self._range_win_pad(),
                   "top_r": self.config.top_r}
        compiled = self._aot_dispatch("ops.value_index.ordered_topk_batch",
                                      ordered_topk_batch, args, statics)
        if compiled is not None:
            return compiled(*args)
        return ordered_topk_batch(*args, **statics)

    def _range_win_pad(self) -> int:
        """Candidate gather width per column: the smallest power-of-two
        bucket holding ``top_r`` (the kernel's prefix-dominance floor)."""
        from hypergraphdb_tpu.ops.setops import _bucket

        return _bucket(self.config.top_r, minimum=8)

    def _pattern_gate(self, view):
        """The pattern lanes' device-path gate: an opaque handle the
        dispatch needs (the base's ELL targets here), or None → every
        lane takes the exact host path."""
        from hypergraphdb_tpu.ops.setops import ell_targets

        return ell_targets(view.base)

    def _pin_view(self, kind: str, host_only: bool = False):
        """Pin the batch's consistent read unit — the ONE override point
        for executors that read a different device layout (the sharded
        executor pins mesh twins here)."""
        return self.mgr.pinned_view(
            self.config.max_lag_edges,
            sync_delta=(kind == "bfs") and not host_only,
        )

    def _execute_join(self, view, plan, consts, n_real: int):
        """One join batch through the single-chip lane executor
        (subclass override point — the sharded executor routes the same
        plan through the mesh's lane-sharded program)."""
        from hypergraphdb_tpu.ops.join import execute_join

        cfg = self.config
        # the view's epoch-cached trie encodings (built at plan time /
        # prewarm when join_factorized): present → serve through them,
        # absent (or disabled) → flat CSRs; never build on the dispatch
        # hot path
        fact = (view.factorized_join_rels()
                if cfg.join_factorized else None)
        return execute_join(view.base, plan, consts,
                            top_r=cfg.top_r, n_real=n_real,
                            row_cap=cfg.join_row_cap,
                            pad_cap=cfg.join_pad_cap,
                            hub_split=cfg.join_hub_split,
                            hub_threshold=cfg.join_hub_threshold,
                            factorized=(None if fact is not None
                                        else False))

    def prewarm(self, buckets, max_hops: Optional[int] = None) -> int:
        """Compile (or load from the AOT cache) the BFS serving
        executables for every bucket width against the current pinned
        view — the deploy-time half of the cold-start story. With NO
        cache configured there is nothing to load or persist, and only
        the BFS buckets are sized (``bfs_bucket_cap``: on a device with
        bounded memory that compiles their programs, which must not land
        inside the first live request's deadline window). Returns the
        number of executables served from cache."""
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops.serving import bfs_serve_batch

        if self.config.prewarm_join_nbr:
            # the join lane's co-incidence CSR: built + uploaded at
            # deploy time (in-budget snapshots only — over budget it
            # raises and the serve path declines to host anyway), plus
            # the factorized trie encoding when the v2 path will use it
            from hypergraphdb_tpu.ops.join import (
                factorized_relations_device,
                neighbor_csr_device,
            )

            try:
                neighbor_csr_device(self.mgr.base)
                if self.config.join_factorized:
                    factorized_relations_device(self.mgr.base)
            except Exception:  # noqa: BLE001 - never block startup
                import logging

                logging.getLogger("hypergraphdb_tpu.serve").warning(
                    "join prewarm failed; first join dispatch builds the "
                    "CSR cold", exc_info=True,
                )
        range_dims = tuple(self.config.prewarm_range_dims or ())
        if range_dims:
            # the range lane's sorted columns (+ per-bucket executables
            # below): first dispatch must not pay the O(N log N) column
            # sort on the dispatch thread
            from hypergraphdb_tpu.storage.value_index import (
                value_index_column,
            )

            for dim in range_dims:
                try:
                    value_index_column(self.mgr.base, int(dim))
                except Exception:  # noqa: BLE001 - never block startup
                    import logging

                    logging.getLogger("hypergraphdb_tpu.serve").warning(
                        "range-column prewarm failed for dim %d; first "
                        "range dispatch sorts it cold", int(dim),
                        exc_info=True,
                    )
        # asked here whatever else is warmed: the first BFS flush would
        # otherwise ask it on the dispatch thread (Batcher.key_cap)
        bfs_cap = self.bfs_bucket_cap()
        if self.aot is None:
            # nothing to warm: no cache to load — skip the pinned_view so
            # cache-less construction stays free
            return 0

        # the hops SET to warm: a deployment serving more than the default
        # (ServeConfig.prewarm_hops) would otherwise compile the missing
        # statics synchronously on the dispatch thread in every fresh
        # process — dispatch-time compiles never persist
        hops_list = ((int(max_hops),) if max_hops is not None
                     else tuple(self.config.prewarm_hops or ())
                     or (self.config.default_max_hops,))
        view = self.mgr.pinned_view(self.config.max_lag_edges,
                                    sync_delta=True)
        n = view.base.num_atoms
        top_r = min(self.config.top_r + 1, n + 1)
        # the pattern lane's ELL targets + executables (ROADMAP 4d):
        # without this, join/pattern traffic in a fresh process pays its
        # (bucket, P) compiles on the dispatch thread at first flush
        arities = tuple(self.config.prewarm_pattern_arities or ())
        ell = None
        if arities:
            from hypergraphdb_tpu.ops.setops import ell_targets

            ell = ell_targets(view.base)
        if range_dims:
            from hypergraphdb_tpu.storage.value_index import (
                build_delta_column,
                type_of_device,
            )

            # one empty delta column serves every warmed (dim, bucket):
            # the executable depends on shapes, not contents
            empty_delta = build_delta_column(self.graph, [], 0, epoch=-1)
        warm = 0
        for b in buckets:
            seeds = jnp.full((int(b),), n, dtype=jnp.int32)
            # a bucket no BFS batch will ever form at warms no BFS program
            bfs_fits = bfs_cap is None or int(b) <= bfs_cap
            if ell is not None:
                from hypergraphdb_tpu.ops.serving import (
                    NO_TYPE,
                    pattern_serve_batch,
                )

                tvec = jnp.full((int(b),), NO_TYPE, dtype=jnp.int32)
                for P in arities:
                    anchors = jnp.full((int(b), int(P)), n,
                                       dtype=jnp.int32)
                    try:
                        warm += self.aot.warm(
                            "ops.serving.pattern_serve_batch",
                            pattern_serve_batch,
                            (view.device, ell, anchors, tvec),
                            {"pad_len": self.config.pattern_pad,
                             "top_r": self.config.top_r},
                        )
                    except Exception:  # noqa: BLE001 - never block startup
                        continue
            for dim in range_dims:
                from hypergraphdb_tpu.ops.value_index import (
                    ordered_topk_batch,
                )
                from hypergraphdb_tpu.storage.value_index import (
                    value_index_column,
                )

                try:
                    bcol = value_index_column(view.base, int(dim))
                    # warm the ANCHOR-FREE program — the steady shape
                    # (anchored batches carry the real incidence CSR and
                    # compile on first use, like overlay BFS batches)
                    inc_off, inc_links = _dummy_inc_csr()
                    zu = jnp.zeros((int(b),), jnp.uint32)
                    zb = jnp.zeros((int(b),), bool)
                    neg = jnp.full((int(b),), -1, jnp.int32)
                    warm += self.aot.warm(
                        "ops.value_index.ordered_topk_batch",
                        ordered_topk_batch,
                        (bcol.rank_hi, bcol.rank_lo,
                         bcol.rank2_hi, bcol.rank2_lo, bcol.gids,
                         jnp.int32(bcol.n),
                         empty_delta.rank_hi, empty_delta.rank_lo,
                         empty_delta.rank2_hi, empty_delta.rank2_lo,
                         empty_delta.gids, jnp.int32(0),
                         type_of_device(view.base), inc_off, inc_links,
                         zu, zu, zu, zu, zb, zu, zu, zu, zu, zb,
                         neg, neg, zb),
                        {"win_pad": self._range_win_pad(),
                         "top_r": self.config.top_r},
                    )
                except Exception:  # noqa: BLE001 - never block startup
                    continue
            for hops in (hops_list if bfs_fits else ()):
                try:
                    warm += self.aot.warm(
                        "ops.serving.bfs_serve_batch", bfs_serve_batch,
                        (view.device, view.delta, seeds),
                        {"max_hops": hops, "top_r": top_r},
                    )
                except Exception:  # noqa: BLE001 - never block startup
                    import logging

                    logging.getLogger("hypergraphdb_tpu.serve").warning(
                        "aot warm failed (bfs_serve_batch, bucket=%d, "
                        "hops=%d); first dispatch compiles cold",
                        int(b), hops, exc_info=True,
                    )
        return warm

    # -- which BFS buckets fit the chip ---------------------------------------
    def _device_memory_is_bounded(self) -> bool:
        """Does the device that holds the snapshot — where
        ``DeviceSnapshot.from_host`` uploads: jax's default device — report
        an allocator limit? (CPU reports none: every program fits.)"""
        from hypergraphdb_tpu.ops.aot_cache import execution_devices

        stats = execution_devices(())[0].memory_stats() or {}
        return stats.get("bytes_limit") is not None

    def _bfs_program(self, view, bucket: int):
        """The compiled dense BFS program of one bucket, at the default
        hop count (hops are a loop: they change no buffer) — through the
        AOT cache where one is configured, persisted (this is the
        bucket's prewarm); jit keeps the executable for the dispatch
        either way. Raises what the compiler raises."""
        import jax.numpy as jnp

        from hypergraphdb_tpu.ops.serving import bfs_serve_batch

        n = view.base.num_atoms
        args = (view.device, view.delta,
                jnp.full((int(bucket),), n, dtype=jnp.int32))
        statics = {"max_hops": self.config.default_max_hops,
                   "top_r": min(self.config.top_r + 1, n + 1)}
        if self.aot is not None and not self._aot_failed:
            return self.aot.get_or_compile("ops.serving.bfs_serve_batch",
                                           bfs_serve_batch, args, statics)
        return bfs_serve_batch.lower(*args, **statics).compile()

    def bfs_bucket_cap(self) -> Optional[int]:
        """The widest configured bucket a BFS batch may take, or None (no
        limit). The dense served BFS holds (K, id space) and (K, edges)
        arrays, so past some width its program does not fit the chip, and
        the chip's compiler refuses it (RESOURCE_EXHAUSTED, "Ran out of
        memory in memory space hbm"). Left to the first wide batch, that
        refusal fails every caller in it and trips the key's breaker. So
        on a device with bounded memory the executor compiles the buckets'
        programs narrowest first — where the dispatch would compile them
        anyway — and stops at the first the compiler refuses: a bucket
        past the cap is neither prewarmed nor formed (``Batcher.key_cap``)
        — a burst rides more batches of the widest bucket that fits — and
        the refusal is logged, once per base shape."""
        base = self.mgr.base
        shape = (int(base.num_atoms), int(len(base.inc_links)))
        if self._bfs_cap[0] == shape:
            return self._bfs_cap[1]
        cap = None
        if self._device_memory_is_bounded():
            import logging

            log = logging.getLogger("hypergraphdb_tpu.serve")
            view = self._pin_view("bfs")
            buckets = sorted(int(b) for b in self.config.buckets)
            for b in buckets:
                try:
                    self._bfs_program(view, b)
                except Exception as e:  # noqa: BLE001 - sorted just below
                    if "RESOURCE_EXHAUSTED" not in str(e):
                        # not a verdict on memory: leave every bucket open
                        # and let the dispatch meet the fault, loudly
                        log.warning("could not compile the BFS program of "
                                    "bucket %d to size BFS batches", b,
                                    exc_info=True)
                        cap = None
                        break
                    log.log(
                        logging.WARNING if cap else logging.ERROR,
                        "BFS bucket %d declined: the compiler refuses its "
                        "program at id space %d and %d incidence entries "
                        "(%s); BFS batches form at most %d wide%s",
                        b, *shape, " ".join(str(e).split())[:240],
                        cap or b, "" if cap else " — and will fail on the "
                        "device and degrade to the host",
                    )
                    cap = cap or b
                    break
                cap = b
            if cap == buckets[-1]:
                cap = None
        self._bfs_cap = (shape, cap)
        return cap

    def max_batch(self, key: tuple) -> Optional[int]:
        """``Batcher.key_cap``: how many tickets one flush of ``key`` may
        take (None = the largest bucket)."""
        return self.bfs_bucket_cap() if key[0] == "bfs" else None

    def _dispatch_cm(self, kind: str, bucket: int, statics: int):
        """The per-dispatch profiler annotation, active only when device
        timing is on or an ``obs.profile`` session is running — the
        common un-profiled path pays two attribute reads and re-enters
        the shared null context (no allocation)."""
        if self.config.device_timing or profiling():
            slot = self._dispatch_seq % 2
            return annotate(
                f"hg.serve.{kind}[K={bucket},s={statics},slot={slot}]"
            )
        return _NULL_CM

    # -- launch (async: never blocks on the device) --------------------------
    def launch(self, batch: MicroBatch) -> LaunchedBatch:
        import jax.numpy as jnp

        kind = batch.key[0]
        if getattr(batch, "force_host", False):
            # breaker-degraded mode: the WHOLE batch takes the exact host
            # path under the pinned epoch — no device work, no delta sync
            view = self._pin_view(kind, host_only=True)
            out = LaunchedBatch(batch=batch, view=view)
            out.host_tickets = list(batch.tickets)
            return out
        if self.faults.enabled:  # the ONE gate read on the disabled path
            # models the DEVICE dispatch failing — deliberately after the
            # force_host branch, so breaker-degraded batches stay immune
            self.faults.check("serve.launch", kind=kind)
        # pattern batches read base + HOST corrections only — don't pay a
        # device-delta upload on their hot path
        view = self._pin_view(kind)
        out = LaunchedBatch(batch=batch, view=view)
        if kind == "bfs":
            max_hops = batch.key[1]
            n = view.base.num_atoms
            seeds = np.full(batch.bucket, n, dtype=np.int32)  # pad → dummy
            lane = 0
            for t in batch.tickets:
                if t.request.seed >= n or t.request.seed < 0:
                    out.host_tickets.append(t)
                    continue
                seeds[lane] = t.request.seed
                out.lane_tickets.append((lane, t))
                lane += 1
            if out.lane_tickets:
                # one slot beyond top_r: an include_seed=False request
                # drops its seed from the window, and the spare slot keeps
                # the remaining prefix full-width (see _bfs_result)
                top_r = min(self.config.top_r + 1, n + 1)
                with self._dispatch_cm("bfs", batch.bucket, max_hops):
                    out.dev_out = self._serve_bfs(
                        view, jnp.asarray(seeds), max_hops, top_r,
                    )
        elif kind == "pattern":
            from hypergraphdb_tpu.ops.serving import NO_TYPE

            P = batch.key[1]
            n = view.base.num_atoms
            ell = self._pattern_gate(view)
            off = view.base.inc_offsets
            anchors = np.full((batch.bucket, P), n, dtype=np.int32)
            type_vec = np.full(batch.bucket, NO_TYPE, dtype=np.int32)
            lane = 0
            for t in batch.tickets:
                req = t.request
                a = np.asarray(req.anchors, dtype=np.int64)
                if ell is None or a.min() < 0 or a.max() >= n:
                    out.host_tickets.append(t)
                    continue
                lens = off[a + 1].astype(np.int64) - off[a]
                order = np.argsort(lens, kind="stable")
                if lens[order[0]] > self.config.pattern_pad:
                    out.host_tickets.append(t)  # base row over budget
                    continue
                anchors[lane] = a[order]
                if req.type_handle is not None:
                    type_vec[lane] = int(req.type_handle)
                out.lane_tickets.append((lane, t))
                lane += 1
            if out.lane_tickets:
                out.cand_records = self._capture_candidates(view)
                with self._dispatch_cm("pattern", batch.bucket, P):
                    out.dev_out = self._serve_pattern(
                        view, ell, anchors, type_vec,
                    )
        elif kind == "range":
            from hypergraphdb_tpu.storage.value_index import (
                FIXED_WIDTH_KINDS,
                value_index_column,
            )

            dim = batch.key[1]
            n = view.base.num_atoms
            K = batch.bucket
            U32 = np.uint32(0xFFFFFFFF)
            bounds = {
                # pad-lane default: lo and hi both leftmost of rank 0 —
                # an empty window, well-defined garbage by construction
                "lo_hi": np.zeros(K, np.uint32),
                "lo_lo": np.zeros(K, np.uint32),
                "lo_hi2": np.zeros(K, np.uint32),
                "lo_lo2": np.zeros(K, np.uint32),
                "lo_right": np.zeros(K, bool),
                "hi_hi": np.zeros(K, np.uint32),
                "hi_lo": np.zeros(K, np.uint32),
                "hi_hi2": np.zeros(K, np.uint32),
                "hi_lo2": np.zeros(K, np.uint32),
                "hi_right": np.zeros(K, bool),
                "type_vec": np.full(K, -1, np.int32),
                "anchor": np.full(K, -1, np.int32),
                "desc": np.zeros(K, bool),
            }
            # columns build lazily: a variable-width batch must consult
            # their device_exact verdicts BEFORE routing lanes, but an
            # all-host batch (every bound ambiguous) must not pay the
            # build/upload at all
            cols = []

            def _cols():
                if not cols:
                    cols.append(value_index_column(view.base, dim))
                    cols.append(self.mgr.value_delta(
                        view, dim, self.config.max_lag_edges))
                return cols

            lane = 0
            for t in batch.tickets:
                req = t.request
                if (not req.exact
                        or (req.limit is not None
                            and req.limit > self.config.top_r)
                        or (req.anchor is not None
                            and (req.anchor < 0 or req.anchor >= n))
                        or (dim not in FIXED_WIDTH_KINDS
                            and not all(c.device_exact for c in _cols()))):
                    # ambiguous variable-width bounds (ties past the
                    # 128-bit rank pair), columns holding any ambiguous
                    # key, over-window limits, and anchors outside the
                    # base (a memtable anchor has no base incidence row
                    # to probe) all serve exactly on host. Anchored lanes
                    # under fresh ingest stay on device: the base-row
                    # probe can only mask fresh links OUT (never falsely
                    # in), and the collect re-offers the full memtable
                    # candidate set through the live-incidence host
                    # probe.
                    out.host_tickets.append(t)
                    continue
                lo, hi = req.lo_rank, req.hi_rank
                if lo is not None:
                    bounds["lo_hi"][lane] = np.uint32(lo >> 32)
                    bounds["lo_lo"][lane] = np.uint32(lo & 0xFFFFFFFF)
                    bounds["lo_hi2"][lane] = np.uint32(req.lo_rank2 >> 32)
                    bounds["lo_lo2"][lane] = np.uint32(
                        req.lo_rank2 & 0xFFFFFFFF)
                    bounds["lo_right"][lane] = req.lo_op == "gt"
                if hi is not None:
                    bounds["hi_hi"][lane] = np.uint32(hi >> 32)
                    bounds["hi_lo"][lane] = np.uint32(hi & 0xFFFFFFFF)
                    bounds["hi_hi2"][lane] = np.uint32(req.hi_rank2 >> 32)
                    bounds["hi_lo2"][lane] = np.uint32(
                        req.hi_rank2 & 0xFFFFFFFF)
                    bounds["hi_right"][lane] = req.hi_op == "lte"
                else:
                    bounds["hi_hi"][lane] = U32
                    bounds["hi_lo"][lane] = U32
                    bounds["hi_hi2"][lane] = U32
                    bounds["hi_lo2"][lane] = U32
                    bounds["hi_right"][lane] = True
                if req.type_handle is not None:
                    bounds["type_vec"][lane] = int(req.type_handle)
                if req.anchor is not None:
                    bounds["anchor"][lane] = int(req.anchor)
                bounds["desc"][lane] = bool(req.desc)
                out.lane_tickets.append((lane, t))
                lane += 1
            if out.lane_tickets:
                bcol, dcol = _cols()
                out.range_covered = dcol.covered
                self.stats.record_range_dispatch()
                with self._dispatch_cm("range", batch.bucket, dim):
                    out.dev_out = self._serve_range(view, bcol, dcol,
                                                    bounds)
        elif kind == "join":
            sig = batch.key[1]
            n = view.base.num_atoms
            # a memtable LINK can mint bindings anywhere in the tuple
            # space — not correctable against a compact device prefix.
            # Exact-at-collect discipline, join edition: while the dirty
            # set stays SMALL and pure-add, the batch still dispatches
            # on device and collect merges the per-lane correction
            # (tuples touching the dirty atoms — ROADMAP 2d); tombstones,
            # revalues, or a dirty set past ``join_dirty_max`` take the
            # whole batch to the exact host path as before (bounded by
            # the next compaction).
            dirty = self._join_dirty_info(view)
            plan = (None if dirty == "full"
                    else self._join_plan(sig, batch.tickets[0].request,
                                         view.base))
            if plan is None:
                out.host_tickets = list(batch.tickets)
            else:
                consts = np.zeros((batch.bucket, sig.n_consts),
                                  dtype=np.int32)
                lane = 0
                for t in batch.tickets:
                    cv = np.asarray(t.request.consts, dtype=np.int64)
                    if len(cv) and (cv.min() < 0 or cv.max() >= n):
                        out.host_tickets.append(t)  # beyond the base
                        continue
                    consts[lane] = cv
                    out.lane_tickets.append((lane, t))
                    lane += 1
                if out.lane_tickets:
                    out.join_plan = plan
                    out.join_dirty = dirty
                    with self._dispatch_cm("join", batch.bucket,
                                           len(plan.steps)):
                        with self.tracer.span("join.execute",
                                              sig=str(sig.atoms)):
                            ex = self._execute_join(view, plan, consts,
                                                    n_real=lane)
                    if ex.hub_lanes:
                        self.stats.record_join_hub_dispatch(ex.hub_lanes)
                    out.join_hub_lanes = int(ex.hub_lanes)
                    out.dev_out = (ex.counts, ex.trunc, ex.tuples)
        else:  # pragma: no cover - batch keys come from our own requests
            raise Unservable(f"unknown batch kind {kind!r}")
        if out.dev_out is not None:
            out.slot = self._dispatch_seq % 2
            self._dispatch_seq += 1
            self.stats.record_device_dispatch()
            if self.config.device_timing and self.tracer.enabled:
                out._t_launch = self.tracer.clock()
        return out

    def _capture_candidates(self, view) -> dict:
        """Memtable candidates' (targets, type), read ONCE per batch right
        after the view is pinned: collect-time corrections then evaluate
        pin-time state, not whatever the live graph mutated into while the
        device ran. A candidate whose record vanished inside the µs-wide
        pin→capture window is treated as dead — equivalent to having
        pinned a moment later. Node candidates (no targets) can never
        match a pattern and drop out here too."""
        g = self.graph
        recs = {}
        for h in (set(view.new_atoms) | view.revalued) - view.dead:
            try:
                ts = {int(t) for t in g.get_targets(h)}
                th = int(g.get_type_handle_of(h))
            except Exception:
                continue
            recs[h] = (ts, th)
        return recs

    # -- collect (sync: downloads compact results, corrects, resolves) -------
    def collect(self, launched: LaunchedBatch) -> list:
        from hypergraphdb_tpu.ops.setops import SENTINEL

        out = []
        view = launched.view
        if launched.dev_out is not None:
            if self.faults.enabled:
                # models the device RESULT download failing — host-only
                # batches (breaker-degraded / all-fallback) stay immune
                self.faults.check("serve.collect",
                                  kind=launched.batch.key[0])
            if launched._t_launch is not None:
                # opt-in device attribution: block on the async handles and
                # record the launch→ready wall delta for the batch's span
                from hypergraphdb_tpu.obs.device import block_timed

                _, t_ready = block_timed(launched.dev_out,
                                         self.tracer.clock)
                launched.t_device = (launched._t_launch, t_ready)
            kind = launched.batch.key[0]
            if kind == "join":
                return self._collect_join(launched)
            if kind == "range":
                return self._collect_range(launched)
            counts, first_r = (np.asarray(x) for x in launched.dev_out)
            if kind == "pattern":
                # batch-invariant memtable views, hoisted off the
                # per-lane path (a 1024-lane batch over a deep memtable
                # would otherwise rebuild these sets 1024×)
                drop = view.dead | view.revalued
                drop_arr = (np.fromiter(drop, dtype=np.int64)
                            if drop else np.empty(0, dtype=np.int64))
            for lane, ticket in launched.lane_tickets:
                row = first_r[lane]
                matches = row[row != SENTINEL].astype(np.int64)
                count = int(counts[lane])
                if kind == "bfs":
                    res = self._bfs_result(ticket.request, count, matches,
                                           view)
                else:
                    res = self._pattern_result(ticket.request, count,
                                               matches, view, drop_arr,
                                               launched.cand_records)
                out.append((ticket, res))
        out.extend(self._serve_host(launched.host_tickets, view.epoch))
        return out

    def _collect_join(self, launched: LaunchedBatch) -> list:
        """Join-batch result assembly: download the compact per-lane
        windows, permute tuple columns from the plan's elimination order
        back to the request's variable order, and re-serve any
        truncation-flagged lane exactly on host (a flagged count is a
        LOWER bound — honest, but not what a caller asked for).

        Batches dispatched under a small pure-add dirty memtable
        (``launched.join_dirty``) merge the per-lane correction here:
        the host enumerates exactly the tuples touching the dirty atoms
        (``join/host.host_join_touching`` — sound because a new link
        only ever mints tuples containing itself or its targets) and
        unions them into the device answer. Lanes whose device window is
        a PREFIX (count beyond top_r) re-serve on host instead — a
        prefix cannot absorb corrections, the pattern lane's rule."""
        view = launched.view
        sig = launched.batch.key[1]
        plan = launched.join_plan
        dirty = launched.join_dirty
        counts, trunc, tuples = (np.asarray(x) for x in launched.dev_out)
        perm = [plan.order.index(v) for v in sig.vars]
        top_r = self.config.top_r
        out = []
        for lane, ticket in launched.lane_tickets:
            try:
                rows = tuples[lane]
                rows = rows[rows[:, 0] >= 0][:, perm].astype(np.int64)
                count = int(counts[lane])
                if trunc[lane] or (dirty and count > len(rows)):
                    self.stats.record_host_fallback()
                    out.append((ticket,
                                self._host_join(ticket.request,
                                                view.epoch)))
                    continue
                if dirty:
                    from hypergraphdb_tpu.join.host import (
                        host_join_touching,
                    )

                    try:
                        extra = host_join_touching(
                            self.graph, sig.bind(ticket.request.consts),
                            dirty,
                        )
                    except Exception:  # noqa: BLE001 - odd shape → exact
                        self.stats.record_host_fallback()
                        out.append((ticket,
                                    self._host_join(ticket.request,
                                                    view.epoch)))
                        continue
                    if extra:
                        merged = sorted(
                            {tuple(int(x) for x in r) for r in rows}
                            | set(extra)
                        )
                        rows = np.asarray(merged, dtype=np.int64)
                        rows = rows.reshape(-1, len(sig.vars))[:top_r]
                        count = len(merged)
                    self.stats.record_join_partial_correction()
                    launched.join_partials += 1
                out.append((ticket, JoinResult(
                    "join", count, rows, sig.vars,
                    count > len(rows), view.epoch,
                )))
            except Exception as e:  # surface, don't kill the batch
                out.append((ticket, e))
        out.extend(self._serve_host(launched.host_tickets, view.epoch))
        return out

    def _collect_range(self, launched: LaunchedBatch) -> list:
        """Range-batch result assembly: download the compact per-lane
        windows and apply the LSM memtable correction — drop
        dead/revalued gids, host-evaluate the residual memtable
        candidates (atoms past the delta column's coverage, plus every
        revalued atom), merge in VALUE order. Prefix lanes (count beyond
        the compact window) with a non-empty correction set re-serve
        exactly on host — a prefix cannot absorb corrections, the
        pattern lane's rule."""
        from hypergraphdb_tpu.ops.setops import SENTINEL

        view = launched.view
        counts_f, first_r, covered, total = (
            np.asarray(x) for x in launched.dev_out
        )
        residual = view.new_atoms[launched.range_covered:]
        drop = view.dead | view.revalued
        # batch-invariant drop array, hoisted off the per-lane path (the
        # pattern collect's discipline: a 1024-lane batch over a deep
        # memtable must not rebuild this conversion 1024×)
        drop_arr = (np.fromiter(drop, dtype=np.int64)
                    if drop else np.empty(0, dtype=np.int64))
        cands = (set(residual) | view.revalued) - view.dead
        # filtered lanes need the FULL memtable candidate set: the
        # kernel's type filter reads the BASE type_of column (a
        # delta-column gid is -1 there) and the anchor filter probes the
        # BASE incidence row (a memtable link incident to the anchor is
        # not in it) — such atoms are masked out on device (never
        # falsely in), so the host merge must re-offer every fresh atom
        # through the live-graph predicate, not just the uncovered
        # residual. Built only when some lane actually carries a filter
        # (an unfiltered range-heavy batch must not pay O(|memtable|)
        # per collect).
        cands_full = (
            (set(view.new_atoms) | view.revalued) - view.dead
            if any(t.request.type_handle is not None
                   or t.request.anchor is not None
                   for _, t in launched.lane_tickets)
            else cands
        )
        out = []
        for lane, ticket in launched.lane_tickets:
            try:
                req = ticket.request
                out.append((ticket, self._range_result(
                    req, int(counts_f[lane]),
                    first_r[lane][first_r[lane] != SENTINEL],
                    bool(covered[lane]), int(total[lane]), view,
                    drop_arr,
                    cands_full
                    if (req.type_handle is not None
                        or req.anchor is not None) else cands,
                )))
            except Exception as e:  # surface, don't kill the batch
                out.append((ticket, e))
        out.extend(self._serve_host(launched.host_tickets, view.epoch))
        return out

    def _range_result(self, req: RangeRequest, count_f: int,
                      matches: np.ndarray, covered: bool, total: int,
                      view, drop_arr: np.ndarray, cands: set):
        filtered = req.type_handle is not None or req.anchor is not None
        if filtered and not covered:
            # the window outran the gather pad under a filter: neither
            # count nor prefix is reconstructible on device
            self.stats.record_host_fallback()
            return self._host_range(req, view.epoch)
        count = count_f if filtered else total
        top_r = self.config.top_r
        upto = min(req.limit if req.limit is not None else top_r, top_r)
        if count <= len(matches):
            # the complete filtered set is in hand: corrections merge
            # exactly (the LSM read-merge, value edition)
            matches = matches.astype(np.int64)
            if len(drop_arr) and len(matches):
                matches = matches[~np.isin(matches, drop_arr)]
            keys = self._range_keys(req) if cands else None
            fresh = [h for h in cands
                     if self._range_matches_host(req, h, keys)]
            if fresh:
                matches = self._range_order(
                    req, np.union1d(matches,
                                    np.asarray(fresh, dtype=np.int64))
                )
            count = len(matches)
            matches = matches[:upto]
            return ServeResult("range", count, matches,
                               count > len(matches), view.epoch)
        # prefix shape: count exact, matches an honest value-ordered
        # prefix — but only while the memtable is quiet for this view
        if len(drop_arr) or cands:
            self.stats.record_host_fallback()
            return self._host_range(req, view.epoch)
        return ServeResult("range", count,
                           matches[:upto].astype(np.int64),
                           count > upto, view.epoch)

    # -- range lane helpers ---------------------------------------------------
    def _range_keys(self, req: RangeRequest) -> tuple:
        """(lo_key, hi_key) order-preserving byte bounds of one request —
        the host comparison unit (exact for every kind, unlike the
        64-bit ranks). None = open."""
        ts = self.graph.typesystem

        def key_of(v):
            if v is None:
                return None
            vt = ts.infer(v)
            if vt is None:
                raise Unservable(f"value {v!r} has no registered type")
            return vt.to_key(v)

        return key_of(req.values[0]), key_of(req.values[1])

    def _range_matches_host(self, req: RangeRequest, h: int,
                            keys: Optional[tuple] = None) -> bool:
        """Does live atom ``h`` satisfy the FULL request predicate —
        kind, bounds, type, anchor? The memtable-correction evaluator.
        ``keys`` lets per-candidate loops pass the request's bound keys
        computed ONCE (``_range_keys`` runs the typesystem) instead of
        re-deriving them per atom."""
        from hypergraphdb_tpu.storage.value_index import value_key_of

        g = self.graph
        if not g.contains(h):
            return False
        key = value_key_of(g, h)
        if key is None or key[0] != req.dim:
            return False
        lo_key, hi_key = keys if keys is not None else self._range_keys(req)
        payload = key[1:]
        if lo_key is not None:
            lo = lo_key[1:]
            if payload < lo or (payload == lo and req.lo_op == "gt"):
                return False
        if hi_key is not None:
            hi = hi_key[1:]
            if payload > hi or (payload == hi and req.hi_op == "lt"):
                return False
        if req.type_handle is not None and int(
            g.get_type_handle_of(h)
        ) != int(req.type_handle):
            return False
        if req.anchor is not None:
            try:
                if int(req.anchor) not in {
                    int(t) for t in g.get_targets(h)
                }:
                    return False
            except Exception:  # noqa: BLE001 - node candidate: no targets
                return False
        return True

    def _range_order(self, req: RangeRequest, gids: np.ndarray
                     ) -> np.ndarray:
        """Sort gids into the request's value order via their live keys
        (bounded work: only complete—≤ top_r—windows are ever merged)."""
        from hypergraphdb_tpu.storage.value_index import value_key_of

        g = self.graph
        keyed = []
        for h in gids.tolist():
            key = value_key_of(g, int(h))
            if key is not None:
                keyed.append((key[1:], int(h)))
        keyed.sort(key=lambda kv: (kv[0], kv[1]))
        if req.desc:
            # descending by value, gid-ascending within ties (the
            # kernel's complemented-rank order)
            keyed.sort(key=lambda kv: kv[1])
            keyed.sort(key=lambda kv: kv[0], reverse=True)
        return np.asarray([h for _, h in keyed], dtype=np.int64)

    def _host_range(self, req: RangeRequest, epoch: int) -> ServeResult:
        """Exact host oracle: walk the by-value system index in key
        order (the scan the device lane replaces), filter, and shape the
        result under the same order/limit/truncation contract."""
        from hypergraphdb_tpu.core.graph import IDX_BY_VALUE

        g = self.graph
        idx = g.store.get_index(IDX_BY_VALUE)
        kb = bytes([req.dim])
        lo_key, hi_key = self._range_keys(req)
        start = lo_key if lo_key is not None else kb
        matched: list[int] = []
        for key, handles in idx.bulk_items(lo=start):
            if key[:1] != kb:
                break  # past the dimension's key family
            if lo_key is not None and key == lo_key and req.lo_op == "gt":
                continue
            if hi_key is not None:
                if key > hi_key or (key == hi_key and req.hi_op == "lt"):
                    break
            for h in np.asarray(handles).tolist():
                h = int(h)
                if req.type_handle is not None and (
                    not g.contains(h)
                    or int(g.get_type_handle_of(h)) != int(req.type_handle)
                ):
                    continue
                if req.anchor is not None:
                    try:
                        if int(req.anchor) not in {
                            int(t) for t in g.get_targets(h)
                        }:
                            continue
                    except Exception:  # noqa: BLE001 - node candidate
                        continue
                matched.append(h)
        arr = self._range_order(req, np.asarray(matched, dtype=np.int64))
        top_r = self.config.top_r
        upto = min(req.limit if req.limit is not None else top_r, top_r)
        return ServeResult("range", len(arr), arr[:upto],
                           len(arr) > upto, epoch, served_by="host")

    def collect_host(self, launched: LaunchedBatch) -> list:
        """Exact host re-serve of the WHOLE batch — the collect-failure
        recovery path: the device handles are poisoned but the pinned
        epoch is still the right consistency label, so every ticket is
        answered by the exact host executors instead of erroring."""
        view = launched.view
        return self._serve_host(launched.batch.tickets,
                                0 if view is None else view.epoch)

    def _serve_host(self, tickets, epoch: int) -> list:
        """The ONE exact host-serving loop (fallback lanes, degraded
        batches, collect recovery): per-ticket dispatch with per-ticket
        exception capture — one failing request surfaces, never kills
        its batch."""
        out = []
        with _thread_cm(self.config, "hg.serve.host_reserve"):
            for ticket in tickets:
                self.stats.record_host_fallback()
                try:
                    kind = ticket.request.kind
                    if kind == "bfs":
                        out.append((ticket, self._host_bfs(ticket.request,
                                                           epoch)))
                    elif kind == "join":
                        out.append((ticket, self._host_join(ticket.request,
                                                            epoch)))
                    elif kind == "range":
                        out.append((ticket, self._host_range(ticket.request,
                                                             epoch)))
                    else:
                        out.append((ticket, self._host_pattern(
                            ticket.request, epoch)))
                except Exception as e:  # surface, don't kill the batch
                    out.append((ticket, e))
        return out

    # -- per-request result assembly -----------------------------------------
    def _bfs_result(self, req: BFSRequest, count: int,
                    matches: np.ndarray, view) -> ServeResult:
        if not req.include_seed and count > 0:
            # a live seed is always in its own visited set
            count -= 1
            matches = matches[matches != req.seed]
        matches = matches[: self.config.top_r]  # trim the spare slot
        truncated = count > len(matches)
        return ServeResult("bfs", count, matches, truncated, view.epoch)

    def _pattern_result(self, req: PatternRequest, count: int,
                        matches: np.ndarray, view, drop_arr: np.ndarray,
                        cand_records: dict) -> ServeResult:
        truncated = count > len(matches)
        if truncated and (len(drop_arr) or cand_records):
            # corrections against a prefix we cannot see past are not
            # reconstructible (a tombstone beyond the window would
            # overcount, a fresh link would punch a hole in the prefix) —
            # serve this rare shape exactly on host instead of bending
            # the count/prefix contract
            self.stats.record_host_fallback()
            return self._host_pattern(req, view.epoch)
        if truncated:
            # memtable quiet (checked above): device numbers are exact
            return ServeResult("pattern", count, matches, True, view.epoch)
        # LSM read-merge over the COMPLETE result set: drop links
        # tombstoned/revalued since the pack, evaluate the pattern over
        # the captured memtable records (pin-time state — never the live
        # graph) — exact at any delta lag.
        if len(drop_arr) and len(matches):
            matches = matches[~np.isin(matches, drop_arr)]
        fresh = [
            h for h, (ts, th) in cand_records.items()
            if all(a in ts for a in req.anchors)
            and (req.type_handle is None or th == int(req.type_handle))
        ]
        if fresh:
            matches = np.union1d(matches,
                                 np.asarray(fresh, dtype=np.int64))
        count = len(matches)
        top_r = self.config.top_r
        if count > top_r:
            # the merge pushed the full set past the compact window:
            # same shape contract as every other truncated result
            return ServeResult("pattern", count, matches[:top_r], True,
                               view.epoch)
        return ServeResult("pattern", count, matches, False, view.epoch)

    # -- join lane helpers ----------------------------------------------------
    def _join_dirty_info(self, view):
        """What the memtable holds that a join answer could see.
        Returns ``None`` — clean, device lane open with no correction;
        a sorted touched-atom list — small pure-ADD dirty set (every new
        link plus its targets, ≤ ``join_dirty_max`` atoms): the batch
        still dispatches on device and collect merges the per-lane
        correction (ROADMAP 2d); ``"full"`` — tombstones/revalues (a
        vanished witness is not correctable against a compact window)
        or a dirty set past the bound: the whole batch takes the exact
        host path. Fresh NODES alone never dirty anything (nothing in
        the base points at them).

        Memoized per epoch with incremental suffix scans — ``new_atoms``
        only grows within an epoch and the touched set only accumulates
        (the ``"full"`` verdict is sticky), so a bulk ingest costs each
        batch only the atoms that arrived since the last one, not an
        O(memtable) store walk on the dispatch thread."""
        if view.dead or view.revalued:
            return "full"
        epoch, n_seen, dirty = self._join_dirty_memo
        if epoch != view.epoch:
            n_seen, dirty = 0, frozenset()
        limit = self.config.join_dirty_max
        if dirty != "full" and len(view.new_atoms) > n_seen:
            g = self.graph
            acc = set(dirty)
            for h in view.new_atoms[n_seen:]:
                try:
                    ts = g.get_targets(h)
                except Exception:  # noqa: BLE001 - racing delete
                    continue
                if ts:
                    acc.add(int(h))
                    acc.update(int(t) for t in ts)
                    if len(acc) > limit:
                        acc = "full"
                        break
            dirty = acc if acc == "full" else frozenset(acc)
        self._join_dirty_memo = (view.epoch, len(view.new_atoms), dirty)
        if dirty == "full":
            return "full"
        return sorted(dirty) if dirty else None

    def _join_plan(self, sig, req0: JoinRequest, base):
        """The signature's compiled decomposition, planned once per
        (signature, base snapshot): the plan's statics ARE the program
        identity, so a cache hit here is a jit cache hit downstream. The
        first request's constants seed the cardinality estimates; the
        structure stays valid for every constant vector of the
        signature. None → the planner declined (host path)."""
        cache = getattr(base, "_join_plan_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(base, "_join_plan_cache", cache)
        if sig not in cache:
            from hypergraphdb_tpu.join.ir import JoinUnsupported
            from hypergraphdb_tpu.join.planner import plan_join
            from hypergraphdb_tpu.ops.join import (
                NBR_MAX_PAIRS,
                nbr_pair_count,
            )

            try:
                if any(a[0] == "co" for a in sig.atoms) and \
                        nbr_pair_count(base) > NBR_MAX_PAIRS:
                    # the co-incidence CSR would be gigabytes — decline
                    # BEFORE launch ever asks execute_join to build it
                    # on the dispatch thread
                    cache[sig] = None
                else:
                    with self.tracer.span("join.plan",
                                          sig=str(sig.atoms)):
                        cache[sig] = plan_join(
                            base, sig.bind(req0.consts), sig,
                            req0.consts,
                        )
                    if cache[sig] is not None and \
                            self.config.join_factorized:
                        # the trie encoding, built HERE (plan time, once
                        # per base epoch — the _nbr_csr discipline) so
                        # the O(E log E) grouping never lands inside a
                        # steady-state dispatch; execute_join picks it
                        # up via the snapshot cache. Its OWN failure
                        # (the closed-co build re-checks the pair
                        # budget, which a co-free signature never
                        # tripped above) must not poison the cached
                        # plan — the flat CSRs still serve it.
                        from hypergraphdb_tpu.ops.join import (
                            factorized_relations,
                        )

                        try:
                            with self.tracer.span("join.factorize"):
                                factorized_relations(base)
                        except Exception:  # noqa: BLE001 - flat serves
                            import logging

                            logging.getLogger(
                                "hypergraphdb_tpu.serve"
                            ).warning(
                                "trie factorization failed; join plan "
                                "serves from the flat CSRs",
                                exc_info=True,
                            )
            except JoinUnsupported:
                cache[sig] = None
        return cache[sig]

    def _host_join(self, req: JoinRequest, epoch: int) -> JoinResult:
        from hypergraphdb_tpu.join.host import host_join

        rows = host_join(self.graph, req.sig.bind(req.consts))
        V = len(req.sig.vars)
        arr = (np.asarray(rows, dtype=np.int64) if rows
               else np.empty((0, V), dtype=np.int64))
        top_r = self.config.top_r
        return JoinResult("join", len(arr), arr[:top_r], req.sig.vars,
                          len(arr) > top_r, epoch, served_by="host")

    # -- exact host fallbacks -------------------------------------------------
    def _host_bfs(self, req: BFSRequest, epoch: int) -> ServeResult:
        from hypergraphdb_tpu.algorithms.traversals import (
            HGBreadthFirstTraversal,
        )

        reached = {
            int(atom) for _, atom in HGBreadthFirstTraversal(
                self.graph, req.seed, max_distance=req.max_hops
            )
        }
        if req.include_seed:
            reached.add(int(req.seed))
        else:
            reached.discard(int(req.seed))
        arr = np.asarray(sorted(reached), dtype=np.int64)
        top_r = self.config.top_r
        return ServeResult("bfs", len(arr), arr[:top_r],
                           len(arr) > top_r, epoch, served_by="host")

    def _host_pattern(self, req: PatternRequest, epoch: int) -> ServeResult:
        from hypergraphdb_tpu.query import conditions as c

        clauses = [c.Incident(a) for a in req.anchors]
        if req.type_handle is not None:
            clauses.append(c.AtomType(int(req.type_handle)))
        cond = clauses[0] if len(clauses) == 1 else c.And(*clauses)
        arr = np.asarray(sorted(int(h) for h in self.graph.find_all(cond)),
                         dtype=np.int64)
        top_r = self.config.top_r
        return ServeResult("pattern", len(arr), arr[:top_r],
                           len(arr) > top_r, epoch, served_by="host")


def _make_executor(graph, config: ServeConfig, stats):
    """Pick the executor for one runtime: the mesh-sharded executor when
    ``ServeConfig(sharded=True)``, or — AUTO mode (``sharded=None``) —
    when more than one device is visible and the pinned base snapshot's
    estimated device footprint exceeds ``hbm_budget_bytes`` (the
    one-chip-cannot-hold-it trigger). Everything else stays on the
    single-chip :class:`DeviceExecutor`."""
    if config.sharded is False or graph is None:
        return DeviceExecutor(graph, config, stats)
    use = config.sharded is True
    if not use and config.hbm_budget_bytes is not None:
        import jax

        n_dev = len(jax.devices())
        if config.mesh_devices is not None:
            n_dev = min(n_dev, int(config.mesh_devices))
        if n_dev > 1:
            from hypergraphdb_tpu.serve.sharded import snapshot_device_bytes

            mgr = graph.incremental or graph.enable_incremental()
            use = snapshot_device_bytes(mgr.base) > config.hbm_budget_bytes
    if not use:
        return DeviceExecutor(graph, config, stats)
    from hypergraphdb_tpu.serve.sharded import ShardedExecutor

    return ShardedExecutor(graph, config, stats)


class ServeRuntime:
    """The serving front door. Threaded by default; ``manual=True`` for
    deterministic stepping (tests). Context manager: ``close(drain=True)``
    on exit."""

    def __init__(self, graph=None, config: Optional[ServeConfig] = None,
                 executor=None):
        self.config = config or ServeConfig()
        self.clock: Clock = self.config.clock or time.monotonic
        self.tracer = self.config.tracer or global_tracer()
        self.stats = ServeStats(self.config.latency_window)
        self.perf = self.config.perf
        self.faults = self.config.faults or global_faults()
        # per-batch-key breaker: a flaky device bucket trips to the exact
        # host-fallback path and recovers via half-open probes; the
        # per-key callbacks feed the labelled serve.breaker.* family
        # (the worst-state gauge alone cannot say WHICH bucket degraded)
        self.breaker = CircuitBreaker(
            threshold=self.config.breaker_threshold,
            cooldown_s=self.config.breaker_cooldown_s,
            clock=self.clock,
            on_state=self.stats.set_breaker_state,
            on_trip=self.stats.record_breaker_trip,
            on_key_state=self.stats.set_breaker_key_state,
            on_key_trip=self.stats.record_breaker_key_trip,
        )
        self._sleep: Callable = self.config.sleep or time.sleep
        # seeded jitter: retries are reproducible under a fixed seed
        self._retry_rng = random.Random(self.config.retry_seed)
        self.queue = AdmissionQueue(
            self.config.max_queue, self.config.policy, self.clock,
            self.stats,
        )
        self.batcher = Batcher(self.queue, self.config.buckets,
                               self.config.max_linger_s)
        self.executor = (
            executor if executor is not None
            else _make_executor(graph, self.config, self.stats)
        )
        self.graph = graph
        # the executor's say on how wide a batch of each key may form
        # (injected executors without one leave every bucket open)
        self.batcher.key_cap = getattr(self.executor, "max_batch", None)
        # deploy-time compile: load-or-build the serving executables for
        # every bucket BEFORE the dispatch thread takes traffic, so a
        # warm AOT cache reaches first dispatch without recompiling.
        # Runs with no cache too — sizing the BFS buckets must not wait
        # for the first live request (injected executors without a
        # prewarm hook are skipped)
        if (self.config.prewarm_aot and graph is not None
                and callable(getattr(self.executor, "prewarm", None))):
            try:
                self.executor.prewarm(self.config.buckets)
            except Exception:  # pragma: no cover - never block startup
                import logging

                logging.getLogger("hypergraphdb_tpu.serve").warning(
                    "aot prewarm failed", exc_info=True,
                )
        #: in-flight batch: (tickets, executor token, batch key,
        #: device_attempted) — what _finalize needs, incl. the breaker's
        #: success/failure bookkeeping
        self._pending: Optional[tuple] = None
        #: attached hgsub SubscriptionManager (``attach_subscriptions``):
        #: the dispatch cycle drives its evaluator rounds, so standing
        #: queries re-fire on the SAME thread that forms batches — their
        #: evals coalesce with ad-hoc traffic by bucket key. Set before
        #: the thread starts; read with getattr-free attribute access on
        #: every cycle (None = one comparison)
        self.subscriptions = None
        #: attached hgplan ``QueryPlanner`` (``attach_planner``): the
        #: cost-based chooser behind ``submit_planned``. None = the
        #: planned entry point is simply unavailable
        self.planner = None
        self._closed = False
        self._close_started = False
        self._draining = False
        self._close_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        if not self.config.manual:
            self._thread = threading.Thread(
                target=self._loop, name="hgdb-serve", daemon=True
            )
            self._thread.start()

    # -- submit --------------------------------------------------------------
    def submit(self, request, deadline_s: Optional[float] = None,
               priority: int = 0, explain: bool = False) -> Future:
        """Admit one request; returns its future. Raises
        :class:`~.types.QueueFull` under fail-fast backpressure,
        :class:`~.types.RuntimeClosed` after close; a deadline that expires
        while blocked lands ON the future as DeadlineExceeded. A higher
        ``priority`` class pops first at batch formation (FIFO within a
        class); shedding and backpressure are priority-blind. An
        ``admission_gate`` refusal raises
        :class:`~.types.AdmissionGated` BEFORE any queue state is
        touched (routers re-route; the request costs this node
        nothing).

        ``explain=True`` requests per-request COST ATTRIBUTION: the
        request's trace is force-sampled and, at resolve time, an
        ``obs.fleet.explain_record`` (serving lane, bucket/pad
        occupancy, device seconds, retries, breaker state, trace id —
        assembled from the ticket's own span tree) is attached to the
        returned future as ``future.explain`` BEFORE the result is
        delivered. Requires tracing (the span tree IS the record's
        source): raises :class:`~.types.Unservable` when the runtime's
        tracer is disabled."""
        gate = self.config.admission_gate
        if gate is not None:
            reason = gate()
            if reason:
                self.stats.record_gated()
                from hypergraphdb_tpu.serve.types import AdmissionGated

                raise AdmissionGated(str(reason))
        if explain and not self.tracer.enabled:
            raise Unservable(
                "explain=True needs tracing: enable the runtime's tracer "
                "(obs.enable(), or ServeConfig(tracer=Tracer().enable()))"
            )
        now = self.clock()
        dl = (deadline_s if deadline_s is not None
              else self.config.default_deadline_s)
        ticket = Ticket(
            request=request, submit_t=now,
            deadline_t=None if dl is None else now + dl,
            priority=int(priority), explain=bool(explain),
        )
        if self.tracer.enabled:  # the ONE gate read on the disabled path
            self._trace_submit(ticket)
            if explain and ticket.trace is not None:
                # the record is built from the FINISHED trace — an
                # explain request must survive any head sampling rate
                ticket.trace.force_sample()
        try:
            self.queue.submit(ticket)
        except Exception as e:
            ticket._close_trace("error", error=type(e).__name__)
            raise
        tr = ticket.trace
        if tr is not None:
            # ending is race-safe: if the dispatch thread already finished
            # the trace, the first end (finish's) won
            tr.marks["submit"].end()
        return ticket.future

    def _trace_submit(self, ticket: Ticket) -> None:
        """Open the request's trace: ``request`` root + ``submit`` and
        ``queue_wait`` spans. BOTH open before the ticket becomes visible
        to the dispatch thread — the thread may form, launch, and resolve
        the batch before ``queue.submit`` even returns to the caller, so
        every mark it pops must already exist (under ``block``
        backpressure ``queue_wait`` therefore includes the blocked-in-
        submit time)."""
        tr = self.tracer.start_trace(
            "serve.request", kind=ticket.request.kind,
            priority=ticket.priority,
        )
        if tr is None:
            return
        root = tr.start_span("request")
        tr.marks["root"] = root
        tr.marks["submit"] = tr.start_span("submit", parent=root)
        tr.marks["queue_wait"] = tr.start_span("queue_wait", parent=root)
        ticket.trace = tr

    def submit_bfs(self, seed: int, max_hops: Optional[int] = None,
                   deadline_s: Optional[float] = None,
                   include_seed: bool = True, priority: int = 0,
                   explain: bool = False) -> Future:
        return self.submit(
            BFSRequest(int(seed),
                       max_hops if max_hops is not None
                       else self.config.default_max_hops,
                       include_seed),
            deadline_s, priority, explain,
        )

    def submit_pattern(self, anchors: Sequence[int],
                       type_handle: Optional[int] = None,
                       deadline_s: Optional[float] = None,
                       priority: int = 0, explain: bool = False) -> Future:
        return self.submit(
            PatternRequest(tuple(int(a) for a in anchors),
                           None if type_handle is None
                           else int(type_handle)),
            deadline_s, priority, explain,
        )

    def submit_join(self, spec, distinct: bool = True,
                    deadline_s: Optional[float] = None,
                    priority: int = 0, explain: bool = False) -> Future:
        """Admit a conjunctive-pattern JOIN: ``spec`` is either a
        prebuilt :class:`~.types.JoinRequest` or a ``{var: condition}``
        mapping with ``query.variables.Var`` cross-references
        (``query/bridge.to_join_request`` does the extraction). Raises
        :class:`~.types.Unservable` for specs outside the pattern
        vocabulary. Resolves to a :class:`~.types.JoinResult`."""
        if not isinstance(spec, JoinRequest):
            from hypergraphdb_tpu.query.bridge import to_join_request

            spec = to_join_request(self.graph, spec, distinct=distinct)
        return self.submit(spec, deadline_s, priority, explain)

    def submit_range(self, lo=None, hi=None, *, lo_op: str = "gte",
                     hi_op: str = "lte", type_handle: Optional[int] = None,
                     anchor: Optional[int] = None, desc: bool = False,
                     limit: Optional[int] = None,
                     deadline_s: Optional[float] = None,
                     priority: int = 0, explain: bool = False) -> Future:
        """Admit a value RANGE / ordered / top-k request (the hgindex
        lane): atoms whose value lies in the ``[lo, hi]`` window of the
        bounds' kind, in value order (``desc=True`` flips it),
        optionally type-filtered / ``anchor``-incident / ``limit``-ed.
        Resolves to a :class:`~.types.ServeResult` with kind
        ``"range"``. Raises :class:`~.types.Unservable` for unbounded or
        mixed-kind windows."""
        from hypergraphdb_tpu.query.bridge import to_range_request

        return self.submit(
            to_range_request(self.graph, lo, hi, lo_op=lo_op, hi_op=hi_op,
                             type_handle=type_handle, anchor=anchor,
                             desc=desc, limit=limit),
            deadline_s, priority, explain,
        )

    def submit_query(self, condition,
                     deadline_s: Optional[float] = None,
                     priority: int = 0) -> Future:
        """Admit a query CONDITION (the batchable subset — see
        ``query/bridge``). Raises :class:`~.types.Unservable` for
        conditions outside it."""
        from hypergraphdb_tpu.query.bridge import to_request

        return self.submit(
            to_request(self.graph, condition,
                       default_max_hops=self.config.default_max_hops),
            deadline_s, priority,
        )

    # -- planned submission (hgplan) -----------------------------------------
    def attach_planner(self, planner) -> None:
        """Wire an hgplan ``QueryPlanner`` into this runtime: the
        planner's telemetry binds to THIS runtime's ``ServeStats``
        (``plan.*`` metrics ride the serving registry), its cardinality
        estimator — unless it already follows a manager — reads this
        runtime's snapshot manager, and — unless the
        planner already carries one — its sentinel guard binds to this
        runtime's perf sentinel (a learned correction may never steer
        the argmin onto a lane currently listed in the sentinel's
        ``violating`` set). ``submit_planned`` is refused until this is
        called."""
        with self._close_lock:
            planner.stats = self.stats
            est = getattr(planner, "estimator", None)
            mgr = getattr(self.executor, "mgr", None)
            if est is not None and est.mgr is None and mgr is not None:
                # price plans from the base the lanes serve, per
                # compaction epoch. A standalone estimator re-packs the
                # whole store per mutation, outside the commit lock: under
                # concurrent ingest that is seconds per planned request
                # and a torn read of the store's link table
                est.mgr = mgr
            if planner.lane_degraded is None and self.perf is not None:
                perf = self.perf

                def _lane_degraded(kind: str) -> bool:
                    try:
                        return kind in perf.health_summary().get(
                            "violating", ())
                    except Exception:
                        return False  # a perf fault must not veto plans

                planner.lane_degraded = _lane_degraded
            self.planner = planner

    def submit_planned(self, condition, deadline_s: Optional[float] = None,
                       priority: int = 0, explain: bool = False,
                       force_shape: Optional[str] = None) -> Future:
        """Admit a query CONDITION through the attached cost-based
        planner: enumerate the candidate lane strategies, dispatch the
        cheapest (``force_shape`` overrides — the differential suite's
        hook), host-filter the residual clauses, and resolve to a
        ``plan.PlannedResult`` whose ``plan`` dict carries
        ``est_rows`` / ``actual_rows`` / the chosen shape. With
        ``explain=True`` the future's ``.explain`` record grows the same
        ``plan`` sub-dict beside the lane attribution (the host shape
        synthesizes a minimal record — no lane, no trace).

        Exactness contract matches ``graph.find_all(condition)``: a
        truncated lane window is re-served brute-force on the host, so
        the planner can be WRONG about cost but never about results."""
        planner = self.planner
        if planner is None:
            raise Unservable(
                "no planner attached: build a plan.QueryPlanner and "
                "attach_planner() it before submit_planned"
            )
        choice = planner.plan(condition, force_shape=force_shape)
        if choice.request is None:
            return self._planned_host(planner, choice, explain)
        inner = self.submit(choice.request, deadline_s, priority, explain)
        outer: Future = Future()

        def _done(f: Future) -> None:
            try:
                res = f.result()
            except Exception as e:
                outer.set_exception(e)
                return
            try:
                out = self._finish_planned(planner, choice, res)
                if explain:
                    ex = dict(getattr(f, "explain", None) or {})
                    ex["plan"] = out.plan
                    outer.explain = ex
            except Exception as e:  # residual/feedback fault → caller
                outer.set_exception(e)
                return
            outer.set_result(out)

        inner.add_done_callback(_done)
        return outer

    def _planned_host(self, planner, choice, explain: bool) -> Future:
        """The host shape: no lane, no queue — the exact scan the
        brute-force oracle defines, executed inline on the caller."""
        matches = tuple(sorted(
            int(h) for h in self.graph.find_all(choice.condition)))
        planner.observe(choice, len(matches))
        plan_rec = choice.explain()
        plan_rec["actual_rows"] = len(matches)
        from hypergraphdb_tpu.plan.planner import PlannedResult

        res = PlannedResult(
            kind="planned", count=len(matches), matches=matches,
            truncated=False, epoch=choice.epoch, lane_kind="host",
            served_by="host", plan=plan_rec,
        )
        fut: Future = Future()
        if explain:
            fut.explain = {"lane": {"kind": "host", "path": "host"},
                           "plan": plan_rec}
        fut.set_result(res)
        return fut

    def _finish_planned(self, planner, choice, res):
        """Turn one lane result into the planned answer: close the
        feedback loop on the PRE-residual row count, then either apply
        the residual filter or — when the lane window truncated — fall
        back to the exact host scan (truncation-honest results have an
        exact ``count`` but only a prefix of ``matches``; filtering a
        prefix would silently drop rows)."""
        actual = int(res.count)
        planner.observe(choice, actual)
        plan_rec = choice.explain()
        plan_rec["actual_rows"] = actual
        truncated = bool(res.truncated)
        if truncated:
            with _thread_cm(self.config, "hg.serve.host_reserve"):
                matches = tuple(sorted(
                    int(h) for h in self.graph.find_all(choice.condition)))
            served_by = "host"
        else:
            if getattr(res, "kind", None) == "join":
                # single-variable condition join: project the "x" column
                # and dedupe — distinct=False keeps one row per
                # WITNESSING binding (auxiliary link vars), not per atom
                col = res.vars.index("x") if "x" in res.vars else 0
                rows = {int(t[col]) for t in res.tuples}
            else:
                rows = {int(h) for h in res.matches}
            g = self.graph
            matches = tuple(sorted(
                h for h in rows
                if all(cl.satisfies(g, h) for cl in choice.residual)))
            served_by = res.served_by
        from hypergraphdb_tpu.plan.planner import PlannedResult

        return PlannedResult(
            kind="planned", count=len(matches), matches=matches,
            truncated=False, epoch=getattr(res, "epoch", choice.epoch),
            lane_kind=res.kind, served_by=served_by, plan=plan_rec,
        )

    # -- dispatch ------------------------------------------------------------
    def attach_subscriptions(self, manager) -> None:
        """Wire an hgsub ``SubscriptionManager`` into the dispatch
        cycle: every ``step``/``pump`` runs one evaluator round before
        batch formation (dirty standing queries re-enter the admission
        queue and coalesce with ad-hoc lanes) and one after finalize
        (completed evals notify within the same wake)."""
        with self._close_lock:
            self.subscriptions = manager

    def _pump_subs(self) -> None:
        m = self.subscriptions
        if m is None:
            return
        try:
            m.pump()
        except Exception:  # the evaluator must never stall dispatch
            import logging

            logging.getLogger("hypergraphdb_tpu.serve").exception(
                "subscription pump error (continuing)"
            )

    def step(self, drain: bool = False) -> bool:
        """ONE synchronous collect→launch→finalize cycle (manual mode /
        tests). Returns whether a batch was dispatched."""
        self._pump_subs()
        t_form = self.tracer.clock() if self.tracer.enabled else None
        batch = self.batcher.next_batch(self.clock(), drain=drain)
        if batch is None:
            return False
        inflight = self._launch_guarded(batch, t_form)
        if inflight is not None:
            self.stats.record_batch(len(inflight[0]), batch.bucket)
            self._finalize(*inflight)
            self._pump_subs()
        return True

    def pump(self, drain: bool = False) -> bool:
        """One PIPELINED cycle: launch the next batch (if any), THEN
        finalize the previously launched one — host assembly of batch N+1
        overlaps device execution of batch N. Returns whether a new batch
        was consumed."""
        self._pump_subs()
        t_form = self.tracer.clock() if self.tracer.enabled else None
        batch = self.batcher.next_batch(self.clock(), drain=drain)
        inflight = None
        if batch is not None:
            inflight = self._launch_guarded(batch, t_form)
            if inflight is not None:
                self.stats.record_batch(len(inflight[0]), batch.bucket)
        prev = self._take_pending()
        if prev is not None:
            self._finalize(*prev)
            self._pump_subs()
        with self._close_lock:
            self._pending = inflight
        return batch is not None

    def _launch_guarded(self, batch, t_form=None):
        """Launch with the self-healing ladder, converting executor
        errors into per-ticket outcomes instead of a dead dispatch
        thread: transient failures get bounded exponential backoff +
        seeded jitter that respects each ticket's remaining deadline
        (a ticket whose deadline falls inside the next sleep is shed NOW,
        never parked past it); permanent failures surface typed to every
        caller; K consecutive device failures trip the batch key's
        circuit breaker, and a tripped/OPEN key re-routes the batch —
        including the one that tripped it — to the exact host-fallback
        path. Returns ``(tickets, token, key, device_attempted)`` for
        ``_finalize``, or None when every ticket was already completed.

        Traced tickets get their ``queue_wait`` closed and
        ``batch_form``/``launch`` spans here — the whole block is behind
        one ``tracer.enabled`` read; the ``launch`` span covers ALL
        attempts. ``t_form`` is the caller's pre-``next_batch``
        timestamp, so ``batch_form`` covers the REAL formation work."""
        tracer = self.tracer
        traced = tracer.enabled
        if traced:
            if t_form is None:
                t_form = tracer.clock()
            n_real = len(batch.tickets)
            pending = []
            for t in batch.tickets:
                tr = t.trace
                if tr is not None and not tr.finished:
                    qw = tr.marks.pop("queue_wait", None)
                    # clamp per ticket: a request submitted AFTER the
                    # caller's t_form capture but in time for take() must
                    # not get a negative queue_wait / a batch_form that
                    # predates its own birth
                    t0_i = t_form
                    if qw is not None:
                        t0_i = max(t_form, qw.t0)
                        qw.end(t0_i)
                    pending.append((tr, t0_i))
            t_l0 = tracer.clock()
            for tr, t0_i in pending:
                if not tr.finished:
                    tr.add_span(
                        "batch_form", t0_i, max(t_l0, t0_i),
                        parent=tr.marks.get("root"), bucket=batch.bucket,
                        n_real=n_real, n_pad=batch.bucket - n_real,
                    )
        key = batch.key
        cfg = self.config
        attempt = 0
        while True:
            device = not batch.force_host and self.breaker.allow(key)
            batch.force_host = not device
            try:
                with _thread_cm(cfg, "hg.serve.launch"):
                    launched = self.executor.launch(batch)
            except Exception as e:
                if not device:
                    # the DEGRADED path itself failed: no ladder left
                    self._fail_batch(batch.tickets, e)
                    return None
                self.breaker.record_failure(key)
                if not is_transient(e, cfg.transient_errors):
                    self._fail_batch(batch.tickets, e)
                    return None
                attempt += 1
                if self.breaker.state_of(key) == OPEN:
                    # this failure tripped the breaker: serve THIS batch
                    # on host immediately — degraded throughput, not a
                    # batch of errors (and no backoff: host is local).
                    # The tripping batch's traces are always-sample: a
                    # trip is exactly the window an operator replays
                    for t in batch.tickets:
                        if t.trace is not None:
                            t.trace.force_sample()
                    continue
                if attempt > cfg.max_retries:
                    self._fail_batch(batch.tickets, e)
                    return None
                self.stats.record_retry()
                if _FLIGHT.enabled:
                    _FLIGHT.record("serve.retry", key=str(key),
                                   attempt=attempt,
                                   error=type(e).__name__)
                if not self._backoff(batch, attempt):
                    return None  # every ticket's deadline < next attempt
                continue
            break
        if traced:
            t_l1 = tracer.clock()
            for t in batch.tickets:
                tr = t.trace
                if tr is not None and not tr.finished:
                    # retries = transient re-attempts this batch paid
                    # (0 on the clean path) — the EXPLAIN record's
                    # retry attribution reads it off this span
                    tr.add_span("launch", t_l0, t_l1,
                                parent=tr.marks.get("root"),
                                retries=attempt)
        return batch.tickets, launched, key, device

    def _backoff(self, batch, attempt: int) -> bool:
        """Sleep the capped exponential backoff (seeded jitter) before
        re-attempting a transient launch failure — deadline-aware:
        tickets whose deadline falls inside the sleep are shed NOW (the
        retry could never answer them), and with none left the batch is
        abandoned. Returns whether anything is left to retry."""
        cfg = self.config
        dt = min(cfg.retry_base_s * (2.0 ** (attempt - 1)), cfg.retry_max_s)
        dt *= 1.0 + cfg.retry_jitter * self._retry_rng.random()
        now = self.clock()
        wake = now + dt
        live = []
        for t in batch.tickets:
            if t.expired(wake):
                t.shed(now)
                self.stats.record_shed()
            else:
                live.append(t)
        batch.tickets = live
        if not live:
            return False
        self._sleep(dt)
        return True

    def _fail_batch(self, tickets, exc: BaseException) -> None:
        if tickets and _FLIGHT.enabled:
            # a typed serve error is an incident: the recorder dumps the
            # window that led here (rate-limited; counting is always on)
            _FLIGHT.incident("serve_error", error=type(exc).__name__,
                             tickets=len(tickets))
        for t in tickets:
            if t.fail(exc):
                self.stats.record_error()

    def _take_pending(self):
        """Swap the in-flight (tickets, token) pair out under the state
        lock (the lock covers only the pointer — finalize's blocking
        download runs outside it)."""
        with self._close_lock:
            prev, self._pending = self._pending, None
            return prev

    def _pending_empty(self) -> bool:
        with self._close_lock:
            return self._pending is None

    def _finalize(self, tickets, token, key=None, device=False) -> None:
        tracer = self.tracer
        traced = tracer.enabled
        t_c0 = tracer.clock() if traced else 0.0
        try:
            with _thread_cm(self.config, "hg.serve.collect"):
                results = self.executor.collect(token)
        except Exception as e:
            results = self._recover_collect(tickets, token, key, device, e)
            if results is None:
                return
        else:
            if device and key is not None:
                self.breaker.record_success(key)
        if traced:
            t_c1 = tracer.clock()
            t_dev = getattr(token, "t_device", None)
            slot = getattr(token, "slot", -1)
            if t_dev is not None:
                # one histogram observation per measured batch — the
                # device-time distribution BENCH_C6 summarizes
                self.stats.record_device_time(t_dev[1] - t_dev[0])
                if self.perf is not None and key is not None:
                    # the perf sentinel's device-seconds/request digest
                    # (guarded like EXPLAIN: a sentinel bug must degrade
                    # observability, never the batch)
                    try:
                        self.perf.observe_batch(
                            key[0], t_dev[1] - t_dev[0],
                            n_real=len(getattr(token, "lane_tickets",
                                               ()) or ()),
                            n_total=getattr(getattr(token, "batch", None),
                                            "bucket", 0) or 0,
                            t=self.clock(),
                        )
                    except Exception:  # noqa: BLE001
                        self.stats.record_perf_error()
            for ticket, res in results:
                tr = ticket.trace
                if tr is None or tr.finished:
                    continue
                root = tr.marks.get("root")
                served_by = getattr(res, "served_by", None)
                if t_dev is not None and served_by == "device":
                    tr.add_span("device", t_dev[0], t_dev[1], parent=root,
                                slot=slot)
                tr.add_span("collect", t_c0, t_c1, parent=root)
                if served_by == "host":
                    tr.add_span("host_fallback", t_c0, t_c1, parent=root)
        now = self.clock()
        device_lane = getattr(self.executor, "device_lane", "device")
        for ticket, res in results:
            if isinstance(res, BaseException):
                if ticket.fail(res):
                    self.stats.record_error()
            else:
                path = ("host"
                        if getattr(res, "served_by", None) == "host"
                        else device_lane)
                if ticket.explain:
                    self._attach_explain(ticket, res, key, path, token)
                if ticket.resolve(res):
                    # a cancel()ed future neither raises out of the
                    # dispatch thread nor counts as a completion
                    self.stats.record_complete(now - ticket.submit_t)
                    self.stats.record_lane(res.kind, path)
                    if self.perf is not None:
                        try:
                            self.perf.observe(res.kind,
                                              now - ticket.submit_t,
                                              path=path, t=now)
                        except Exception:  # noqa: BLE001
                            self.stats.record_perf_error()
        if self.perf is not None:
            # rate-limited drift evaluation rides the completion path —
            # the sentinel has no thread of its own. Guarded: an
            # evaluation bug raising out of _finalize would unwind
            # pump() before the NEXT batch's pending handoff and strand
            # its tickets — observability must never cost a request
            try:
                self.perf.maybe_tick()
            except Exception:  # noqa: BLE001
                import logging

                logging.getLogger("hypergraphdb_tpu.serve").warning(
                    "perf sentinel tick failed (continuing)",
                    exc_info=True,
                )

    def _attach_explain(self, ticket, res, key, path: str,
                        token=None) -> None:
        """The EXPLAIN resolve path: finish the ticket's trace EARLY
        (terminal ``resolve`` — ``Ticket.resolve``'s own close then
        no-ops, first-end-wins) and attach the cost-attribution record
        to the future BEFORE the result is delivered, so a caller
        reading ``fut.result()`` then ``fut.explain`` never races this
        thread. The record is assembled FROM the finished span tree
        (``obs.fleet.explain_record``) — the one source of truth the
        fleet trace view also serves. Join requests additionally carry
        the batch's plan-shape/hub/correction attribution read off the
        launched token (``_join_explain``)."""
        tr = ticket.trace
        if tr is None:
            return
        tr.finish_terminal("resolve", parent=tr.marks.get("root"))
        from hypergraphdb_tpu.obs.fleet import explain_record

        try:
            ticket.future.explain = explain_record(
                tr, result=res, lane_path=path,
                breaker_state=(None if key is None
                               else self.breaker.state_of(key)),
                shard_owner=self._shard_owner(ticket.request),
                join=self._join_explain(res, path, token),
            )
        except Exception:  # noqa: BLE001 - never fail a resolve over EXPLAIN
            ticket.future.explain = None

    @staticmethod
    def _join_explain(res, path: str, token):
        """Join-engine attribution for the EXPLAIN record (ROADMAP: the
        PR-13 records predate join engine v2): the chosen plan shape —
        ``bushy`` (GHD bag decomposition) / ``hub`` (degree-split
        dense-frontier lanes in this batch) / ``flat`` (the PR-10 step
        chain) / ``host`` (exact host path, no device plan) — plus the
        batch's ``hub_dispatches`` and collect-side
        ``partial_corrections`` (batch-level counts: the request reports
        the dispatch it rode, the per-batch twin of the
        ``serve.join.*`` counters). None for non-join requests."""
        if getattr(res, "kind", None) != "join":
            return None
        plan = getattr(token, "join_plan", None)
        hub = int(getattr(token, "join_hub_lanes", 0) or 0)
        if path == "host" or plan is None:
            shape = "host"
        elif type(plan).__name__ == "BushyJoinPlan":
            shape = "bushy"
        elif hub:
            shape = "hub"
        else:
            shape = "flat"
        return {
            "plan": shape,
            "hub_dispatches": hub,
            "partial_corrections": int(
                getattr(token, "join_partials", 0) or 0),
        }

    def _shard_owner(self, request):
        """The mesh partition that owns this request's primary id (the
        EXPLAIN record's placement attribution), or None off the sharded
        executor / for gid-addressed shapes with no raw ids."""
        ex = self.executor
        if getattr(ex, "mesh", None) is None:
            return None
        sbase = getattr(getattr(ex, "mgr", None), "_sharded_base", None)
        pmap = getattr(sbase, "partition_map", None)
        if pmap is None:
            return None
        rid = getattr(request, "seed", None)
        if rid is None:
            anchors = getattr(request, "anchors", None)
            if not anchors:
                return None
            rid = max(anchors)
        try:
            return int(pmap.owner_of(int(rid)))
        except Exception:  # noqa: BLE001 - ids beyond the map: unowned
            return None

    def _recover_collect(self, tickets, token, key, device,
                         exc: BaseException):
        """A collect failure poisons the whole batch's device handles;
        the recovery is an exact host re-serve under the same pinned
        epoch (the executor's ``collect_host`` hook), not a device retry
        — the async results are gone either way. Feeds the breaker like
        any other device failure. Returns replacement results, or None
        after failing every ticket typed."""
        if device and key is not None:
            self.breaker.record_failure(key)
        host = getattr(self.executor, "collect_host", None)
        if host is not None and is_transient(exc,
                                             self.config.transient_errors):
            self.stats.record_retry()
            try:
                return host(token)
            except Exception as e2:
                exc = e2
        self._fail_batch(tickets, exc)
        return None

    def _loop(self) -> None:
        import logging

        log = logging.getLogger("hypergraphdb_tpu.serve")
        while True:
            try:
                if self._closed and not self._draining:
                    prev = self._take_pending()
                    if prev is not None:
                        self._finalize(*prev)
                    self.queue.cancel_all()
                    return
                worked = self.pump(drain=self._draining)
                if worked:
                    continue  # keep forming batches while the device runs
                # exit only once _closed is set (which happens AFTER
                # admission closed): no submit can land behind our back
                if (self._closed and self._draining
                        and self.queue.depth() == 0
                        and self._pending_empty()):
                    return
                ttf = self.batcher.time_to_flush(self.clock())
                with _thread_cm(self.config, "hg.serve.park"):
                    if ttf is None:
                        # empty queue: wait_for_work's non-empty pre-check
                        # makes the submit-before-wait race safe for an
                        # unbounded park
                        self.queue.wait_for_work(None)
                    else:
                        # items queued but linger remaining: sleep the
                        # remainder (a submit filling the bucket notifies
                        # and wakes us early; a missed wakeup costs at
                        # most max_linger_s)
                        self.queue.park(ttf)
            except Exception:
                # the per-batch paths already route errors onto tickets;
                # anything landing here is a runtime bug — log it and
                # keep serving rather than stranding every future caller
                log.exception("serve dispatch loop error (continuing)")
                time.sleep(0.01)  # no hot-spin on a persistent fault

    # -- lifecycle -----------------------------------------------------------
    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Stop admitting and shut down. ``drain=True`` flushes and
        completes everything queued and in flight; ``drain=False``
        completes only the in-flight batch and fails queued tickets with
        RuntimeClosed."""
        with self._close_lock:
            already = self._close_started
            self._close_started = True
            if not already:
                self._draining = drain
        if not already:
            # admission closes BEFORE the thread sees _closed: a submit
            # racing close() either lands while the thread still serves or
            # raises RuntimeClosed — never a silently stranded ticket
            self.queue.close()
            with self._close_lock:
                self._closed = True
        if self._thread is not None:
            self._thread.join(timeout)
            return
        if already:
            return
        # manual mode: run the shutdown inline, deterministically
        prev = self._take_pending()
        if prev is not None:
            self._finalize(*prev)
        if drain:
            while self.step(drain=True):
                pass
        else:
            self.queue.cancel_all()

    def __enter__(self) -> "ServeRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=True)

    def stats_snapshot(self) -> dict:
        out = self.stats.snapshot(queue_depth=self.queue.depth())
        aot = getattr(self.executor, "aot", None)
        if aot is not None:
            out["aot"] = aot.stats.as_dict()
        return out
