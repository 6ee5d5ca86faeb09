"""hgobs — the unified observability subsystem.

One surface every layer reports into, replacing the reference's scatter
of ad-hoc counters (``HGStats`` / ``TxMonitor`` / ``HGIndexStats``) that
this repro had faithfully reproduced as ``utils.metrics.Metrics`` vs
``serve.stats.ServeStats``:

- **tracing** (:mod:`~hypergraphdb_tpu.obs.trace`): bounded span trees
  with explicit parenting and injectable clocks. A served request emits
  ``submit → queue_wait → batch_form → launch → device → collect →
  resolve`` (or ``shed`` / ``host_fallback``); a compaction pass emits
  ``compact → buffer_drain → device_swap``; a query emits
  ``compile → plan → execute``;
- **metrics** (:mod:`~hypergraphdb_tpu.obs.registry`): one registry of
  counters/gauges/log-bucketed histograms under dotted namespaces
  (``serve.*``, ``graph.*``, ``compact.*``, ``query.*``, ``tx.*``);
- **device timing** (:mod:`~hypergraphdb_tpu.obs.device`): opt-in
  launch→ready wall deltas, per-dispatch profiler annotations, a gated
  ``jax.profiler`` session, and ``phase`` for coarse synced host steps
  (seconds in the registry, a host span on the device trace's clock).
  Every phase instance also keeps a RECORD in ``phase_log()`` (a
  ``FlightRecorder`` of its own): who called it (``parent``, and ``op``,
  the outermost phase: ``hg.bfs.pull`` / ``.match`` / ``.pairs`` around an
  operation), wall beside thread CPU, switches and page faults, and its
  STEPS — ``ph.step(sub)`` / ``ph.wait(x)`` on the handle ``phase``
  yields: where the dispatch ends and the wait begins. One
  ``jax.monitoring`` LISTENER, registered at the first phase entry, puts
  JAX's trace / lower / compile / cache-load seconds on the phase that
  paid them (histograms ``jit.*``, records of kind ``jit``); an instance
  far over its name's median that compiled nothing is counted
  (``obs.phase.stalls``) and logged once, as a JSON line;
- **export** (:mod:`~hypergraphdb_tpu.obs.export`): Prometheus text and
  schema-versioned JSONL traces;
- **flight recorder** (:mod:`~hypergraphdb_tpu.obs.flight`): an
  always-on bounded ring of recent structured events (span terminals,
  fault firings, breaker transitions, retries, compaction swaps) that
  dumps its window to JSONL on incident;
- **HTTP endpoint** (:mod:`~hypergraphdb_tpu.obs.http`): ``/metrics``
  (Prometheus scrape), ``/healthz`` (per-key breaker states + queue
  depth + staleness), ``/debug/traces``, ``/debug/flight``;
- **fleet plane** (:mod:`~hypergraphdb_tpu.obs.fleet`): one collector
  over every process behind the front door — per-node-labelled metric
  merges, cross-process span-tree assembly on the 128-bit trace ids,
  remote incident-window retention, and per-request EXPLAIN cost
  attribution;
- **SLOs** (:mod:`~hypergraphdb_tpu.obs.slo`): declarative objectives
  over sliding windows with multi-window error-budget burn-rate alerts
  that fire as flight-recorder incidents;
- **perf sentinel** (:mod:`~hypergraphdb_tpu.obs.perf`): per-lane
  rolling digests vs the committed ``PERF_BASELINE.json``, multi-window
  drift detection with auto-captured incident profiles, and mesh
  skew/straggler attribution — the runtime twin of hgverify's HV401
  static cost gate.

Cross-process tracing: trace contexts propagate over peer messages
(``peer/messages.attach_trace``), so a replication push or snapshot
transfer is ONE span tree spanning sender and receiver — see
``obs.trace`` and README "Distributed tracing & operations".

Overhead contract: with tracing DISABLED (the default), every
instrumentation site costs one attribute read and allocates nothing —
regression-tested by ``tests/test_obs_serving.py``. With tracing ON,
head-based per-root-kind sampling (``tracer().set_sample_rate``) plus
the always-sample overrides (errors, sheds, breaker trips) keep the
finished-trace buffer bounded at production qps.

Usage::

    from hypergraphdb_tpu import obs

    obs.enable()                      # tracing on, process-wide
    obs.tracer().set_sample_rate("serve.request", 0.01)
    ... serve / query / compact ...
    print(obs.export.prometheus_text(rt.stats.registry))
    for t in obs.tracer().drain():
        ...
"""

from hypergraphdb_tpu.obs import device, export, fleet, flight, http, perf, slo
from hypergraphdb_tpu.obs.device import (
    annotate,
    block_timed,
    phase,
    phase_log,
    profile,
    profiling,
)
from hypergraphdb_tpu.obs.export import (
    TRACE_SCHEMA_VERSION,
    merge_expositions,
    parse_traces_jsonl,
    prometheus_text,
    relabel_exposition,
    sample_value,
    trace_to_dict,
    traces_to_jsonl,
    write_telemetry,
)
from hypergraphdb_tpu.obs.fleet import (
    FleetCollector,
    HTTPNodeSource,
    LocalNodeSource,
    explain_record,
)
from hypergraphdb_tpu.obs.flight import (
    FlightRecorder,
    global_flight,
    install_sigterm_dump,
    parse_flight_jsonl,
)
from hypergraphdb_tpu.obs.http import (
    TelemetryServer,
    breaker_key_label,
    composite_health,
    runtime_health,
)
from hypergraphdb_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
)
from hypergraphdb_tpu.obs.perf import (
    PerfSentinel,
    load_baseline,
    seed_baseline,
    shard_skew,
)
from hypergraphdb_tpu.obs.slo import Objective, SLOMonitor, fleet_objectives
from hypergraphdb_tpu.obs.trace import Clock, Span, Trace, Tracer, global_tracer


def tracer() -> Tracer:
    """The process-wide tracer (disabled until :func:`enable`)."""
    return global_tracer()


def enable(clock=None) -> Tracer:
    """Turn process-wide tracing on; returns the tracer."""
    return global_tracer().enable(clock)


def disable() -> Tracer:
    """Turn process-wide tracing off (already-open traces still finish)."""
    return global_tracer().disable()


__all__ = [
    "Clock",
    "Counter",
    "FleetCollector",
    "FlightRecorder",
    "Gauge",
    "HTTPNodeSource",
    "Histogram",
    "LocalNodeSource",
    "Objective",
    "PerfSentinel",
    "Registry",
    "SLOMonitor",
    "Span",
    "TRACE_SCHEMA_VERSION",
    "TelemetryServer",
    "Trace",
    "Tracer",
    "annotate",
    "block_timed",
    "breaker_key_label",
    "composite_health",
    "default_registry",
    "device",
    "disable",
    "enable",
    "explain_record",
    "export",
    "fleet",
    "fleet_objectives",
    "flight",
    "global_flight",
    "global_tracer",
    "http",
    "install_sigterm_dump",
    "load_baseline",
    "merge_expositions",
    "parse_flight_jsonl",
    "parse_traces_jsonl",
    "perf",
    "phase",
    "phase_log",
    "profile",
    "profiling",
    "prometheus_text",
    "relabel_exposition",
    "runtime_health",
    "sample_value",
    "seed_baseline",
    "shard_skew",
    "slo",
    "trace_to_dict",
    "tracer",
    "traces_to_jsonl",
    "write_telemetry",
]
