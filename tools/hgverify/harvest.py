"""Entry-point harvesting: trace and lower every registered entry.

The registry lives in ``hypergraphdb_tpu.verify`` (the product side, so
kernel modules can decorate without depending on the tools tree); this
module imports the kernel modules — which populates the registry as a
side effect — then traces each entry's exemplar args to a closed jaxpr
and compiles it on the CPU backend for XLA's static cost analysis.

Everything runs under ``JAX_PLATFORMS=cpu``: tracing is
platform-independent (the jaxpr IS the ground truth of what a TPU run
would execute), and the CPU cost model, while not TPU-accurate in
absolute terms, is deterministic — exactly what a *regression* gate
needs.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass
from typing import Optional

#: kernel modules whose import populates the production registry
PRODUCT_MODULES = (
    "hypergraphdb_tpu.ops.frontier",
    "hypergraphdb_tpu.ops.bitfrontier",
    "hypergraphdb_tpu.ops.ellbfs",
    "hypergraphdb_tpu.ops.setops",
    "hypergraphdb_tpu.ops.pallas_gather",
    "hypergraphdb_tpu.ops.incremental",
    "hypergraphdb_tpu.ops.serving",
    "hypergraphdb_tpu.ops.join",
    "hypergraphdb_tpu.ops.sharded_serving",
    "hypergraphdb_tpu.ops.value_index",
    "hypergraphdb_tpu.parallel.sharded",
)

#: cost metrics the budget gate tracks, in report order
COST_METRICS = ("flops", "bytes_accessed", "temp_bytes")


@dataclass
class Trace:
    """One harvested entry: the traced jaxpr + measured static costs, or
    the error that prevented either (an HV100 finding downstream)."""

    entry: object                  # verify.Entry
    jaxpr: Optional[object] = None     # jax.core.ClosedJaxpr
    costs: Optional[dict] = None       # metric -> number
    error: Optional[str] = None        # trace/lower failure summary

    @property
    def ok(self) -> bool:
        return self.jaxpr is not None


def production_registry():
    """Import the kernel modules and return the populated registry."""
    import importlib

    for name in PRODUCT_MODULES:
        importlib.import_module(name)
    from hypergraphdb_tpu.verify import REGISTRY

    return REGISTRY


def harvest(registry) -> list:
    """Trace + cost-compile every entry in ``registry``; never raises for
    a single bad entry — failures surface as ``Trace.error``."""
    return [trace_entry(e) for e in registry]


def _split_exemplars(raw) -> tuple:
    """A ``shapes=`` callable returns either a tuple of positional
    exemplars, or an ``(args_tuple, kwargs_dict)`` pair for entries whose
    traced arguments sit after static positional parameters."""
    if (isinstance(raw, tuple) and len(raw) == 2
            and isinstance(raw[0], (tuple, list))
            and isinstance(raw[1], dict)):
        return tuple(raw[0]), dict(raw[1])
    return tuple(raw), {}


def _bind(entry, n_pos: int, kw_names: list):
    """Flatten (positional + keyword) exemplars into one positional
    signature so every exemplar is a traced INPUT (a partial-bound
    ShapeDtypeStruct would leak into the trace as a closure constant);
    static kwargs stay concrete Python values."""
    fn, statics = entry.fn, entry.statics

    def bound(*flat):
        kws = dict(zip(kw_names, flat[n_pos:]))
        return fn(*flat[:n_pos], **kws, **statics)

    return bound


def trace_entry(entry) -> Trace:
    import jax

    try:
        args, kwargs = _split_exemplars(entry.shapes())
        kw_names = list(kwargs)
        flat = args + tuple(kwargs[k] for k in kw_names)
        bound = _bind(entry, len(args), kw_names)
        # ONE trace serves both consumers: ``traced.jaxpr`` for the
        # HV1xx-HV3xx walks (inner pjit eqns keep their donated_invars)
        # and ``traced.lower()`` for the cost analysis
        traced = jax.jit(bound).trace(*flat)
        jaxpr = traced.jaxpr
    except Exception as exc:  # noqa: BLE001 - reported as HV100
        return Trace(entry=entry, error=_summ(exc))
    costs = None
    cost_err = None
    try:
        costs = measure_costs(traced)
    except Exception as exc:  # noqa: BLE001 - reported as HV100
        cost_err = _summ(exc)
    return Trace(entry=entry, jaxpr=jaxpr, costs=costs, error=cost_err)


def measure_costs(traced) -> dict:
    """Compile the traced entry on the current (CPU) backend and read
    XLA's static cost analysis: FLOPs, bytes accessed, and the peak
    temp-buffer footprint from the memory analysis."""
    with warnings.catch_warnings():
        # CPU drops donation with a warning; that is HV3xx's job to judge
        warnings.simplefilter("ignore")
        compiled = traced.lower().compile()
    ca = compiled.cost_analysis()
    props = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    mem = compiled.memory_analysis()
    return {
        "flops": int(props.get("flops", 0) or 0),
        "bytes_accessed": int(props.get("bytes accessed", 0) or 0),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0) or 0),
    }


def _summ(exc: Exception) -> str:
    s = f"{type(exc).__name__}: {exc}"
    first = s.splitlines()[0] if s else type(exc).__name__
    return first[:300]


def rel_path(path: str) -> str:
    try:
        rel = os.path.relpath(path)
    except ValueError:  # pragma: no cover - different drive on windows
        return path
    return path if rel.startswith("..") else rel
