"""Device-timing hooks: dispatch wall-clock + profiler trace sessions.

JAX dispatch is asynchronous: ``launch`` returns array HANDLES and the
host only learns how long the device actually ran when something blocks
on them. The serving runtime exploits that for pipelining — which means
naive timestamps around ``launch`` measure host assembly, not device
execution. :func:`block_timed` is the one honest measurement available
without a profiler: block until the handles are ready and report the
launch→ready wall delta, attributed to the batch's ``device`` span by the
caller. It is OPT-IN (``ServeConfig.device_timing``) because the block
itself serializes the pipeline's collect side a little earlier than a
plain download would.

**Per-hop attribution via the profiler**: within one double-buffered
dispatch pipeline, ``block_timed`` can only see whole-batch wall deltas —
what ran INSIDE the kernel (and which pipeline slot a batch occupied) is
the profiler's to answer. :func:`profile` opens a ``jax.profiler`` trace
session; while one is active (``profiling()``), the serving executor
wraps every kernel dispatch in :func:`annotate` — a named
``jax.profiler.TraceAnnotation`` carrying the batch kind, bucket, and
double-buffer slot — so the profile's device timeline is attributable
per batch: which slot launched it, what overlapped it, how long the
device actually ran. Each ``device`` span likewise carries its ``slot``
(dispatch sequence mod 2), closing the host-side half of the PR-5
"per-hop device spans need profiler integration" follow-up.

Both hooks are clean no-ops when jax/profiling is unavailable — call
sites carry the knob unconditionally.

**Coarse host steps**: :func:`phase` is the one primitive for a host step
that is synced at its end (a plan build, an upload, one stage of a hop):
seconds into the default registry always, a host span on the device
trace's clock while a session is open, a child span under the thread's
current trace. ``PERF.md`` section 3 lists every name and its reader.

**The host's seconds from inside**: every phase INSTANCE also leaves one
record in :func:`phase_log` (a ``FlightRecorder`` of its own): its name,
its ``id``, the enclosing phase (``parent``) and the outermost one (``op``:
the records of one operation share it), ``t0``/``t1``, the thread's CPU
seconds and its involuntary switches and page faults beside the wall,
the seconds of each :meth:`PhaseHandle.step` (``step.<sub>``: where the
dispatch ends and the wait begins — :meth:`PhaseHandle.wait` is
``block_until_ready`` as step ``wait``), and what JAX did inside it
(``jit.trace_s`` / ``.lower_s`` / ``.compile_s`` / ``.load_s``, from ONE
``jax.monitoring`` listener registered at the first phase entry, which
also appends a record of kind ``jit`` an event). An instance that took
far longer than its name's median and compiled nothing is flagged
``stall``, counted (``obs.phase.stalls``) and logged once, as it happens.

No module-level jax import: the deterministic tier-1 tests import obs
with zero device work.
"""

from __future__ import annotations

import itertools
import json
import logging
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

from hypergraphdb_tpu.obs.flight import FlightRecorder
from hypergraphdb_tpu.obs.registry import default_registry
from hypergraphdb_tpu.obs.trace import global_tracer

try:  # the thread's own switches and faults; not every platform has them
    import resource

    _RUSAGE_THREAD: Optional[int] = resource.RUSAGE_THREAD
except (ImportError, AttributeError):
    _RUSAGE_THREAD = None

_LOG = logging.getLogger("hypergraphdb_tpu.obs")

#: True while a profile() session is open — the serving executor gates
#: its per-dispatch TraceAnnotations on (device_timing or this), so a
#: plain run pays nothing for annotation support
_PROFILING = False


def profiling() -> bool:
    """Whether an ``obs.profile`` session is currently active."""
    return _PROFILING


@contextmanager
def annotate(name: str):
    """A named ``jax.profiler.TraceAnnotation`` around a host-side
    dispatch (NOT inside jit — purity of the traced graph is hgverify
    HV1xx territory); a no-op when the profiler is unavailable."""
    try:
        import jax

        ann = jax.profiler.TraceAnnotation(name)
    except Exception:
        yield False
        return
    with ann:
        yield True


#: one record a phase INSTANCE and one a JAX compile event, newest last;
#: at 9-16 phases an operation the ring holds about a thousand operations
PHASE_LOG_CAPACITY = 16_384
#: a stall: an instance over STALL_MIN_S (the one compare the normal path
#: pays) that holds no ``jit`` seconds and ran over STALL_FACTOR times its
#: name's median, once the name has STALL_MIN_SAMPLES
STALL_MIN_S = 0.25
STALL_FACTOR = 8.0
STALL_MIN_SAMPLES = 8
#: raw seconds a ``phase.<name>`` histogram keeps, so that the median a
#: stall is held against is exact and not a bucket's upper edge
PHASE_WINDOW = 64

#: JAX 0.9.0's duration events -> the field of a phase's ``jit.*`` they add
#: to and the registry histogram beside it
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace_s", "jit.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower_s", "jit.lower"),
    # a cache hit's load is INSIDE this one: a reader takes load_s out
    "/jax/core/compile/backend_compile_duration":
        ("compile_s", "jit.compile"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("load_s", "jit.cache_load"),
}

_PHASE_LOG = FlightRecorder(capacity=PHASE_LOG_CAPACITY,
                            clock=time.perf_counter)
_PHASE_IDS = itertools.count(1)   # next() is one GIL-atomic step
_OPEN = threading.local()         # .stack: the thread's open phases
_PHASE_HISTS: dict = {}           # name -> its histogram (_phase_hist)
_LISTENER_LOCK = threading.Lock()
_LISTENING = False                # the one jax.monitoring listener is on


def phase_log() -> FlightRecorder:
    """The ring of phase-instance and ``jit`` records (``records()``:
    ``(t, kind, fields)``, oldest first; kind ``phase`` or ``jit``)."""
    return _PHASE_LOG


def _open_phases() -> list:
    try:
        return _OPEN.stack
    except AttributeError:
        stack = _OPEN.stack = []
        return stack


def _inside_a_trace() -> bool:
    """Whether this thread is tracing right now: an event that fires then
    (every ``jnp`` call of a traced body is a traced ``jit`` of its own,
    every ``pallas_call`` site traces its kernel) lies inside the
    enclosing trace's own seconds and is not counted twice."""
    import jax

    ctx = getattr(jax.core, "trace_ctx", None)
    return ctx is not None and not ctx.is_top_level()


def _on_jit_event(event: str, secs: float, **kw) -> None:
    """The ``jax.monitoring`` listener: JAX calls it on the thread that
    traced, lowered, compiled or loaded, so the thread's innermost open
    phase is the one that paid."""
    known = _JIT_EVENTS.get(event)
    if known is None or _inside_a_trace():
        return
    field, hist = known
    stack = _open_phases()
    if stack:
        jit = stack[-1].jit
        jit[field] = jit.get(field, 0.0) + secs
    default_registry().histogram(hist).observe(secs)
    _PHASE_LOG.record("jit", stage=field, fun_name=kw.get("fun_name"),
                      secs=secs, op=stack[0].id if stack else 0)


def _listen_to_jax() -> None:
    """Register the listener, once a process, where jax is importable."""
    global _LISTENING
    with _LISTENER_LOCK:
        if _LISTENING:
            return
        _LISTENING = True  # one try: a process without jax never asks again
        try:
            from jax import monitoring
        except ImportError:
            return
        monitoring.register_event_duration_secs_listener(_on_jit_event)


class PhaseHandle:
    """What ``with phase(name) as ph:`` yields: the open instance."""

    __slots__ = ("name", "id", "parent", "op", "steps", "jit")

    def __init__(self, name: str, parent: "Optional[PhaseHandle]"):
        self.name = name
        self.id = next(_PHASE_IDS)
        self.parent = parent.id if parent is not None else 0
        self.op = parent.op if parent is not None else self.id
        self.steps: dict = {}
        self.jit: dict = {}

    @contextmanager
    def step(self, sub: str):
        """A part of the phase: its seconds into the record's
        ``step.<sub>`` and, under ``obs.profile()``, a ``TraceAnnotation``
        ``<phase>.<sub>`` (so a device idle gap is charged to the step).
        Coarse as a phase is: never per request, row or chunk."""
        t0 = time.perf_counter()
        try:
            if _PROFILING:
                with annotate(f"{self.name}.{sub}"):
                    yield
            else:
                yield
        finally:
            self.steps[sub] = (self.steps.get(sub, 0.0)
                               + time.perf_counter() - t0)

    def wait(self, x):
        """``jax.block_until_ready(x)`` as step ``wait``; returns ``x``."""
        import jax

        with self.step("wait"):
            jax.block_until_ready(x)
        return x


def _thread_faults() -> Optional[tuple]:
    if _RUSAGE_THREAD is None:
        return None
    ru = resource.getrusage(_RUSAGE_THREAD)
    return ru.ru_nivcsw, ru.ru_minflt, ru.ru_majflt


def _device_memory() -> dict:
    """The first device's allocator statistics, for a stall's line."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:  # a log line must not fail the phase it reports
        return {}
    return {k: stats.get(k) for k in (
        "bytes_in_use", "peak_bytes_in_use", "largest_free_block_bytes",
        "num_allocs")}


def _phase_hist(name: str):
    """``phase.<name>`` of the default registry, found once a name: the
    registry's get-or-create checks its arguments on every call."""
    hist = _PHASE_HISTS.get(name)
    if hist is None:
        hist = _PHASE_HISTS[name] = default_registry().histogram(
            f"phase.{name}", window=PHASE_WINDOW)
    return hist


def _flag_stall(fields: dict, op_name: str, hist) -> None:
    """If the instance ran over STALL_FACTOR times its name's median (of
    at least STALL_MIN_SAMPLES): flag the record, count it and say so,
    once, as a JSON line — which operation (the ``op_index``-th of its
    name, warm-up included, from 0), which phase, its steps, wall beside
    CPU, switches and faults, and the allocator's numbers right now."""
    if hist.count < STALL_MIN_SAMPLES:
        return
    wall, median = fields["t1"] - fields["t0"], hist.percentile(0.5)
    if wall <= STALL_FACTOR * median:
        return
    fields["stall"] = True
    done = default_registry().get(f"phase.{op_name}")
    default_registry().counter("obs.phase.stalls").inc()
    _LOG.warning("obs.phase stall %s", json.dumps(
        {**fields, "wall_s": wall, "median_s": median, "op_name": op_name,
         "op_index": 0 if done is None else done.count,
         "memory": _device_memory()}, sort_keys=True))


@contextmanager
def phase(name: str):
    """A coarse host step, named ``hg.<layer>.<step>``, around a dispatch
    AND the sync that ends it. Always: the elapsed ``time.perf_counter()``
    seconds go into the default registry's histogram ``phase.<name>`` (its
    ``total`` and ``count`` are what readers use), whether the body raises
    or not, and one record of the instance goes into :func:`phase_log`
    (module docstring). While an ``obs.profile`` session is open: also a
    ``TraceAnnotation``, so the step is a host span on the device trace's
    clock and the trace reducer charges the device's idle gaps to it.
    Under the thread's current trace of the process tracer: also a child
    span, through ``Tracer.span``. Yields the instance's
    :class:`PhaseHandle` (``ph.step(sub)``, ``ph.wait(x)``).

    Rule: a phase is per call, per hop or per dispatch — NEVER per
    request, row or chunk (a 3-hop traversal records about 20)."""
    if not _LISTENING:
        _listen_to_jax()
    stack = _open_phases()
    ph = PhaseHandle(name, stack[-1] if stack else None)
    with global_tracer().span(name):
        stack.append(ph)
        faults0 = _thread_faults()
        cpu0 = time.thread_time()
        t0 = time.perf_counter()
        try:
            if _PROFILING:
                with annotate(name):
                    yield ph
            else:
                yield ph
        finally:
            t1 = time.perf_counter()
            cpu_s = time.thread_time() - cpu0
            stack.pop()
            fields = {"name": name, "id": ph.id, "parent": ph.parent,
                      "op": ph.op, "t0": t0, "t1": t1, "cpu_s": cpu_s}
            if faults0 is not None:
                fields.update(zip(("nivcsw", "minflt", "majflt"), (
                    now - was for now, was in zip(_thread_faults(),
                                                  faults0))))
            for sub, secs in ph.steps.items():
                fields[f"step.{sub}"] = secs
            for stage, secs in ph.jit.items():
                fields[f"jit.{stage}"] = secs
            hist = _phase_hist(name)
            if t1 - t0 > STALL_MIN_S and not ph.jit:
                _flag_stall(fields, stack[0].name if stack else name, hist)
            hist.observe(t1 - t0)
            _PHASE_LOG.record("phase", **fields)


def block_timed(handles, clock: Callable[[], float]) -> tuple:
    """Block until ``handles`` (any pytree of jax arrays) are ready;
    returns ``(handles, t_ready)``. Against a launch timestamp taken on
    the same clock, ``t_ready`` gives the launch→ready wall delta — the
    per-dispatch device attribution (see ``serve/runtime.py``)."""
    import jax

    jax.block_until_ready(handles)
    return handles, clock()


@contextmanager
def profile(logdir: Optional[str]):
    """A ``jax.profiler`` trace session writing to ``logdir``; a no-op
    context when ``logdir`` is falsy or the profiler is unavailable (CPU
    CI images without profiling support must not error). Sets the
    :func:`profiling` flag so dispatch sites turn their per-batch
    :func:`annotate` markers on for the session's duration. The session
    traces the host's ``TraceAnnotation``s and the device, NOT the Python
    interpreter: the Python tracer slows every host thread (under it a
    served window left requests unanswered, ``PERF.md`` section 6) and
    makes the trace large."""
    global _PROFILING

    if not logdir:
        yield False
        return
    try:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # TraceAnnotations only
        jax.profiler.start_trace(logdir, profiler_options=options)
    except Exception:
        yield False
        return
    _PROFILING = True
    try:
        yield True
    finally:
        _PROFILING = False
        try:
            jax.profiler.stop_trace()
        except Exception:  # hglint: disable=HG1005
            pass  # teardown: a torn session must not mask the workload error
