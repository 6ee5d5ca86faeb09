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

No module-level jax import: the deterministic tier-1 tests import obs
with zero device work.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Optional

from hypergraphdb_tpu.obs.registry import default_registry
from hypergraphdb_tpu.obs.trace import global_tracer

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


@contextmanager
def phase(name: str):
    """A coarse host step, named ``hg.<layer>.<step>``, around a dispatch
    AND the sync that ends it. Always: the elapsed ``time.perf_counter()``
    seconds go into the default registry's histogram ``phase.<name>`` (its
    ``total`` and ``count`` are what readers use), whether the body raises
    or not. While an ``obs.profile`` session is open: also a
    ``TraceAnnotation``, so the step is a host span on the device trace's
    clock and the trace reducer charges the device's idle gaps to it.
    Under the thread's current trace of the process tracer: also a child
    span, through ``Tracer.span``.

    Rule: a phase is per call, per hop or per dispatch — NEVER per
    request, row or chunk (a 3-hop traversal records about 20)."""
    with global_tracer().span(name):
        t0 = time.perf_counter()
        try:
            if _PROFILING:
                with annotate(name):
                    yield
            else:
                yield
        finally:
            default_registry().histogram(f"phase.{name}").observe(
                time.perf_counter() - t0)


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
