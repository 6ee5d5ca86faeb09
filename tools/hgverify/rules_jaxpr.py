"""HV1xx–HV3xx: contract checks over the traced closed jaxpr.

These are the IR-level twins of hglint's AST predictions (HG1xx host
sync, HG6xx collectives, HG106 donation): instead of guessing from
syntax, they walk the equations tracing actually produced — through
``pjit``/``cond``/``scan``/``while``/``shard_map`` sub-jaxprs — so a
callback smuggled in through five layers of helpers, or a collective
whose axis name was computed, is found exactly where XLA will run it.
"""

from __future__ import annotations

from collections import Counter

from tools.hgverify.harvest import Trace, rel_path
from tools.hgverify.model import Finding

#: callback primitive name -> (rule, one-line hazard)
CALLBACK_PRIMS = {
    "pure_callback": ("HV101", "a host round-trip per dispatch"),
    "io_callback": ("HV102", "an ordered host side effect per dispatch"),
    "debug_callback": ("HV103", "host debug callback baked into the "
                                "compiled graph"),
    # jax.debug.print has its own primitive (jax.debug.callback keeps
    # debug_callback)
    "debug_print": ("HV103", "host debug print baked into the compiled "
                             "graph"),
    "outside_call": ("HV104", "legacy host_callback staging"),
    "host_callback_call": ("HV104", "legacy host_callback staging"),
}

#: primitives that communicate across a named mesh axis (axis names live
#: in the ``axes``/``axis_name``/``axis_index_groups`` params)
COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "ppermute", "pgather",
}

#: device-local mesh queries: they carry an axis name but move no data
NON_COMMUNICATING = {"axis_index"}


def check(traces: list) -> list:
    findings = []
    for tr in traces:
        findings += check_trace(tr)
    return findings


def check_trace(tr: Trace) -> list:
    entry = tr.entry
    path, line, scope = rel_path(entry.path), entry.line, entry.name
    if not tr.ok:
        return [Finding(
            rule="HV100", path=path, line=line, scope=scope,
            message=(f"entry failed to trace/lower with its registered "
                     f"exemplars: {tr.error}"),
        )]
    findings = []
    if tr.error:  # traced, but cost lowering failed
        findings.append(Finding(
            rule="HV100", path=path, line=line, scope=scope,
            message=f"entry traced but failed to compile for cost "
                    f"analysis: {tr.error}",
        ))
    walk = JaxprWalk(tr.jaxpr)
    findings += _check_callbacks(walk, path, line, scope)
    findings += _check_collectives(walk, entry, path, line, scope)
    findings += _check_donation(walk, entry, path, line, scope)
    return findings


# ------------------------------------------------------------------- walker


class JaxprWalk:
    """One recursive pass collecting everything the rules need: callback
    equations, collective equations with their axis names, ``cond`` /
    ``switch`` equations (for branch comparison), and ``pjit`` equations
    carrying donation metadata."""

    def __init__(self, closed):
        self.callbacks: list = []      # (prim_name, depth)
        self.collectives: list = []    # (prim_name, axes tuple)
        self.conds: list = []          # eqn
        self.pjits: list = []          # nested jax.jit equations
        self.shard_meshes: list = []   # tuple of axis names per shard_map
        self._walk(closed.jaxpr, 0)

    def _walk(self, jaxpr, depth):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in CALLBACK_PRIMS:
                self.callbacks.append((name, depth))
            if name in COLLECTIVE_PRIMS:
                self.collectives.append((name, _axes_of(eqn)))
            if name == "cond":
                self.conds.append(eqn)
            if name == "jit":  # a nested jax.jit call (once named pjit)
                self.pjits.append(eqn)
            if name == "shard_map":
                mesh = eqn.params.get("mesh")
                axes = tuple(getattr(mesh, "axis_names", ()) or ())
                if axes:
                    self.shard_meshes.append(axes)
            for sub in _sub_jaxprs(eqn):
                self._walk(sub, depth + 1)


def _sub_jaxprs(eqn):
    # jax.extend is not an attribute of a bare ``import jax``
    from jax.extend import core as jex_core

    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for w in vs:
            if isinstance(w, jex_core.ClosedJaxpr):
                yield w.jaxpr
            elif isinstance(w, jex_core.Jaxpr):
                yield w


def _axes_of(eqn) -> tuple:
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", None)
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if isinstance(a, str))
    return (axes,) if isinstance(axes, str) else ()


# ------------------------------------------------------------------- HV1xx


def _check_callbacks(walk: JaxprWalk, path, line, scope) -> list:
    findings = []
    seen = Counter(name for name, _ in walk.callbacks)
    for prim, n in sorted(seen.items()):
        rule, hazard = CALLBACK_PRIMS[prim]
        findings.append(Finding(
            rule=rule, path=path, line=line, scope=scope,
            message=(f"traced graph contains {n}x `{prim}` — {hazard}; "
                     f"hoist the host work out of the jitted region"),
        ))
    return findings


# ------------------------------------------------------------------- HV2xx


def _branch_collectives(branch) -> tuple:
    """Sorted multiset of (collective, axes) inside one cond branch."""
    sub = JaxprWalk(branch)
    return tuple(sorted(
        (name, axes) for name, axes in sub.collectives
        if name not in NON_COMMUNICATING
    ))


def _check_collectives(walk: JaxprWalk, entry, path, line, scope) -> list:
    findings = []
    comm = [(n, a) for n, a in walk.collectives
            if n not in NON_COMMUNICATING]
    used_axes = sorted({ax for _, axes in walk.collectives for ax in axes}
                       | {ax for axes in walk.shard_meshes for ax in axes})
    if entry.mesh is not None:
        declared = set(entry.mesh)
        ghost = [ax for ax in used_axes if ax not in declared]
        if ghost:
            findings.append(Finding(
                rule="HV201", path=path, line=line, scope=scope,
                message=(
                    f"traced collectives/meshes use axis "
                    f"{sorted(set(ghost))} but the entry declares mesh "
                    f"axes {sorted(declared)} — on the deployment mesh "
                    f"these collectives target a nonexistent axis"
                ),
            ))
    elif comm or walk.shard_meshes:
        what = sorted({n for n, _ in comm}) or ["shard_map"]
        findings.append(Finding(
            rule="HV203", path=path, line=line, scope=scope,
            message=(
                f"traced graph issues {what} over axes {used_axes} but "
                f"the entry is registered without a mesh= declaration — "
                f"declare the deployment mesh so axis names are checked"
            ),
        ))
    for eqn in walk.conds:
        branches = eqn.params.get("branches", ())
        sets = [_branch_collectives(b) for b in branches]
        if len({s for s in sets}) > 1:
            desc = " vs ".join(
                "[" + ", ".join(f"{n}{list(a)}" for n, a in s) + "]"
                for s in sets
            )
            findings.append(Finding(
                rule="HV202", path=path, line=line, scope=scope,
                message=(
                    f"cond/switch branches carry mismatched collectives "
                    f"({desc}) — devices taking different branches issue "
                    f"different collective sequences and the mesh "
                    f"deadlocks"
                ),
            ))
    return findings


# ------------------------------------------------------------------- HV3xx


def _check_donation(walk: JaxprWalk, entry, path, line, scope) -> list:
    findings = []
    donated_any = False
    for eqn in walk.pjits:
        donated = eqn.params.get("donated_invars", ())
        if not any(donated):
            continue
        donated_any = True
        inner = eqn.params.get("jaxpr")
        if inner is None:
            continue
        # an input returned unchanged stays in the nested jit's body: its
        # own invar shows up among its outvars, once per aliased result
        out_ids = Counter(id(v) for v in inner.jaxpr.outvars)
        out_avals = [v.aval for v in inner.jaxpr.outvars]
        for pos, (var, is_don) in enumerate(
                zip(inner.jaxpr.invars, donated)):
            if not is_don:
                continue
            aval = var.aval
            n_pass = out_ids.get(id(var), 0)
            if n_pass >= 2:
                findings.append(Finding(
                    rule="HV302", path=path, line=line, scope=scope,
                    message=(
                        f"donated argument {pos} ({_fmt_aval(aval)}) is "
                        f"returned as {n_pass} outputs — the donated "
                        f"buffer would alias multiple result buffers"
                    ),
                ))
            elif n_pass == 0 and not any(
                    _aval_match(aval, oa) for oa in out_avals):
                findings.append(Finding(
                    rule="HV301", path=path, line=line, scope=scope,
                    message=(
                        f"donated argument {pos} "
                        f"({_fmt_aval(aval)}) matches no output "
                        f"shape/dtype — XLA drops the donation silently "
                        f"and the buffer is copied, not reused"
                    ),
                ))
    if entry.donate and not donated_any:
        findings.append(Finding(
            rule="HV303", path=path, line=line, scope=scope,
            message=(
                "entry is registered with donate=True but the traced "
                "graph donates no buffers — the donate_argnums "
                "annotation was lost (wrapper re-jit without donation?)"
            ),
        ))
    return findings


def _aval_match(a, b) -> bool:
    return getattr(a, "shape", None) == getattr(b, "shape", ()) and \
        getattr(a, "dtype", None) == getattr(b, "dtype", None)


def _fmt_aval(a) -> str:
    dt = getattr(a, "dtype", None)
    return f"{getattr(dt, 'name', dt)}{list(getattr(a, 'shape', ()))}"
