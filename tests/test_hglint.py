"""Tier-1 gate + precision pins for the hglint static analyzer.

Two jobs:

1. pin analyzer precision against the checked-in fixture sets —
   ``hglint_fixtures/bad_pkg`` (every seeded hazard must be flagged) and
   ``hglint_fixtures/clean_pkg`` (zero findings allowed);
2. act as the repo gate: ``hypergraphdb_tpu`` linted against
   ``tools/hglint/baseline.json`` must produce no NEW findings, so a PR
   that introduces a fresh host-sync/retrace/Pallas/lock hazard fails
   tier-1.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.hglint import (  # noqa: E402
    RULES,
    apply_baseline,
    baseline_counts,
    load_baseline,
    run_lint,
    write_baseline,
)

FIXTURES = Path(__file__).parent / "hglint_fixtures"
BASELINE = REPO / "tools" / "hglint" / "baseline.json"


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------ bad fixtures


def test_bad_fixture_flags_every_family():
    findings = run_lint([str(FIXTURES / "bad_pkg")])
    rules = _rules(findings)
    # family 1: host-sync-in-traced-code, every spelling, + donation (106)
    # and host-numpy upload (107)
    assert {"HG101", "HG102", "HG103", "HG104", "HG105",
            "HG106", "HG107"} <= rules
    # family 2: retrace hazards
    assert {"HG201", "HG202", "HG203", "HG204"} <= rules
    # family 3: Pallas contracts
    assert {"HG301", "HG302", "HG303", "HG304"} <= rules
    # family 4: lock order + contract discipline
    assert {"HG401", "HG402", "HG403"} <= rules
    # family 5: VMEM budgets (incl. scalar-prefetch SMEM)
    assert {"HG501", "HG502", "HG503"} <= rules
    # family 6: shard_map collective consistency (incl. cond branches)
    assert {"HG601", "HG602", "HG603", "HG604"} <= rules
    assert len(findings) >= 8  # acceptance floor; actual seed is larger


def test_taint_flows_through_call_graph():
    """block_until_ready lives in an UNdecorated helper; it must be flagged
    because a jit root calls the helper."""
    findings = run_lint([str(FIXTURES / "bad_pkg" / "hostsync.py")])
    hits = [f for f in findings if f.rule == "HG105"]
    assert len(hits) == 1
    assert hits[0].scope == "_helper_sync"
    assert "bad_transitive" in hits[0].message


def test_pallas_out_of_bounds_and_arity():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "pallas_bad.py")])
    msgs = [f.message for f in findings if f.rule == "HG302"]
    assert any("out of bounds" in m for m in msgs)
    assert any("grid has rank 2" in m for m in msgs)


# ------------------------------------------------------------ vmem fixtures


def test_vmem_overflow_and_unresolvable_are_distinct():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "vmem_bad.py")])
    by_rule = {f.rule: f for f in findings if f.rule.startswith("HG5")}
    assert set(by_rule) == {"HG501", "HG502"}
    assert "exceeds" in by_rule["HG501"].message
    assert by_rule["HG501"].scope == "overflow"
    assert "not statically resolvable" in by_rule["HG502"].message
    assert by_rule["HG502"].scope == "unresolvable"


def test_vmem_budget_is_configurable():
    # the 32 MiB fixture passes under a 64 MiB budget; the resolvable-but-
    # small spec never flags
    findings = run_lint(
        [str(FIXTURES / "bad_pkg" / "vmem_bad.py")], vmem_budget=64 << 20
    )
    assert [f for f in findings if f.rule == "HG501"] == []


def test_vmem_pragma_suppresses_hg502():
    # clean_pkg/vmem_ok.py contains a genuinely unresolvable pallas_call
    # annotated with `# hglint: disable=HG502` — covered by the clean
    # sweep, pinned here so the pragma path has a dedicated failure mode
    findings = run_lint([str(FIXTURES / "clean_pkg" / "vmem_ok.py")])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_smem_scalar_prefetch_budget():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "smem_bad.py")])
    hits = [f for f in findings if f.rule == "HG503"]
    assert len(hits) == 1
    assert "SMEM" in hits[0].message and hits[0].scope == "smem_overflow"
    # the fitting twin (the pallas_gather SEG contract) stays silent
    ok = run_lint([str(FIXTURES / "clean_pkg" / "smem_ok.py")])
    assert [f for f in ok if f.rule == "HG503"] == []


def test_fused_bfs_kernel_window_fixtures():
    """The window math of a fused pull-BFS hop kernel, on two
    self-contained fixtures (the kernel they were shaped after left the
    tree at PR 29; HG501/HG503 are what is tested): a scalar-prefetched
    chunk plan overflowing SMEM and a scratch+window set overflowing VMEM
    are both caught; the twin that fits folds clean."""
    findings = run_lint([str(FIXTURES / "bad_pkg" / "fusedbfs_bad.py")])
    by_rule = {f.rule: f for f in findings}
    assert set(by_rule) == {"HG501", "HG503"}
    assert by_rule["HG503"].scope == "fused_hop_smem_overflow"
    assert by_rule["HG501"].scope == "fused_hop_vmem_overflow"
    ok = run_lint([str(FIXTURES / "clean_pkg" / "fusedbfs_ok.py")])
    assert ok == [], "\n".join(f.render() for f in ok)


def test_shapes_fold_through_scan_and_vmap():
    """ShapeDtype propagates through lax.scan carries and jax.vmap
    results: the wrapshape fixtures' None block dims fold, so overflows
    surface as HG501 (not the weaker HG502), and the fitting twins fold
    clean (no HG502 either)."""
    findings = run_lint([str(FIXTURES / "bad_pkg" / "wrapshape_bad.py")])
    by_scope = {f.scope: f.rule for f in findings
                if f.rule.startswith("HG5")}
    assert by_scope == {"scan_carried_overflow": "HG501",
                        "vmap_result_overflow": "HG501"}
    ok = run_lint([str(FIXTURES / "clean_pkg" / "wrapshape_ok.py")])
    assert [f for f in ok if f.rule.startswith("HG5")] == []


# ------------------------------------------------------ collective fixtures


def test_collective_axis_and_divergence_flagged():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "collectives_bad.py")])
    rules = {f.rule: f for f in findings}
    assert {"HG601", "HG602", "HG603"} <= set(rules)
    assert "'ghost'" in rules["HG601"].message
    assert "deadlock" in rules["HG602"].message
    assert rules["HG602"].scope == "_diverging_body"
    assert "'model'" in rules["HG603"].message
    assert rules["HG603"].scope == "_mismatch_helper"


def test_collectives_clean_region_is_silent():
    findings = run_lint([str(FIXTURES / "clean_pkg" / "collectives_ok.py")])
    assert [f for f in findings if f.rule.startswith("HG6")] == []


def test_cond_branch_collective_mismatch_flagged():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "condcoll_bad.py")])
    hits = [f for f in findings if f.rule == "HG604"]
    by_scope = {f.scope: f for f in hits}
    # _helper_body: the mismatched psum hides one call deep — the branch
    # scan must follow resolvable helpers in both directions
    assert set(by_scope) == {"_cond_body", "_switch_body", "_helper_body"}
    assert "mismatched collectives" in by_scope["_cond_body"].message
    # identical-psum branches must stay silent — including a branch that
    # routes the SAME psum through a helper
    ok = run_lint([str(FIXTURES / "clean_pkg" / "condcoll_ok.py")])
    assert [f for f in ok if f.rule == "HG604"] == []


def test_decorator_args_are_host_scope(tmp_path):
    """Decorator expressions of a module-level jitted function execute at
    import (host) — numpy work there must NOT be flagged as traced; the
    same hazard on a def NESTED inside a jit root executes under tracing
    and must be flagged."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "m.py").write_text(
        "import jax\n"
        "import numpy as np\n\n\n"
        "def _register(table):\n"
        "    def deco(fn):\n"
        "        return fn\n"
        "    return deco\n\n\n"
        "@_register(table=np.arange(8))\n"
        "@jax.jit\n"
        "def host_decorated(x):\n"
        "    return x * 2\n"
    )
    assert run_lint([str(pkg)]) == [], "host-side decorator arg flagged"
    (pkg / "m.py").write_text(
        "import jax\n"
        "import numpy as np\n\n\n"
        "def _register(table):\n"
        "    def deco(fn):\n"
        "        return fn\n"
        "    return deco\n\n\n"
        "@jax.jit\n"
        "def traced(x):\n"
        "    @_register(table=np.arange(8))\n"
        "    def inner(y):\n"
        "        return y\n"
        "    return inner(x)\n"
    )
    rules = {f.rule for f in run_lint([str(pkg)])}
    assert "HG103" in rules, "traced nested-def decorator arg missed"


# -------------------------------------------------------- donation fixtures


def test_donated_buffer_reuse_flagged():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "donation_bad.py")])
    hits = [f for f in findings if f.rule == "HG106"]
    by_scope = {f.scope: f for f in hits}
    assert set(by_scope) == {"read_after_donate", "loop_donate",
                             "branch_test_read", "iter_read"}
    assert len(hits) == 4
    assert "donated to `_update`" in by_scope["read_after_donate"].message
    assert "next loop iteration" in by_scope["loop_donate"].message
    # reads hiding in a branch condition / loop iterator are still reads
    assert "donated to `_update`" in by_scope["branch_test_read"].message
    assert "donated to `_update`" in by_scope["iter_read"].message


def test_donation_rebind_idiom_is_silent():
    findings = run_lint([str(FIXTURES / "clean_pkg" / "donation_ok.py")])
    assert [f for f in findings if f.rule == "HG106"] == []


# --------------------------------------------------------- asarray fixtures


def test_host_numpy_upload_flagged():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "asarray_bad.py")])
    hits = [f for f in findings if f.rule == "HG107"]
    assert len(hits) == 2
    assert any("_TABLE" in f.message for f in hits)
    assert any("mask" in f.message for f in hits)


# ------------------------------------------------------------ lock fixtures


def test_lock_cycle_flagged():
    findings = run_lint([str(FIXTURES / "bad_pkg" / "locks_cycle.py")])
    cycles = [f for f in findings if f.rule == "HG401"]
    assert len(cycles) == 1
    assert "lock_a" in cycles[0].message and "lock_b" in cycles[0].message


def test_clean_two_lock_module_not_flagged():
    findings = run_lint([str(FIXTURES / "clean_pkg" / "locks_ok.py")])
    assert [f for f in findings if f.rule.startswith("HG4")] == []


def test_locked_contract_violation_flagged():
    # inverse *_locked contract: a `_locked` leaf invoked from a caller
    # that provably holds NO registered lock
    findings = run_lint([str(FIXTURES / "bad_pkg" / "locks_cycle.py")])
    (hit,) = [f for f in findings if f.rule == "HG403"]
    assert hit.line == 49 and hit.scope == "Journal.drain_fast"
    assert "_append_locked" in hit.message
    assert "holding no registered lock" in hit.message


# ------------------------------------------------------------ clean fixtures


def test_clean_fixture_is_silent():
    findings = run_lint([str(FIXTURES / "clean_pkg")])
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------------------------- repo gate


def test_repo_gate_passes_with_baseline(monkeypatch):
    """The tier-1 contract: hypergraphdb_tpu linted against the checked-in
    baseline reports zero NEW findings."""
    monkeypatch.chdir(REPO)  # baseline keys are repo-root-relative
    findings = run_lint(["hypergraphdb_tpu"])
    baseline = load_baseline(str(BASELINE))
    fresh = apply_baseline(findings, baseline)
    assert fresh == [], (
        "new hglint findings (fix them or regenerate the baseline via "
        "`python -m tools.hglint hypergraphdb_tpu --write-baseline "
        "tools/hglint/baseline.json`):\n"
        + "\n".join(f.render() for f in fresh)
    )


def test_repo_baseline_is_not_stale(monkeypatch):
    """Every baseline entry must still correspond to a live finding —
    otherwise fixed hazards stay suppressed forever."""
    monkeypatch.chdir(REPO)
    live = baseline_counts(run_lint(["hypergraphdb_tpu"]))
    baseline = load_baseline(str(BASELINE))
    stale = {
        k: (v, live.get(k, 0))
        for k, v in baseline.items()
        if live.get(k, 0) < v
    }
    assert stale == {}, f"baseline entries with no live finding: {stale}"


# ------------------------------------------------------------- baseline io


def test_baseline_roundtrip(tmp_path):
    findings = run_lint([str(FIXTURES / "bad_pkg")])
    path = tmp_path / "baseline.json"
    write_baseline(findings, str(path))
    loaded = load_baseline(str(path))
    assert loaded == baseline_counts(findings)
    # everything baselined -> nothing new
    assert apply_baseline(findings, loaded) == []
    # dropping one entry resurfaces exactly that finding count
    key, n = next(iter(sorted(loaded.items())))
    partial = dict(loaded)
    partial[key] = n - 1
    fresh = apply_baseline(findings, partial)
    assert len(fresh) == 1 and fresh[0].baseline_key == key


def test_rule_registry_consistency():
    findings = run_lint([str(FIXTURES / "bad_pkg")])
    assert _rules(findings) <= set(RULES), "finding with unregistered rule id"


_BAD_SNIPPET = '''\
import jax


@jax.jit
def f(x):
    return x.item()
'''

_FIXED_SNIPPET = '''\
import jax


@jax.jit
def f(x):
    return x
'''


def test_baseline_lifecycle_staleness_forces_removal(tmp_path):
    """The full suppression lifecycle: a finding appears, gets baselined
    (gate passes), the hazard is FIXED — and the staleness check must then
    reject the baseline entry so the suppression cannot outlive the bug."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "hot.py"
    bl = tmp_path / "baseline.json"

    # 1. the hazard appears
    mod.write_text(_BAD_SNIPPET)
    findings = run_lint([str(pkg)])
    assert [f.rule for f in findings] == ["HG101"]

    # 2. it is baselined: the gate goes quiet
    write_baseline(findings, str(bl))
    loaded = load_baseline(str(bl))
    assert apply_baseline(run_lint([str(pkg)]), loaded) == []

    # 3. the hazard is fixed but the baseline still carries the entry:
    #    the staleness check (mirrors test_repo_baseline_is_not_stale)
    #    must flag it for removal
    mod.write_text(_FIXED_SNIPPET)
    live = baseline_counts(run_lint([str(pkg)]))
    stale = {k: v for k, v in loaded.items() if live.get(k, 0) < v}
    assert stale, "fixed hazard left no stale baseline entry to remove"

    # 4. removing the stale entry closes the loop: gate still clean
    pruned = {k: v for k, v in loaded.items() if k not in stale}
    assert apply_baseline(run_lint([str(pkg)]), pruned) == []


# ---------------------------------------------------------------- filters


def test_only_family_filter():
    all_f = run_lint([str(FIXTURES / "bad_pkg")])
    vmem_only = run_lint([str(FIXTURES / "bad_pkg")], only="HG5")
    assert vmem_only and all(f.rule.startswith("HG5") for f in vmem_only)
    assert len(vmem_only) < len(all_f)
    multi = run_lint([str(FIXTURES / "bad_pkg")], only="HG5,HG601")
    assert {f.rule for f in multi} <= {"HG501", "HG502", "HG503", "HG601"}
    assert any(f.rule == "HG601" for f in multi)


def test_only_typo_refuses_silent_green():
    # a prefix matching no rule must raise, not skip every runner and
    # report a clean run
    with pytest.raises(ValueError, match="matches no known rule"):
        run_lint([str(FIXTURES / "bad_pkg")], only="HG0")
    with pytest.raises(ValueError, match="matches no known rule"):
        run_lint([str(FIXTURES / "bad_pkg")], only="hg5")  # case-sensitive


def test_pragma_disables_named_rule_only(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "m.py").write_text(
        "import jax\n\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()  # hglint: disable=HG101\n"
    )
    assert run_lint([str(pkg)]) == []
    # a pragma for a DIFFERENT rule must not suppress the finding
    (pkg / "m.py").write_text(
        "import jax\n\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()  # hglint: disable=HG999\n"
    )
    assert [f.rule for f in run_lint([str(pkg)])] == ["HG101"]


# ------------------------------------------------------------------- CLI


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", str(REPO))
    bad = subprocess.run(
        [sys.executable, "-m", "tools.hglint",
         str(FIXTURES / "bad_pkg")],
        cwd=REPO, capture_output=True, text=True, env=env,
    )
    assert bad.returncode == 1
    assert "HG101" in bad.stdout
    clean = subprocess.run(
        [sys.executable, "-m", "tools.hglint",
         str(FIXTURES / "clean_pkg")],
        cwd=REPO, capture_output=True, text=True, env=env,
    )
    assert clean.returncode == 0

    out = subprocess.run(
        [sys.executable, "-m", "tools.hglint", str(FIXTURES / "bad_pkg"),
         "--json"],
        cwd=REPO, capture_output=True, text=True, env=env,
    )
    data = json.loads(out.stdout)
    assert isinstance(data, list) and len(data) >= 8
    assert {"rule", "severity", "path", "line", "scope", "message"} <= set(
        data[0]
    )
