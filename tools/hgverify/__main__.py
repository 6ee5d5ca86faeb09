"""CLI: ``python -m tools.hgverify [--update-costs] [--only HV4] ...``.

Exit status: 0 no findings · 1 findings · 2 usage error (argparse) · 3
analyzer crash — the same crash-vs-finding contract as ``tools.hglint``,
so ``tools/verify.sh`` surfaces analyzer bugs as infrastructure failures.

The trace environment is pinned before JAX's backend initializes: CPU
platform, 8 forced host devices — matching the test harness, so the
committed ``costs.json`` numbers are reproducible everywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def _pin_trace_env() -> None:
    """Must run before the first backend touch. Nothing imports jax
    ahead of this in the current installation (it has no sitecustomize);
    the config update below covers a caller that already imported jax —
    backend init is lazy, so both routes hold until the first touch."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:  # pragma: no cover - jax import error surfaces later
        pass


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="hgverify",
        description="jaxpr-level ground-truth contract verification and "
                    "static cost-model regression gate over the "
                    "registered kernel entry points",
    )
    p.add_argument("--costs", metavar="FILE", default=None,
                   help="cost budget file "
                        "(default: tools/hgverify/costs.json)")
    p.add_argument("--update-costs", action="store_true",
                   help="rewrite the budget file from current "
                        "measurements (accepting cost changes), then "
                        "report remaining findings")
    p.add_argument("--tolerance", metavar="FRAC", type=float, default=None,
                   help="relative cost drift tolerance for HV401 "
                        "(default 0.15 = ±15%%)")
    p.add_argument("--only", metavar="PREFIXES", default=None,
                   help="comma-separated rule-id prefixes to report "
                        "(e.g. 'HV4' or 'HV1,HV301'); HV100 always "
                        "surfaces")
    p.add_argument("--concord", action="store_true",
                   help="diff jaxpr ground truth against hglint's AST "
                        "predictions on the entry modules")
    p.add_argument("--concord-paths", metavar="PATHS",
                   default="hypergraphdb_tpu",
                   help="comma-separated hglint paths for --concord")
    p.add_argument("--output", choices=("text", "json"), default="text",
                   help="'json' emits the full machine-readable report")
    p.add_argument("--severity", choices=("error", "warning", "info"),
                   default=None,
                   help="only report findings at this severity")
    args = p.parse_args(argv)

    from tools.hgverify.model import parse_only

    try:
        parse_only(args.only)   # validate prefixes up front
    except ValueError as e:
        p.error(str(e))         # usage error: exit 2

    _pin_trace_env()

    try:
        from tools.hgverify import concord as concord_mod
        from tools.hgverify import engine

        findings, meta = engine.run_verify(
            costs_path=args.costs, only=args.only,
            tolerance=args.tolerance, update_costs=args.update_costs,
        )
        if args.severity:
            findings = [f for f in findings
                        if f.severity == args.severity]
        table = None
        if args.concord:
            # cross-tabulate against the FULL ground truth — --only /
            # --severity filter the report, never the concordance
            table = concord_mod.concord(
                meta["traces"], meta["all_findings"],
                [s for s in args.concord_paths.split(",") if s],
            )
    except Exception:
        traceback.print_exc(file=sys.stderr)
        print("hgverify: internal analyzer crash (exit 3) — this is a "
              "verifier bug, not a finding", file=sys.stderr)
        return 3

    if args.output == "json":
        print(json.dumps(engine.build_report(
            findings, meta, only=args.only, concordance=table,
        ), indent=2))
    else:
        for f in findings:
            print(f.render())
        if args.update_costs:
            print(f"hgverify: wrote cost budgets for {meta['traced']} "
                  f"entries to {meta['costs_path']}")
        print(f"hgverify: {engine.summarize(findings, meta)}")
        if table is not None:
            print(concord_mod.render(table))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
