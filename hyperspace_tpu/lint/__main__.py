"""CLI: ``python -m hyperspace_tpu.lint``.

Exit codes: 0 clean (new findings all absent), 1 new violations (or a
failed --trace check), 2 usage/internal error.  ``--sarif`` adds a
side-channel artifact and changes no exit code; ``--fix`` applies the
mechanical hygiene autofixes (``--fix --dry-run`` previews the diff)
and exits by the POST-fix finding count.
"""

from __future__ import annotations

import argparse
import os
import sys

from hyperspace_tpu.lint import engine


def _detect_root(explicit: str | None) -> str:
    if explicit:
        return os.path.abspath(explicit)
    cwd = os.getcwd()
    if os.path.exists(os.path.join(cwd, "hyperspace_tpu", "config.py")):
        return cwd
    # Fall back to the repo the installed package lives in.
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m hyperspace_tpu.lint",
        description="AST-based invariant checker for the hyperspace-tpu "
                    "contracts (docs/18-static-analysis.md)")
    p.add_argument("--root", default=None,
                   help="repo root (default: auto-detect)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule subset")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--json", action="store_true", help="machine output")
    p.add_argument("--baseline", default=None,
                   help=f"baseline path (default <root>/"
                        f"{engine.BASELINE_NAME})")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding as new")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline to the current findings "
                        "and exit 0")
    p.add_argument("--show-baselined", action="store_true")
    p.add_argument("--fix", action="store_true",
                   help="apply the mechanical hygiene autofixes (dead/"
                        "duplicate/redundant imports, mutable default "
                        "args), then relint")
    p.add_argument("--dry-run", action="store_true",
                   help="with --fix: print the unified diff, write "
                        "nothing")
    p.add_argument("--sarif", default=None, metavar="PATH",
                   help="also write findings as SARIF 2.1.0 (CI PR "
                        "annotation); exit codes unchanged")
    p.add_argument("--check-catalog", action="store_true",
                   help="run only the telemetry-catalog rule (the docs/16 "
                        "contract); combine with --trace")
    p.add_argument("--trace", default=None, metavar="JSONL",
                   help="also verify a JSONL trace: required span "
                        "kinds present, every span in the docs/16 taxonomy")
    args = p.parse_args(argv)

    from hyperspace_tpu.lint.rules import all_rules

    rules = all_rules()
    if args.list_rules:
        for r in rules:
            print(f"{r.name:22s} {r.description}")
        return 0

    rule_names = None
    if args.check_catalog:
        rule_names = ["telemetry-catalog"]
    elif args.rules:
        rule_names = [r.strip() for r in args.rules.split(",") if r.strip()]

    root = _detect_root(args.root)
    baseline_path = args.baseline or os.path.join(root, engine.BASELINE_NAME)
    baseline = set() if args.no_baseline \
        else engine.load_baseline(baseline_path)

    try:
        ctx = engine.build_context(root)
        findings, expired = engine.run_lint(root, rule_names, baseline,
                                            ctx=ctx)
    except ValueError as e:
        print(f"hslint: {e}", file=sys.stderr)
        return 2

    if args.fix:
        from hyperspace_tpu.lint import fix as fixer

        fixes = fixer.plan_fixes(ctx, findings)
        if args.dry_run:
            for fx in fixes:
                sys.stdout.write(fx.diff())
            print(f"hslint --fix --dry-run: {sum(len(f.applied) for f in fixes)} "
                  f"finding(s) fixable across {len(fixes)} file(s); "
                  f"nothing written")
            return 0
        fixer.apply_fixes(root, fixes)
        for fx in fixes:
            print(f"fixed {len(fx.applied)} finding(s) in {fx.relpath}")
        # Relint from disk: the exit code reflects the post-fix state,
        # and a fix that broke a file (syntax) surfaces immediately.
        ctx = engine.build_context(root)
        findings, expired = engine.run_lint(root, rule_names, baseline,
                                            ctx=ctx)

    if args.update_baseline:
        engine.write_baseline(baseline_path, findings)
        print(f"hslint: baseline rewritten with {len(findings)} "
              f"entr{'y' if len(findings) == 1 else 'ies'} at "
              f"{baseline_path}")
        return 0

    active = [r.name for r in rules] if rule_names is None else rule_names
    if args.sarif:
        from hyperspace_tpu.lint import sarif

        # A CI artifact at a user-chosen path, like the trace sink.
        # hslint: allow[io-seam] SARIF artifact, not index data
        with open(args.sarif, "w", encoding="utf-8") as f:
            f.write(sarif.render_sarif(
                findings, [r for r in rules if r.name in set(active)],
                root))
    trace_problems = []
    if args.trace:
        from hyperspace_tpu.lint import catalog

        _metrics, spans = catalog.telemetry_catalog(ctx)
        trace_problems = catalog.check_trace(args.trace, list(spans))

    if args.json:
        print(engine.render_json(findings, expired, active, root))
        if trace_problems:
            for prob in trace_problems:
                print(f"trace: {prob}", file=sys.stderr)
    else:
        shown = findings if args.show_baselined \
            else [f for f in findings if not f.baselined]
        if args.show_baselined:
            for f in shown:
                mark = " (baselined)" if f.baselined else ""
                print(f"{f.path}:{f.line}: [{f.rule}] {f.message}{mark}")
            new = [f for f in findings if not f.baselined]
            print(f"hslint: {len(new)} new, "
                  f"{len(findings) - len(new)} baselined")
            for fp in expired:
                print(f"expired baseline entry: {fp}")
        else:
            print(engine.render_human(findings, expired, active))
        for prob in trace_problems:
            print(f"trace: {prob}")

    new_count = sum(1 for f in findings if not f.baselined)
    return 1 if (new_count or trace_problems) else 0


if __name__ == "__main__":
    sys.exit(main())
