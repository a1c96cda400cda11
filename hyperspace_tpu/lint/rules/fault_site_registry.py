"""fault-site-registry: fault-injection site strings must match the
declared ``SITES`` registry in ``io/faults.py`` — both directions.

A ``faults.check("stoer.put")`` typo is the worst kind of bug: the test
that armed the injector still passes (nothing fires), and the crash
matrix silently stops covering the site it thinks it covers.  The same
goes for ``FaultPlan(site=...)`` in tests.  Conversely, a registry site
no checkpoint ever calls is coverage theater.
"""

from __future__ import annotations

import ast
import difflib
from typing import Dict, List, Optional, Tuple

from hyperspace_tpu.lint import catalog
from hyperspace_tpu.lint.engine import Finding, LintContext, const_str

_SCAN_INCLUDE = ("hyperspace_tpu/", "tests/")
# tests/test_lint.py carries deliberately-typo'd fixture sites.
_SCAN_EXCLUDE = ("hyperspace_tpu/lint/", "tests/test_lint.py")

# faults.<fn>(...) -> positional index of the site argument.
_SITE_ARG = {"check": 0, "fire": 0, "net": 0, "corrupt_file": 0,
             "write_payload": 2, "atomic_replace": 2}


def _site_from_call(node: ast.Call) -> Optional[Tuple[str, str]]:
    """(site, how) for a fault-checkpoint call or FaultPlan(...), else
    None.  Non-literal site args (conf-driven) are skipped — the conf
    path is covered by the registry validation inside faults.py itself."""
    func = node.func
    attr = None
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name) \
            and func.value.id == "faults":
        attr = func.attr
    elif isinstance(func, ast.Name) and func.id in _SITE_ARG:
        attr = func.id
    if attr in _SITE_ARG:
        idx = _SITE_ARG[attr]
        arg = node.args[idx] if len(node.args) > idx else None
        for kw in node.keywords:
            if kw.arg == "site":
                arg = kw.value
        s = const_str(arg) if arg is not None else None
        return (s, attr) if s is not None else None
    ctor = func.id if isinstance(func, ast.Name) else (
        func.attr if isinstance(func, ast.Attribute) else None)
    if ctor == "FaultPlan":
        arg = node.args[0] if node.args else None
        for kw in node.keywords:
            if kw.arg == "site":
                arg = kw.value
        s = const_str(arg) if arg is not None else None
        return (s, "FaultPlan") if s is not None else None
    return None


class Rule:
    name = "fault-site-registry"
    description = ("faults.check/fire site strings match the declared "
                   "SITES registry in io/faults.py")

    def run(self, ctx: LintContext) -> List[Finding]:
        sites, reg_line = catalog.fault_sites(ctx)
        findings: List[Finding] = []
        if not sites:
            return [Finding(self.name, catalog.FAULTS_PATH, 1,
                            "io/faults.py declares no SITES registry",
                            ident="no-registry")]
        used: Dict[str, List[Tuple[str, int, str]]] = {}
        for src in ctx.py_files(include=_SCAN_INCLUDE,
                                exclude=_SCAN_EXCLUDE):
            if src.tree is None:
                continue
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call):
                    continue
                hit = _site_from_call(node)
                if hit is None:
                    continue
                site, how = hit
                used.setdefault(site, []).append(
                    (src.relpath, node.lineno, how))

        for site, hits in sorted(used.items()):
            if site in sites:
                continue
            close = difflib.get_close_matches(site, sites, n=1, cutoff=0.7)
            hint = f" (did you mean {close[0]!r}?)" if close else ""
            for path, line, how in hits:
                findings.append(Finding(
                    self.name, path, line,
                    f"fault site {site!r} ({how}) is not in the io/faults.py "
                    f"SITES registry — it will silently never fire{hint}",
                    ident=f"unknown-site:{site}"))

        # Checkpoint coverage only counts sites wired into the ENGINE
        # (tests arming a site don't make it real).
        engine_used = {s for s, hits in used.items()
                       if any(p.startswith("hyperspace_tpu/")
                              and how != "FaultPlan"
                              for p, _l, how in hits)}
        for site in sorted(sites - engine_used):
            findings.append(Finding(
                self.name, catalog.FAULTS_PATH, reg_line,
                f"registry site {site!r} has no faults checkpoint in the "
                f"engine — dead registry entry or missing instrumentation",
                ident=f"unused-site:{site}"))
        return findings
