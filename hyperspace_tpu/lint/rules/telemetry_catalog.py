"""telemetry-catalog: metric and span names used in code must appear in
the docs/16-observability.md catalog, and vice versa.

Usage collection is AST-based: ``metrics.inc/observe/set_gauge(...)``
calls and ``span(...)`` / ``trace.span(...)`` / ``Span(...)`` openings.
F-string names become segment patterns (``f"rule.{slug}.applied"``
matches the catalog row ``rule.<slug>.applied``); a name with NO literal
segment is refused outside the dynamic-emitter allowlist below, because
a fully dynamic name can neither be checked nor capped by the catalog.

The reverse direction — a catalog row no code emits — is what the old
CI span-grep could never test: deleting an emission site used to leave
the doc row lying.
"""

from __future__ import annotations

import ast
import difflib
import fnmatch
from typing import Dict, List, Optional, Set, Tuple

from hyperspace_tpu.lint import catalog
from hyperspace_tpu.lint.engine import (
    Finding,
    LintContext,
    const_str,
    joined_pattern,
)

_SCAN_INCLUDE = ("hyperspace_tpu/",)
_SCAN_EXCLUDE = (
    "hyperspace_tpu/lint/",
    "hyperspace_tpu/telemetry/metrics.py",   # the registry itself
    "hyperspace_tpu/telemetry/trace.py",     # the span machinery itself
)

# Files allowed to emit metric names assembled from variables, with the
# concrete families they emit (counted as covering those catalog rows).
ALLOW_DYNAMIC: Dict[str, Tuple[str, ...]] = {
    # ByteBudgetLRU: one mechanism, two metric prefixes (docs/16).
    "hyperspace_tpu/execution/device_cache.py":
        ("cache.device.*", "serve.plan_cache.*"),
}

# Catalog rows computed, not emitted (metrics.snapshot() derives them).
DERIVED_METRICS = {"cache.device.hit_ratio"}

_METRIC_METHODS = {"inc", "observe", "set_gauge"}


def _display(name: str) -> str:
    return name.replace("\x00", "<?>")


def _extract_name(arg: ast.AST) -> Tuple[Optional[str], bool]:
    """(pattern-or-name, is_static).  ``is_static`` False means the arg
    was not a (f-)string literal at all."""
    s = const_str(arg)
    if s is not None:
        return s, True
    p = joined_pattern(arg)
    if p is not None:
        return p, True
    return None, False


class _Usage:
    __slots__ = ("name", "path", "line", "kind")

    def __init__(self, name: str, path: str, line: int, kind: str) -> None:
        self.name = name
        self.path = path
        self.line = line
        self.kind = kind  # "metric" | "span"


class Rule:
    name = "telemetry-catalog"
    description = ("metric/span names in code and the docs/16 catalog "
                   "agree in both directions")

    def run(self, ctx: LintContext) -> List[Finding]:
        metric_entries, span_entries = catalog.telemetry_catalog(ctx)
        findings: List[Finding] = []
        if not metric_entries or not span_entries:
            return [Finding(self.name, catalog.OBS_DOC_PATH, 1,
                            "could not parse the docs/16 metric/span tables",
                            ident="unparseable")]

        usages: List[_Usage] = []
        for src in ctx.py_files(include=_SCAN_INCLUDE,
                                exclude=_SCAN_EXCLUDE):
            if src.tree is None:
                continue
            dynamic_ok = src.relpath in ALLOW_DYNAMIC
            metric_bases, span_names, trace_bases = self._aliases(src.tree)
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call):
                    continue
                kind = None
                if isinstance(node.func, ast.Attribute):
                    base = node.func.value
                    if isinstance(base, ast.Name):
                        if node.func.attr in _METRIC_METHODS \
                                and base.id in metric_bases:
                            kind = "metric"
                        elif node.func.attr == "span" \
                                and base.id in trace_bases:
                            kind = "span"
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in span_names:
                    kind = "span"
                if kind is None or not node.args:
                    continue
                name, static = self._check_one(
                    src, node, kind, dynamic_ok, findings)
                if name is not None:
                    usages.append(_Usage(name, src.relpath,
                                         node.lineno, kind))

        self._forward(usages, metric_entries, span_entries, findings)
        self._reverse(usages, metric_entries, span_entries, findings)
        return findings

    # -- collection helpers --------------------------------------------------
    def _aliases(self, tree: ast.Module):
        """Per-file alias sets: names that reach the metrics module, the
        ``span``/``Span`` callables, and the trace module."""
        metric_bases = {"metrics"}
        span_names: Set[str] = set()
        trace_bases = {"trace"}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                if node.module.endswith("telemetry") or \
                        node.module.endswith("telemetry.metrics"):
                    for a in node.names:
                        if a.name == "metrics" or \
                                node.module.endswith(".metrics"):
                            metric_bases.add(a.asname or a.name)
                if node.module.endswith("telemetry.trace") or \
                        node.module.endswith("telemetry"):
                    for a in node.names:
                        if a.name in ("span", "Span"):
                            span_names.add(a.asname or a.name)
                        if a.name == "trace":
                            trace_bases.add(a.asname or a.name)
        return metric_bases, span_names, trace_bases

    def _check_one(self, src, node: ast.Call, kind: str, dynamic_ok: bool,
                   findings: List[Finding]):
        from hyperspace_tpu.lint.engine import enclosing_function_name

        name, static = _extract_name(node.args[0])
        if not static:
            if not dynamic_ok:
                fn = enclosing_function_name(src.tree, node.lineno)
                findings.append(Finding(
                    self.name, src.relpath, node.lineno,
                    f"{kind} name is a runtime expression — use a literal "
                    f"or an allowlisted dynamic emitter "
                    f"(docs/18-static-analysis.md)",
                    ident=f"dynamic:{kind}:{fn}"))
            return None, False
        if name is not None and "\x00" in name:
            segs = name.split(".")
            if all("\x00" in s for s in segs):
                if not dynamic_ok:
                    findings.append(Finding(
                        self.name, src.relpath, node.lineno,
                        f"fully dynamic {kind} name (no literal segment) — "
                        f"the catalog cannot check or bound it",
                        ident=f"dynamic:{kind}:{_display(name)}"))
                return None, False
        if dynamic_ok:
            return None, False  # vouched for by the allowlist families
        return name, True

    # -- checks --------------------------------------------------------------
    def _forward(self, usages, metric_entries, span_entries, findings):
        for u in usages:
            entries = metric_entries if u.kind == "metric" else span_entries
            if any(catalog.name_matches_entry(u.name, e) for e in entries):
                continue
            close = difflib.get_close_matches(
                _display(u.name), list(entries), n=1, cutoff=0.8)
            hint = f" (did you mean {close[0]!r}?)" if close else ""
            doc = "docs/16 metric catalog" if u.kind == "metric" \
                else "docs/16 span taxonomy"
            findings.append(Finding(
                self.name, u.path, u.line,
                f"{u.kind} name {_display(u.name)!r} is not in the {doc}"
                f"{hint}",
                ident=f"uncataloged:{u.kind}:{_display(u.name)}"))

    def _reverse(self, usages, metric_entries, span_entries, findings):
        dynamic_globs = [g for globs in ALLOW_DYNAMIC.values() for g in globs]
        for kind, entries in (("metric", metric_entries),
                              ("span", span_entries)):
            for entry, line in sorted(entries.items()):
                if kind == "metric" and entry in DERIVED_METRICS:
                    continue
                if any(u.kind == kind
                       and catalog.name_matches_entry(u.name, entry)
                       for u in usages):
                    continue
                if any(fnmatch.fnmatchcase(entry, g)
                       for g in dynamic_globs):
                    continue
                findings.append(Finding(
                    self.name, catalog.OBS_DOC_PATH, line,
                    f"docs/16 {kind} catalog entry {entry!r} has no "
                    f"emission site in code",
                    ident=f"unemitted:{kind}:{entry}"))
