"""conf-registry: every ``hyperspace.*`` key literal must be declared in
config.py, wired into ``_FIELD_BY_KEY``, documented in docs/02, and
actually used — in both directions, so the three surfaces cannot drift:

  - a literal used anywhere (engine, tests, examples) that
    config.py does not declare is a typo'd or unregistered key — with
    near-miss suggestions, since ``conf.set`` raising ``KeyError`` at
    runtime is a far worse place to learn about it;
  - a declared key missing its docs/02 row is invisible to operators;
  - a docs/02 row for an undeclared key documents vapor;
  - a declared key no literal outside config.py ever mentions is dead
    weight (delete it, or baseline it with a reason if it is a
    compatibility placeholder).
"""

from __future__ import annotations

import ast
import difflib
from typing import Dict, List, Set, Tuple

from hyperspace_tpu.lint import catalog
from hyperspace_tpu.lint.engine import Finding, LintContext

# tests/test_lint.py is excluded: its fixture snippets deliberately
# contain typo'd keys (that's what they test).
_SCAN_EXCLUDE = (catalog.CONFIG_PATH, "hyperspace_tpu/lint/",
                 "tests/test_lint.py")


def _near_miss(key: str, declared) -> str:
    close = difflib.get_close_matches(key, declared, n=1, cutoff=0.8)
    return f" (did you mean {close[0]!r}?)" if close else ""


class Rule:
    name = "conf-registry"
    description = ("hyperspace.* conf keys agree across code, config.py, "
                   "and docs/02-configuration.md")

    def run(self, ctx: LintContext) -> List[Finding]:
        declared, wired, line_of, field_of = catalog.conf_registry(ctx)
        documented = catalog.documented_conf_keys(ctx)
        findings: List[Finding] = []
        if not declared:
            return [Finding(self.name, catalog.CONFIG_PATH, 1,
                            "could not parse the conf-key registry",
                            ident="unparseable")]

        # Three ways a key is "used" outside config.py: its string
        # literal, its constant name (NUM_BUCKETS), or its dataclass
        # field (conf.num_buckets / getattr(conf, "num_buckets", ...)).
        used: Dict[str, List[Tuple[str, int]]] = {}
        names_used: Set[str] = set()  # Name ids, Attribute attrs, strings
        for src in ctx.py_files(exclude=_SCAN_EXCLUDE):
            if src.tree is None:
                continue
            seen_here: Set[str] = set()
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    if catalog._CONF_KEY_RE.match(node.value):
                        if node.value not in seen_here:
                            seen_here.add(node.value)
                            used.setdefault(node.value, []).append(
                                (src.relpath, node.lineno))
                    else:
                        names_used.add(node.value)
                elif isinstance(node, ast.Name):
                    names_used.add(node.id)
                elif isinstance(node, ast.Attribute):
                    names_used.add(node.attr)

        for key, sites in sorted(used.items()):
            if key in declared:
                continue
            for path, line in sites:
                findings.append(Finding(
                    self.name, path, line,
                    f"conf key {key!r} is not declared in config.py"
                    f"{_near_miss(key, declared)}",
                    ident=f"undeclared:{key}"))

        for key, const in sorted(declared.items()):
            if key not in wired:
                findings.append(Finding(
                    self.name, catalog.CONFIG_PATH, line_of[key],
                    f"conf key {key!r} ({const}) is declared but not wired "
                    f"into _FIELD_BY_KEY (set()/get() raise KeyError on it)",
                    ident=f"unwired:{key}"))
            if key not in documented:
                findings.append(Finding(
                    self.name, catalog.CONFIG_PATH, line_of[key],
                    f"conf key {key!r} ({const}) has no row in "
                    f"docs/02-configuration.md",
                    ident=f"undocumented:{key}"))
            alive = key in used or const in names_used \
                or field_of.get(key) in names_used
            if not alive:
                findings.append(Finding(
                    self.name, catalog.CONFIG_PATH, line_of[key],
                    f"conf key {key!r} ({const}) is declared but neither "
                    f"its literal, its constant, nor its field "
                    f"({field_of.get(key, '?')}) is referenced outside "
                    f"config.py — dead key?",
                    ident=f"unused:{key}"))

        for key, line in sorted(documented.items()):
            if key not in declared:
                findings.append(Finding(
                    self.name, catalog.CONF_DOC_PATH, line,
                    f"docs/02 documents {key!r}, which config.py does not "
                    f"declare{_near_miss(key, declared)}",
                    ident=f"doc-undeclared:{key}"))
        return findings
