"""hygiene: the ``_bvf``-class drift ADVICE.md keeps finding — duplicate
and redundant imports, dead module-level imports, import shadowing, and
mutable default arguments.

Checks (all scope-aware; the lazy function-level import idiom this
codebase uses to break cycles is NOT flagged unless the same binding
already exists at module level — then the lazy copy is pure noise):

  - duplicate import of the same binding twice in one scope;
  - function-level import that re-creates an identical module-level
    binding;
  - module-level import never referenced anywhere in the file
    (``__init__.py`` re-export surfaces are exempt);
  - module-level assignment that rebinds an imported name;
  - mutable default argument (``def f(x=[])``).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from hyperspace_tpu.lint.engine import Finding, LintContext

_SCAN_INCLUDE = ("hyperspace_tpu/", "run-tests.py", "tools/")
_SCAN_EXCLUDE = ()

Binding = Tuple[Optional[str], str, Optional[str]]  # (module, name, asname)


def _bindings(node) -> List[Binding]:
    if isinstance(node, ast.Import):
        return [(None, a.name, a.asname) for a in node.names]
    if isinstance(node, ast.ImportFrom):
        return [(node.module, a.name, a.asname) for a in node.names]
    return []


def _bound_name(b: Binding) -> str:
    module, name, asname = b
    if asname:
        return asname
    return name.split(".")[0] if module is None else name


class Rule:
    name = "hygiene"
    description = ("duplicate/dead imports, import shadowing, mutable "
                   "default args")

    def run(self, ctx: LintContext) -> List[Finding]:
        findings: List[Finding] = []
        for src in ctx.py_files(include=_SCAN_INCLUDE,
                                exclude=_SCAN_EXCLUDE):
            if src.tree is None:
                continue
            self._scan_file(src, findings)
        return findings

    def _scan_file(self, src, findings: List[Finding]) -> None:
        tree = src.tree
        module_bindings: Dict[Binding, int] = {}
        module_names: Dict[str, int] = {}

        # --- module scope: duplicates + shadowing ---------------------------
        self._scan_scope(src, tree.body, "<module>", module_bindings,
                         findings)
        for b, line in module_bindings.items():
            module_names.setdefault(_bound_name(b), line)

        for node in tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in module_names \
                            and node.lineno > module_names[t.id]:
                        findings.append(Finding(
                            self.name, src.relpath, node.lineno,
                            f"module-level assignment to {t.id!r} rebinds "
                            f"the import of the same name (line "
                            f"{module_names[t.id]})",
                            ident=f"shadow-import:{t.id}"))

        # --- function scopes ------------------------------------------------
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope_bindings: Dict[Binding, int] = {}
                self._scan_scope(src, node.body, node.name, scope_bindings,
                                 findings)
                for b, line in scope_bindings.items():
                    if b in module_bindings:
                        findings.append(Finding(
                            self.name, src.relpath, line,
                            f"{node.name}() re-imports "
                            f"{_bound_name(b)!r}, already imported at "
                            f"module level (line {module_bindings[b]})",
                            ident=f"redundant-import:{node.name}:"
                                  f"{_bound_name(b)}"))
                self._check_defaults(src, node, findings)

        # --- dead module-level imports --------------------------------------
        if not src.relpath.endswith("__init__.py"):
            self._check_dead(src, tree, module_bindings, findings)

    def _scan_scope(self, src, body, scope_name: str,
                    bindings: Dict[Binding, int],
                    findings: List[Finding]) -> None:
        """Collect import bindings of one scope (module body or one
        function body, nested defs excluded).  Duplicates are flagged
        only within one statement BLOCK — two lazy imports in mutually
        exclusive branches are fine; two in the same suite (the
        ``_bvf`` shape from ADVICE.md) are not."""

        def scan_block(block) -> None:
            block_bindings: Dict[Binding, int] = {}
            for node in block:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue
                if isinstance(node, (ast.Import, ast.ImportFrom)):
                    for b in _bindings(node):
                        if b[0] == "__future__":
                            continue
                        if b in block_bindings:
                            findings.append(Finding(
                                self.name, src.relpath, node.lineno,
                                f"duplicate import of {_bound_name(b)!r} "
                                f"in {scope_name} (first at line "
                                f"{block_bindings[b]})",
                                ident=f"dup-import:{scope_name}:"
                                      f"{_bound_name(b)}"))
                        else:
                            block_bindings[b] = node.lineno
                        if b not in bindings:
                            bindings[b] = node.lineno
                for attr in ("body", "orelse", "finalbody", "handlers"):
                    sub = getattr(node, attr, None)
                    if isinstance(sub, list):
                        if attr == "handlers":
                            for h in sub:
                                scan_block(h.body)
                        else:
                            scan_block(sub)

        scan_block(list(body))

    def _check_defaults(self, src, node, findings: List[Finding]) -> None:
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None]
        for d in defaults:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set,
                                     ast.ListComp, ast.DictComp,
                                     ast.SetComp))
            if isinstance(d, ast.Call) and isinstance(d.func, ast.Name) \
                    and d.func.id in ("list", "dict", "set") \
                    and not d.args and not d.keywords:
                mutable = True
            if mutable:
                findings.append(Finding(
                    self.name, src.relpath, d.lineno,
                    f"mutable default argument in {node.name}() — shared "
                    f"across calls; default to None and create inside",
                    ident=f"mutable-default:{node.name}"))

    def _check_dead(self, src, tree, module_bindings: Dict[Binding, int],
                    findings: List[Finding]) -> None:
        used: Set[str] = set()

        def use_annotation_string(value: str) -> None:
            # Quoted annotations ('-> "Tuple[np.ndarray, ...]"') hide
            # their names from the Name walk; parse them.
            try:
                expr = ast.parse(value, mode="eval")
            except SyntaxError:
                return
            for n in ast.walk(expr):
                if isinstance(n, ast.Name):
                    used.add(n.id)

        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                used.add(node.id)
            ann = None
            if isinstance(node, (ast.arg, ast.AnnAssign)):
                ann = node.annotation
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                ann = node.returns
            if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                use_annotation_string(ann.value)
        # __all__ strings export names without a Name node.
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets):
                if isinstance(node.value, (ast.List, ast.Tuple)):
                    for e in node.value.elts:
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, str):
                            used.add(e.value)
        for b, line in module_bindings.items():
            name = _bound_name(b)
            if name in used:
                continue
            # Deliberate side-effect imports carry `# noqa: F401` (the
            # flake8 convention already used here) or an hslint pragma.
            src_line = src.lines[line - 1] if line <= len(src.lines) else ""
            if "noqa" in src_line and \
                    ("F401" in src_line or "noqa:" not in src_line):
                continue
            findings.append(Finding(
                self.name, src.relpath, line,
                f"module-level import {name!r} is never used in this "
                f"file",
                ident=f"dead-import:{name}"))
