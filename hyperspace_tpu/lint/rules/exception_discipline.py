"""exception-discipline: no silent failure on the commit/serving paths.

Three checks:

  - **bare except** anywhere in the engine: catches
    ``KeyboardInterrupt``/``SystemExit`` — and this codebase models
    crashes as ``InjectedCrash(BaseException)`` precisely so cleanup
    code CANNOT swallow them; a bare except re-opens that hole.
  - **swallowed Exception** (``except Exception: pass`` and the
    BaseException variant) on the action-commit and serving hot paths:
    diagnostic side-writes may be fault-quiet, but an action or a
    served request that eats an error commits lies.  Elsewhere (e.g.
    the perf ledger, trace sinks) swallowing is the documented
    contract, so the scope is deliberate.
  - **wire-error taxonomy**: every literal ``ERR ...`` status line and
    every ``WireError(code, ...)`` in ``interop/`` must use a code
    declared by the ``ERR_*`` constants in server.py — a typo'd code
    silently downgrades a retryable shed to a permanent failure in
    every client.
"""

from __future__ import annotations

import ast
from typing import List

from hyperspace_tpu.lint import catalog
from hyperspace_tpu.lint.engine import (
    Finding,
    LintContext,
    const_str,
    enclosing_function_name,
)

_SCAN_INCLUDE = ("hyperspace_tpu/", "run-tests.py")
_SCAN_EXCLUDE = ("hyperspace_tpu/lint/",)

# Where `except Exception: pass` is a correctness bug, not a policy call.
_HOT_PATHS = (
    "hyperspace_tpu/actions/",
    "hyperspace_tpu/interop/",
    "hyperspace_tpu/index/",
    "hyperspace_tpu/dataset.py",
    "hyperspace_tpu/io/log_store.py",
)

_WIRE_SCAN = ("hyperspace_tpu/interop/",)


def _handler_names(handler: ast.ExceptHandler) -> List[str]:
    t = handler.type
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, ast.Tuple):
        return [e.id for e in t.elts if isinstance(e, ast.Name)]
    return []


def _in(path: str, prefixes) -> bool:
    return any(path == p or (p.endswith("/") and path.startswith(p))
               for p in prefixes)


class Rule:
    name = "exception-discipline"
    description = ("no bare except anywhere; no swallowed Exception on "
                   "commit/serving hot paths; ERR lines use the taxonomy")

    def run(self, ctx: LintContext) -> List[Finding]:
        findings: List[Finding] = []
        codes = catalog.wire_codes(ctx)
        for src in ctx.py_files(include=_SCAN_INCLUDE,
                                exclude=_SCAN_EXCLUDE):
            if src.tree is None:
                continue
            hot = _in(src.relpath, _HOT_PATHS)
            for node in ast.walk(src.tree):
                if isinstance(node, ast.ExceptHandler):
                    self._check_handler(src, node, hot, findings)
                elif isinstance(node, ast.Call) and codes:
                    self._check_wire(src, node, codes, findings)
            if codes and _in(src.relpath, _WIRE_SCAN):
                self._check_err_literals(src, codes, findings)
        return findings

    def _check_handler(self, src, node: ast.ExceptHandler, hot: bool,
                       findings: List[Finding]) -> None:
        fn = enclosing_function_name(src.tree, node.lineno)
        if node.type is None:
            findings.append(Finding(
                self.name, src.relpath, node.lineno,
                f"bare `except:` in {fn}() — catches SystemExit/"
                f"KeyboardInterrupt and the injector's InjectedCrash; "
                f"name the exception types",
                ident=f"bare-except:{fn}"))
            return
        if not hot:
            return
        names = _handler_names(node)
        swallows = ("Exception" in names or "BaseException" in names) \
            and len(node.body) == 1 and isinstance(node.body[0], ast.Pass)
        if swallows:
            findings.append(Finding(
                self.name, src.relpath, node.lineno,
                f"`except {'/'.join(names)}: pass` in {fn}() on a "
                f"commit/serving hot path swallows errors the caller "
                f"must see — handle, log via telemetry, or narrow the type",
                ident=f"swallow:{fn}"))

    def _check_wire(self, src, node: ast.Call, codes,
                    findings: List[Finding]) -> None:
        func = node.func
        ctor = func.id if isinstance(func, ast.Name) else (
            func.attr if isinstance(func, ast.Attribute) else None)
        if ctor != "WireError" or not node.args:
            return
        arg = node.args[0]
        lit = const_str(arg)
        if lit is not None and lit not in codes:
            findings.append(Finding(
                self.name, src.relpath, node.lineno,
                f"WireError code {lit!r} is not in the ERR_* taxonomy "
                f"({', '.join(sorted(codes))})",
                ident=f"wire-code:{lit}"))
        if isinstance(arg, ast.Name) and not arg.id.startswith("ERR_") \
                and arg.id not in ("code",):
            findings.append(Finding(
                self.name, src.relpath, node.lineno,
                f"WireError code should be an ERR_* constant, not "
                f"{arg.id!r}",
                ident=f"wire-code-var:{arg.id}"))

    def _check_err_literals(self, src, codes,
                            findings: List[Finding]) -> None:
        """Literal ``"ERR <word> ..."`` strings (plain or f-string heads)
        must lead with a taxonomy code or an interpolated expression."""
        if src.tree is None:
            return
        for node in ast.walk(src.tree):
            head = None
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                head = node.value
            elif isinstance(node, ast.JoinedStr) and node.values and \
                    isinstance(node.values[0], ast.Constant) and \
                    isinstance(node.values[0].value, str):
                head = node.values[0].value
            if head is None or not head.startswith("ERR "):
                continue
            rest = head[4:]
            if not rest:
                continue  # code comes from an interpolated expression
            word = rest.split()[0] if rest.split() else ""
            if word and word.isupper() and word not in codes:
                findings.append(Finding(
                    self.name, src.relpath, node.lineno,
                    f"wire status literal starts 'ERR {word}', which is "
                    f"not a taxonomy code ({', '.join(sorted(codes))})",
                    ident=f"err-literal:{word}"))
