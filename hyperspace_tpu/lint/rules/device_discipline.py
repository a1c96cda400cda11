"""device-discipline: the device hot path (``ops/``, ``execution/``,
``parallel/``) never syncs to host unannounced.

ROADMAP items 1-2 restructure exactly these modules; this rule makes the
invariants the PR 11 timeline profiler can only *measure* into statically
*enforced* contracts.  Checks:

  - **implicit-sync** — ``float()/int()/bool()``, ``.item()/.tolist()``,
    ``np.asarray()/np.array()``, or an ``if``/``while`` test on a value
    the taint analysis proves device-resident.  Each is a blocking
    device→host transfer the profiler cannot attribute.  The sanctioned
    forms are ``sync_guard.pull(x, site)`` / ``sync_guard.scalar(x,
    site)`` (execution/sync_guard.py — attributed, guard-audited, and
    ``exec.transfer.d2h``-counted) or an ALLOW entry below.
  - **device-loop** — a Python ``for`` loop iterating a device array:
    every element access is its own transfer.
  - **untimed-sync** — a raw ``block_until_ready`` outside the
    ``timeline.kernel_begin/kernel_end`` seams: it stalls the host with
    no ``exec.kernel.*.device_ms`` attribution.
  - **float64-literal** — an explicit float64 dtype outside a
    ``with _enable_x64():`` region: under the 32-bit default the
    x64 shim exists to scope, it silently downcasts (the grouped-
    aggregate 1e-6 relative error from PR 1).
  - **jit-unsafe** — inside a ``jax.jit``-decorated function: conf/env/
    clock reads (traced once, then baked stale into the compiled
    program) and mutable default arguments (unhashable static args
    poison the jit cache); at call sites of jitted functions, a literal
    list/dict/set passed in a ``static_argnames`` position (cache-
    busting unhashable static).

Device taint is interprocedural: a function whose return value is
device-resident (directly, through a jit-decorated callee, or through
another device-returning function — fixpoint over the lint/callgraph.py
edges) taints its callers' locals.  Calls whose result is bound inside a
``with _enable_x64():`` block are also treated as device values — in
this codebase the scoped-x64 shim brackets exactly the device compute
regions.

Legitimate boundary sites (the ONE dynamic-shape sync a kernel needs,
a host mirror that accepts either residency) are registered in ALLOW
below with a reason, or carry an inline
``# hslint: allow[device-discipline] <reason>`` pragma.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from hyperspace_tpu.lint import callgraph
from hyperspace_tpu.lint.engine import Finding, LintContext, call_name

_SCAN_INCLUDE = (
    "hyperspace_tpu/ops/",
    "hyperspace_tpu/execution/",
    "hyperspace_tpu/parallel/",
)
_SCAN_EXCLUDE = (
    # The attributed-conversion seam itself: its pulls are the product.
    "hyperspace_tpu/execution/sync_guard.py",
)

# (path, function qualname, check) -> reason.  The registry is the
# reviewable list of every sanctioned raw sync left in the hot path;
# prefer sync_guard.pull/scalar at new sites (docs/18).
ALLOW: Dict[Tuple[str, str, str], str] = {
    ("hyperspace_tpu/ops/aggregate.py", "_segment_reduce",
     "float64-literal"):
        "mean accumulates in f64 by design; the kernel is only ever "
        "traced under grouped_aggregate's scoped-x64 region, so the "
        "dtype survives",
}

# jax/jnp callables that do NOT produce device arrays.
_JAX_HOST_CALLS = {
    "jax.device_get", "jax.jit", "jax.local_devices", "jax.devices",
    "jax.default_backend", "jax.tree_util.tree_leaves",
    "jax.tree_util.tree_map", "jax.process_index",
    "jax.transfer_guard_device_to_host",
    "jnp.issubdtype", "jnp.iinfo", "jnp.finfo", "jnp.dtype",
}

# Builtins whose result is never a device array even in an x64 region.
_HOST_BUILTINS = {
    "int", "float", "bool", "len", "tuple", "list", "dict", "set",
    "min", "max", "sum", "abs", "range", "zip", "enumerate", "sorted",
    "isinstance", "getattr", "hasattr", "str", "repr", "print", "round",
    "id", "type", "iter", "next", "divmod",
}

_CONVERT_BUILTINS = {"float", "int", "bool"}
_CONVERT_METHODS = {"item", "tolist"}
_NP_CONVERTERS = {"np.asarray", "np.array", "numpy.asarray",
                  "numpy.array"}
_SANCTIONED_SUFFIXES = ("sync_guard.pull", "sync_guard.scalar")
# Methods on a device array that stay on device.
_DEVICE_METHODS_KEEP = {"astype", "reshape", "sum", "min", "max", "any",
                        "all", "at", "set", "add", "block_until_ready",
                        "copy", "squeeze", "ravel", "flatten"}
_JIT_BANNED_CALLS = {"os.getenv", "time.time", "time.monotonic",
                     "time.monotonic_ns", "time.perf_counter", "open",
                     "use_pallas"}


def _x64_spans(tree: ast.Module) -> List[Tuple[int, int]]:
    """(start, end) line spans of ``with _enable_x64():`` blocks."""
    spans = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if isinstance(item.context_expr, ast.Call):
                    name = call_name(item.context_expr)
                    if name.endswith("enable_x64"):
                        spans.append((node.lineno,
                                      getattr(node, "end_lineno",
                                              node.lineno)))
    return spans


def _in_spans(line: int, spans: List[Tuple[int, int]]) -> bool:
    return any(a <= line <= b for a, b in spans)


class _Taint(ast.NodeVisitor):
    """Per-function forward taint pass: which local names are provably
    device arrays, given ``device_fids`` (the interprocedural
    fixpoint's current device-returning function set)."""

    def __init__(self, rule: "Rule", graph, index_path: str,
                 info, device_fids: Set[str],
                 x64_spans: List[Tuple[int, int]],
                 collect=None) -> None:
        self.rule = rule
        self.graph = graph
        self.path = index_path
        self.info = info
        self.device_fids = device_fids
        self.x64_spans = x64_spans
        self.tainted: Set[str] = set()
        self.returns_device = False
        self.collect = collect  # List[Finding] when checking; None on
        # the fixpoint pre-passes

    # -- expression taint ---------------------------------------------------
    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.Compare):
            return self.is_tainted(node.left) or \
                any(self.is_tainted(c) for c in node.comparators)
        if isinstance(node, ast.Call):
            return self._call_is_device(node)
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or \
                self.is_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_tainted(e) for e in node.elts)
        return False

    def _call_is_device(self, node: ast.Call) -> bool:
        raw = call_name(node)
        if not raw:
            # method call on a non-name chain — device iff receiver is
            if isinstance(node.func, ast.Attribute):
                return self.is_tainted(node.func.value) and \
                    node.func.attr in _DEVICE_METHODS_KEEP
            return False
        if raw in _JAX_HOST_CALLS or any(
                raw.endswith(s) for s in _SANCTIONED_SUFFIXES):
            return False
        if raw.startswith("jnp.") or raw.startswith("jax."):
            return True
        # Method chain on a tainted receiver (rk.astype(...), w[:, 1]).
        if "." in raw:
            head = raw.split(".")[0]
            attr = raw.rsplit(".", 1)[1]
            if head in self.tainted and attr in _DEVICE_METHODS_KEEP:
                return True
        targets = self.graph._resolve(
            self.graph._indexes[self.path], self.info, raw)
        if targets:
            # Trust in-package resolution: device iff the callee is in
            # the fixpoint's device-returning set.
            return any(t in self.device_fids for t in targets)
        # Unresolved PLAIN-NAME call bound inside a scoped-x64 region
        # (a compiled-predicate callable, a shard-mapped program): the
        # shim brackets device compute, so treat the result as device.
        # Method calls on known-host locals stay host.
        if _in_spans(node.lineno, self.x64_spans) and \
                isinstance(node.func, ast.Name) and \
                raw not in _HOST_BUILTINS:
            return True
        return False

    # -- statements ---------------------------------------------------------
    def _bind(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, tainted)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, tainted)

    def visit_Assign(self, node: ast.Assign) -> None:
        self._check_expr(node.value)
        t = self.is_tainted(node.value)
        for target in node.targets:
            self._bind(target, t)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_expr(node.value)
            self._bind(node.target, self.is_tainted(node.value))

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_expr(node.value)
        if self.is_tainted(node.value):
            self._bind(node.target, True)

    def visit_Return(self, node: ast.Return) -> None:
        if node.value is not None:
            self._check_expr(node.value)
            if self.is_tainted(node.value):
                self.returns_device = True

    def visit_For(self, node: ast.For) -> None:
        self._check_expr(node.iter)
        if self.collect is not None and self.is_tainted(node.iter) and \
                not isinstance(node.iter, ast.Call):
            self.rule._emit(
                self.collect, self.path, node.lineno, "device-loop",
                self.info.qualname,
                "Python-level loop iterates a device array — every "
                "element access is its own host transfer; pull once with "
                "sync_guard.pull() or keep the loop on device")
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_If(self, node: ast.If) -> None:
        self._flag_test(node.test)
        self._check_expr(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_While(self, node: ast.While) -> None:
        self._flag_test(node.test)
        self._check_expr(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_Expr(self, node: ast.Expr) -> None:
        self._check_expr(node.value)

    def visit_With(self, node: ast.With) -> None:
        for item in node.items:
            self._check_expr(item.context_expr)
            if item.optional_vars is not None:
                self._bind(item.optional_vars,
                           self.is_tainted(item.context_expr))
        for stmt in node.body:
            self.visit(stmt)

    visit_AsyncWith = visit_With

    def visit_FunctionDef(self, node) -> None:  # nested defs: own pass
        pass

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- conversion checks --------------------------------------------------
    def _flag_test(self, test: ast.AST) -> None:
        if self.collect is not None and self.is_tainted(test):
            self.rule._emit(
                self.collect, self.path, test.lineno, "implicit-sync",
                self.info.qualname,
                "branching on a device value forces an implicit "
                "device→host bool() sync — pull it once with "
                "sync_guard.scalar(x, site) and branch on the host value")

    def _check_expr(self, expr: ast.AST) -> None:
        if self.collect is None:
            return
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            raw = call_name(node)
            if raw in _CONVERT_BUILTINS and len(node.args) == 1 and \
                    self.is_tainted(node.args[0]):
                self.rule._emit(
                    self.collect, self.path, node.lineno, "implicit-sync",
                    self.info.qualname,
                    f"{raw}() on a device value is an implicit, "
                    f"unattributed device→host sync — use "
                    f"sync_guard.scalar(x, site)")
            elif raw in _NP_CONVERTERS and node.args and \
                    self.is_tainted(node.args[0]):
                self.rule._emit(
                    self.collect, self.path, node.lineno, "implicit-sync",
                    self.info.qualname,
                    f"{raw}() pulls a device array to host outside the "
                    f"attributed seams — use sync_guard.pull(x, site)")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _CONVERT_METHODS and \
                    self.is_tainted(node.func.value):
                self.rule._emit(
                    self.collect, self.path, node.lineno, "implicit-sync",
                    self.info.qualname,
                    f".{node.func.attr}() on a device value is an "
                    f"implicit, unattributed device→host sync — use "
                    f"sync_guard.scalar(x, site)")


class Rule:
    name = "device-discipline"
    description = ("no unattributed host syncs, float64 drift, device "
                   "loops, or jit-cache-busting patterns in the device "
                   "hot path")

    def run(self, ctx: LintContext) -> List[Finding]:
        graph = callgraph.for_context(ctx)
        findings: List[Finding] = []
        files = [f for f in ctx.py_files(include=_SCAN_INCLUDE,
                                         exclude=_SCAN_EXCLUDE)
                 if f.tree is not None]

        # Interprocedural device-taint fixpoint over the scanned files:
        # jit-decorated functions return device arrays by construction;
        # a function returning another device function's result joins
        # the set on the next sweep (cycles converge — membership only
        # grows and is bounded by the function count).
        device_fids: Set[str] = set()
        infos = []
        for src in files:
            for info in graph.functions_in(src.relpath):
                infos.append((src, info))
                if callgraph.is_jit_decorated(info):
                    device_fids.add(info.fid)
        spans_by_path = {src.relpath: _x64_spans(src.tree) for src in files}
        for _ in range(4):
            grew = False
            for src, info in infos:
                if info.fid in device_fids:
                    continue
                t = _Taint(self, graph, src.relpath, info, device_fids,
                           spans_by_path[src.relpath])
                for stmt in info.node.body:
                    t.visit(stmt)
                if t.returns_device:
                    device_fids.add(info.fid)
                    grew = True
            if not grew:
                break

        # Checking pass: conversions, loops, branch tests.  Jitted
        # function BODIES are exempt — a traced value cannot silently
        # sync inside a trace (it raises loudly at trace time instead).
        for src, info in infos:
            if callgraph.is_jit_decorated(info):
                continue
            t = _Taint(self, graph, src.relpath, info, device_fids,
                       spans_by_path[src.relpath], collect=findings)
            for stmt in info.node.body:
                t.visit(stmt)

        for src in files:
            self._check_untimed_sync(src, graph, findings)
            self._check_float64(src, spans_by_path[src.relpath], findings)
            self._check_jit_unsafe(src, graph, findings)
        return [f for f in findings if not self._allowed(f)]

    # -- helpers -------------------------------------------------------------
    def _emit(self, findings: List[Finding], path: str, line: int,
              check: str, qualname: str, message: str) -> None:
        findings.append(Finding(
            self.name, path, line, f"[{check}] {message}",
            ident=f"{check}:{qualname}:{line_key(findings, check, qualname)}"))

    def _allowed(self, f: Finding) -> bool:
        check = f.ident.split(":", 1)[0]
        qual = f.ident.split(":")[1] if f.ident.count(":") >= 1 else ""
        return (f.path, qual, check) in ALLOW

    def _check_untimed_sync(self, src, graph, findings) -> None:
        for info in graph.functions_in(src.relpath):
            for site in graph.sites_of(info.fid):
                if site.name.endswith("block_until_ready"):
                    self._emit(
                        findings, src.relpath, site.line, "untimed-sync",
                        info.qualname,
                        "raw block_until_ready stalls the host with no "
                        "exec.kernel.*.device_ms attribution — wrap the "
                        "dispatch in timeline.kernel_begin/kernel_end")

    def _check_float64(self, src, x64_spans, findings) -> None:
        # DEVICE dtypes only: host numpy is 64-bit regardless of the jax
        # x64 mode, so np.float64 on host arrays is not drift.
        for node in ast.walk(src.tree):
            name = None
            if isinstance(node, ast.Attribute) and \
                    node.attr in ("float64", "complex128") and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id in ("jnp", "jax"):
                name = f"{node.value.id}.{node.attr}"
            if name is None or _in_spans(node.lineno, x64_spans):
                continue
            from hyperspace_tpu.lint.engine import enclosing_function_name
            fn = enclosing_function_name(src.tree, node.lineno)
            self._emit(
                findings, src.relpath, node.lineno, "float64-literal", fn,
                f"{name} outside a scoped `with _enable_x64():` region — "
                f"under the 32-bit default this silently downcasts")

    def _check_jit_unsafe(self, src, graph, findings) -> None:
        for info in graph.functions_in(src.relpath):
            jitted = callgraph.is_jit_decorated(info)
            if jitted:
                args = info.node.args
                defaults = list(args.defaults) + [
                    d for d in args.kw_defaults if d is not None]
                for d in defaults:
                    if isinstance(d, (ast.List, ast.Dict, ast.Set,
                                      ast.ListComp, ast.DictComp,
                                      ast.SetComp)):
                        self._emit(
                            findings, src.relpath, d.lineno, "jit-unsafe",
                            info.qualname,
                            "mutable default argument on a jitted "
                            "function — unhashable as a static arg, and "
                            "a fresh object per trace busts the jit "
                            "cache")
                for site in graph.sites_of(info.fid):
                    bad = site.name in _JIT_BANNED_CALLS \
                        or site.name.startswith("os.environ") \
                        or site.name.startswith("conf.") \
                        or ".conf." in site.name
                    if bad:
                        self._emit(
                            findings, src.relpath, site.line, "jit-unsafe",
                            info.qualname,
                            f"{site.name}() inside a jitted function is "
                            f"read ONCE at trace time and baked into the "
                            f"compiled program — hoist it to a (static) "
                            f"argument")
            # Call sites passing literal containers in static positions.
            statics = _static_argnames(info.node)
            if not statics:
                continue
            params = [a.arg for a in info.node.args.args]
            positions = {params.index(s) for s in statics if s in params}
            for caller_site in graph.callers_of(info.fid):
                call_node = _find_call(graph, caller_site, info.name)
                if call_node is None:
                    continue
                for i, arg in enumerate(call_node.args):
                    if i in positions and isinstance(
                            arg, (ast.List, ast.Dict, ast.Set,
                                  ast.ListComp)):
                        caller = graph.functions[caller_site.caller]
                        self._emit(
                            findings, caller.path, arg.lineno,
                            "jit-unsafe", caller.qualname,
                            f"literal list/dict passed in static arg "
                            f"position {i} of jitted {info.name}() — "
                            f"unhashable static args raise (or retrace "
                            f"per call); pass a tuple")
                for kw in call_node.keywords:
                    if kw.arg in statics and isinstance(
                            kw.value, (ast.List, ast.Dict, ast.Set,
                                       ast.ListComp)):
                        caller = graph.functions[caller_site.caller]
                        self._emit(
                            findings, caller.path, kw.value.lineno,
                            "jit-unsafe", caller.qualname,
                            f"literal list/dict passed as static arg "
                            f"{kw.arg!r} of jitted {info.name}() — "
                            f"unhashable static args bust the jit cache; "
                            f"pass a tuple")


def _static_argnames(node) -> Set[str]:
    """``static_argnames`` of a ``partial(jax.jit, ...)`` decorator."""
    for dec in node.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        inner = call_name(dec)
        if not (inner == "partial" or inner.endswith(".partial")):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnames" and \
                    isinstance(kw.value, (ast.Tuple, ast.List)):
                return {e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
    return set()


def _find_call(graph, site, name: str) -> Optional[ast.Call]:
    """The ast.Call node behind a CallSite (re-walked by line)."""
    caller = graph.functions.get(site.caller)
    if caller is None:
        return None
    for node in ast.walk(caller.node):
        if isinstance(node, ast.Call) and node.lineno == site.line and \
                call_name(node).endswith(name):
            return node
    return None


def line_key(findings: List[Finding], check: str, qualname: str) -> int:
    """Disambiguating suffix for multiple same-check findings in one
    function: the ordinal among those already collected (line numbers
    would churn the baseline on unrelated edits above)."""
    prefix = f"{check}:{qualname}:"
    return sum(1 for f in findings if f.ident.startswith(prefix))
