"""blocking-discipline: the serving path cannot stall behind a lock, an
accept loop, or an unchecked deadline.

Three call-graph-powered analyses (lint/callgraph.py — these are the
checks PR 8's single-module rules could not express):

  1. **lock-held blocking** — in the thread-shared modules
     (``interop/server.py``, ``telemetry/``,
     ``execution/plan_cache.py``), no blocking primitive may be
     REACHABLE while a lock is held: a socket send/recv, a LogStore
     ``put/read/list/delete``, ``time.sleep``, parquet/file IO, or a
     write-mode ``open``.  The query propagates the lexical with-lock
     context across call edges (cycle-tolerant), so a helper three
     frames deep that appends to the perf ledger still convicts the
     locked caller — the PR 8 EWMA lost-update shape, generalized from
     "mutate under the lock" to "never BLOCK under it".  A finding
     names the whole witness chain.
  2. **block-free paths** — the accept loop (``process_request``,
     ``_acquire_conn``/``_release_conn``) and the inline-verb surface
     (``_serve_verb``) must stay free of store/file IO*, sleeps, and
     query execution (``Executor.execute``/``collect``): they are what
     still answers while the admission queue sheds, so anything slow
     here is an outage amplifier.  (*The verb surface reads the perf
     ledger / decision journal by design — store READS are allowed
     there; the accept loop allows only its bounded, timeout-guarded
     reject send.)
  3. **deadline discipline** — the PR 9 exit-check bug class, caught
     statically: ``Executor._execute_node`` must open with a deadline
     check, ``Executor.execute`` must re-check AFTER the dispatch
     (entry-only checks all ran on the way down), the worker loop must
     establish a ``deadline.scope`` around job execution, and operator
     handlers (``Executor._execute_*``) may only be dispatched from
     inside executor.py — an external caller would bypass the checked
     dispatcher entirely.

Deliberate exceptions carry an entry in ALLOW below (reason required)
or an inline ``# hslint: allow[blocking-discipline] <reason>`` pragma.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from hyperspace_tpu.lint import callgraph
from hyperspace_tpu.lint.engine import Finding, LintContext

# Modules whose locks must never be held across a blocking call.
LOCKED_MODULES = (
    "hyperspace_tpu/interop/server.py",
    "hyperspace_tpu/telemetry/",
    "hyperspace_tpu/execution/plan_cache.py",
)

# (path, function qualname, check) -> reason.
ALLOW: Dict[Tuple[str, str, str], str] = {
    ("hyperspace_tpu/telemetry/trace.py", "JsonlTraceSink.emit",
     "lock-held-blocking"):
        "the sink lock EXISTS to serialize appends/rotation of one "
        "local line-buffered file; contention is bounded by trace "
        "volume, and the lock is private to the sink",
}

_SOCKET_METHODS = {"sendall", "recv", "recv_into", "accept", "connect"}
_STORE_METHODS = {"put", "put_if_absent", "put_if_generation_match",
                  "read", "list_keys", "delete"}
_STORE_READ_METHODS = {"read", "list_keys"}
_IO_PATHS = (
    "hyperspace_tpu/io/parquet.py",
    "hyperspace_tpu/io/files.py",
    "hyperspace_tpu/io/log_store.py",
    "hyperspace_tpu/io/avro.py",
)
# Opening a store object touches no file; what blocks is the store
# method calls caught by ``_STORE_METHODS``.
_NOT_IO = ("hyperspace_tpu/io/log_store.py::store_for",)

_EXEC_TARGETS = (
    "hyperspace_tpu/execution/executor.py::Executor.execute",
    "hyperspace_tpu/dataset.py::Dataset.collect",
)


def _blocking_kind(site: callgraph.CallSite,
                   allow_store_reads: bool = False) -> Optional[str]:
    """What blocks at this call site ("" -> not blocking)."""
    n = site.name
    if n == "time.sleep" or n.endswith(".sleep"):
        return "time.sleep()"
    last = n.rsplit(".", 1)[-1]
    if "." in n and last in _SOCKET_METHODS:
        return f"socket .{last}()"
    if "." in n and last in _STORE_METHODS:
        receiver = n.rsplit(".", 1)[0].lower()
        if "store" in receiver:
            if allow_store_reads and last in _STORE_READ_METHODS:
                return None
            return f"store .{last}()"
    for t in site.targets:
        path, qual = t.split("::", 1)
        if path in _IO_PATHS and t not in _NOT_IO:
            return f"io call {qual}()"
    if n == "open":
        return "open()"
    return None


class Rule:
    name = "blocking-discipline"
    description = ("no blocking call reachable under a lock; accept "
                   "loop and inline verbs block-free; every executor "
                   "dispatch path deadline-checked")

    def run(self, ctx: LintContext) -> List[Finding]:
        graph = callgraph.for_context(ctx)
        findings: List[Finding] = []
        self._check_lock_held(ctx, graph, findings)
        self._check_block_free(ctx, graph, findings)
        self._check_deadlines(ctx, graph, findings)
        return [f for f in findings if not self._allowed(f)]

    def _allowed(self, f: Finding) -> bool:
        parts = f.ident.split(":")
        check = parts[0]
        qual = parts[1] if len(parts) > 1 else ""
        return (f.path, qual, check) in ALLOW

    # -- 1: lock-held blocking ----------------------------------------------
    def _check_lock_held(self, ctx, graph, findings) -> None:
        for src in ctx.py_files(include=LOCKED_MODULES):
            if src.tree is None or \
                    src.relpath.startswith("hyperspace_tpu/lint/"):
                continue
            for info in graph.functions_in(src.relpath):
                for site in graph.sites_of(info.fid):
                    if not site.locks:
                        continue
                    kind = _blocking_kind(site)
                    if kind:
                        findings.append(Finding(
                            self.name, src.relpath, site.line,
                            f"[lock-held-blocking] {kind} while holding "
                            f"{self._lock_names(site)} in "
                            f"{info.qualname}() — every other thread "
                            f"needing the lock stalls behind the IO",
                            ident=f"lock-held-blocking:{info.qualname}:"
                                  f"{site.name}"))
                        continue
                    for target in site.targets:
                        hit = graph.find_path(
                            target, lambda s: bool(_blocking_kind(s)))
                        if hit is None:
                            continue
                        chain, blocked = hit
                        findings.append(Finding(
                            self.name, src.relpath, site.line,
                            f"[lock-held-blocking] "
                            f"{_blocking_kind(blocked)} reachable while "
                            f"holding {self._lock_names(site)}: "
                            f"{info.qualname} -> "
                            f"{callgraph.describe_chain(graph, chain, blocked)}",
                            ident=f"lock-held-blocking:{info.qualname}:"
                                  f"{site.name}"))
                        break

    @staticmethod
    def _lock_names(site: callgraph.CallSite) -> str:
        return ", ".join(lk.split(":", 1)[1] for lk in site.locks)

    # -- 2: block-free paths -------------------------------------------------
    def _check_block_free(self, ctx, graph, findings) -> None:
        server = "hyperspace_tpu/interop/server.py"
        contracts = []  # (info, allow_store_reads, allow_bounded_send, label)
        for info in graph.functions_in(server):
            if info.name in ("process_request", "_acquire_conn",
                             "_release_conn"):
                contracts.append((info, False, True,
                                  "the accept loop"))
            elif info.name == "_serve_verb":
                contracts.append((info, True, True,
                                  "the inline-verb surface"))
            elif info.name in ("_event_loop", "_on_accept",
                               "_on_readable", "_on_wakeup"):
                # The async accept path (serving.ioMode=async): ONE
                # thread owns every connection's reads, so anything
                # blocking here stalls the whole listener, not one
                # connection.  Socket ops are allowed (non-blocking fds
                # + the bounded reject send); stores and sleeps are not.
                contracts.append((info, False, True,
                                  "the async event loop"))
        for info, store_reads, bounded_send, label in contracts:
            hit = graph.find_path(
                info.fid,
                lambda s: self._forbidden_inline(s, store_reads,
                                                 bounded_send))
            if hit is None:
                continue
            chain, blocked = hit
            what = _blocking_kind(blocked, allow_store_reads=store_reads) \
                or f"query execution via {blocked.name}()"
            findings.append(Finding(
                self.name, info.path, info.lineno,
                f"[block-free] {what} reachable from {info.qualname}() — "
                f"{label} must answer while the admission queue sheds: "
                f"{callgraph.describe_chain(graph, chain, blocked)}",
                ident=f"block-free:{info.qualname}:{blocked.name}"))

    @staticmethod
    def _forbidden_inline(site: callgraph.CallSite, store_reads: bool,
                          bounded_send: bool) -> bool:
        if any(t in _EXEC_TARGETS for t in site.targets):
            return True
        kind = _blocking_kind(site, allow_store_reads=store_reads)
        if kind is None:
            return False
        if bounded_send and kind.startswith("socket"):
            # The reject send is deliberate and timeout-bounded.
            return False
        if kind == "open()":
            return False  # loopback /proc reads etc.; writes are io-seam's
        return True

    # -- 3: deadline discipline ----------------------------------------------
    def _check_deadlines(self, ctx, graph, findings) -> None:
        ex_path = "hyperspace_tpu/execution/executor.py"

        def is_check(site: callgraph.CallSite) -> bool:
            return site.name.endswith(".check") and \
                any("utils/deadline.py" in t for t in site.targets)

        node_fn = graph.function(ex_path, "Executor._execute_node")
        if node_fn is not None:
            first = node_fn.node.body[0] if node_fn.node.body else None
            entry_line = getattr(first, "lineno", -1)
            has_entry = any(
                is_check(s) and s.line <= entry_line + 1
                for s in graph.sites_of(node_fn.fid))
            if not has_entry:
                findings.append(Finding(
                    self.name, ex_path, node_fn.lineno,
                    "[deadline] Executor._execute_node must open with a "
                    "deadline.check() — operator ENTRY is the seam every "
                    "dispatch path funnels through",
                    ident="deadline:Executor._execute_node:entry"))
        exec_fn = graph.function(ex_path, "Executor.execute")
        if exec_fn is not None:
            dispatch_line = None
            for s in graph.sites_of(exec_fn.fid):
                if s.name.endswith("_execute_node"):
                    dispatch_line = s.line
                    break
            has_exit = dispatch_line is not None and any(
                is_check(s) and s.line > dispatch_line
                for s in graph.sites_of(exec_fn.fid))
            if not has_exit:
                findings.append(Finding(
                    self.name, ex_path, exec_fn.lineno,
                    "[deadline] Executor.execute must deadline-check "
                    "AFTER _execute_node returns (the PR 9 exit-check "
                    "class: entry-only checks all ran on the way down, "
                    "so an expiry inside a long scan never aborts the "
                    "work stacked above it)",
                    ident="deadline:Executor.execute:exit"))
        run_fn = graph.function("hyperspace_tpu/interop/server.py",
                                "_WorkerPool._run")
        if run_fn is not None:
            has_scope = any(
                s.name.endswith(".scope") and
                any("utils/deadline.py" in t for t in s.targets)
                for s in graph.sites_of(run_fn.fid))
            if not has_scope:
                findings.append(Finding(
                    self.name, "hyperspace_tpu/interop/server.py",
                    run_fn.lineno,
                    "[deadline] _WorkerPool._run must execute jobs under "
                    "a deadline.scope() — without it no executor check "
                    "downstream can ever fire for a served request",
                    ident="deadline:_WorkerPool._run:scope"))
        # Operator handlers are dispatched only from inside executor.py.
        for fid, info in graph.functions.items():
            if info.path != ex_path or \
                    not info.qualname.startswith("Executor._execute_") or \
                    info.qualname == "Executor._execute_node":
                continue
            for site in graph.callers_of(fid):
                caller_path = site.caller.split("::", 1)[0]
                if caller_path != ex_path:
                    findings.append(Finding(
                        self.name, caller_path, site.line,
                        f"[deadline] {site.name}() dispatches executor "
                        f"operator {info.qualname} from outside "
                        f"executor.py — bypassing the deadline-checked "
                        f"dispatcher (_execute_node)",
                        ident=f"deadline:{info.qualname}:external"))
        # The collect seam re-checks before execution fallbacks.
        collect = graph.function("hyperspace_tpu/dataset.py",
                                 "Dataset.collect")
        if collect is not None and not graph.reaches(collect.fid, is_check):
            findings.append(Finding(
                self.name, "hyperspace_tpu/dataset.py", collect.lineno,
                "[deadline] Dataset.collect must reach a deadline check "
                "at its planning seam (a request that expired in the "
                "queue should not plan, replan, and containment-probe "
                "first)",
                ident="deadline:Dataset.collect:planning"))
