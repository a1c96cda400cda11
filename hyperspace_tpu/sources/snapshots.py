"""One snapshot per versioned table per query.

A query over a versioned table (Delta, Iceberg) reads the version that
is latest when the query starts.  ``Dataset.collect`` opens a
:func:`query_scope`; inside it, the first relation that needs a table's
version resolves it, and every later relation of the same query over
that table (the plan cache's validity check, the rules' hybrid-scan
file lists, the executor's scans) reads the same version and replays
its log once.  Outside a scope each relation resolves on its own.

The scope also collects the version each table was read at; the answer
states them as the schema metadata :data:`SOURCE_VERSIONS_KEY`, a JSON
object from table path to version (:func:`with_source_versions`).
Plain file sources have no version and never appear there.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
from typing import Any, Callable, Dict, Hashable, Iterator, Optional, TypeVar

import pyarrow as pa

T = TypeVar("T")

SOURCE_VERSIONS_KEY = b"hyperspace.source_versions"


class _Scope:
    __slots__ = ("versions", "memo")

    def __init__(self) -> None:
        self.versions: Dict[str, int] = {}
        self.memo: Dict[Hashable, Any] = {}


_SCOPE: contextvars.ContextVar[Optional[_Scope]] = contextvars.ContextVar(
    "hyperspace_snapshot_scope", default=None)


@contextlib.contextmanager
def query_scope() -> Iterator[Dict[str, int]]:
    """Pin each versioned table's snapshot for the enclosed query; yields
    the map of table path to the version read.  A nested scope (a query
    run inside another) joins the enclosing one."""
    scope = _SCOPE.get()
    if scope is not None:
        yield scope.versions
        return
    scope = _Scope()
    token = _SCOPE.set(scope)
    try:
        yield scope.versions
    finally:
        _SCOPE.reset(token)


def resolve_once(key: Hashable, resolve: Callable[[], T]) -> T:
    """``resolve()`` once per query scope for ``key``; every call resolves
    outside a scope."""
    scope = _SCOPE.get()
    if scope is None:
        return resolve()
    if key not in scope.memo:
        scope.memo[key] = resolve()
    return scope.memo[key]


def record_version(table: str, version: int) -> None:
    """Note that the current query reads ``table`` at ``version``."""
    scope = _SCOPE.get()
    if scope is not None:
        scope.versions[table] = int(version)


def with_source_versions(table: pa.Table, versions: Dict[str, int]
                         ) -> pa.Table:
    """``table`` with ``versions`` stated in its schema metadata."""
    if not versions:
        return table
    meta = dict(table.schema.metadata or {})
    meta[SOURCE_VERSIONS_KEY] = json.dumps(versions, sort_keys=True).encode()
    return table.replace_schema_metadata(meta)


def source_versions_of(table: pa.Table) -> Dict[str, int]:
    """The versions an answer states (empty when it states none)."""
    raw = (table.schema.metadata or {}).get(SOURCE_VERSIONS_KEY)
    return {} if raw is None else {k: int(v)
                                   for k, v in json.loads(raw).items()}
