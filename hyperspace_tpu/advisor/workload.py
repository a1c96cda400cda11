"""Opt-in workload capture: a bounded, deduplicated log of query shapes.

``Dataset.collect`` feeds one record per query here when
``hyperspace.advisor.capture.enabled`` is on, built from the user's
logical plan plus the query's run report (telemetry/report.py carries the
measured per-scan bytes).  A *fingerprint* is purely structural — filter
columns and their predicate kinds, join keys, grouping and projected
columns, source relation roots — never literal data values, so capturing
is safe to leave on against sensitive data.

Records persist through the :class:`~hyperspace_tpu.io.log_store.LogStore`
seam (backend follows ``hyperspace.index.logStoreClass``) under
``<systemPath>/_hyperspace_workload/`` — one percent-encoded flat key per
fingerprint — so the same code works over :class:`PosixLogStore` and
:class:`EmulatedObjectStore`, survives restarts, and merges across
processes via generation-CAS.

Cost contract: repeats of a known fingerprint fold into an in-process hit
counter and only flush to the store at power-of-two total hit counts (or
every 32 pending), so the steady-state per-query cost is a plan walk and
a dict update.  ``flush_pending`` forces the counters out — the
recommender and ``workload_table`` call it first, so reads never lag.

Bound: at most ``hyperspace.advisor.capture.maxEntries`` distinct
fingerprints; new shapes beyond the cap are dropped and counted in the
``advisor.capture.dropped`` metric.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
import urllib.parse
from typing import Any, Dict, List, Optional, Tuple

from hyperspace_tpu.plan.expr import BinOp, Col, IsIn, Lit, split_conjuncts
from hyperspace_tpu.plan.nodes import Aggregate, Filter, Join, LogicalPlan

WORKLOAD_DIR = "_hyperspace_workload"
RECORD_VERSION = 1
# Pending hits are forced out whenever they exceed this, even off a
# power-of-two boundary (bounds worst-case loss on an abrupt exit).
MAX_PENDING = 32


def workload_root(conf) -> str:
    from hyperspace_tpu.index.path_resolver import PathResolver

    return os.path.join(PathResolver(conf).system_path, WORKLOAD_DIR)


def store_for(conf):
    """The capture store, rooted at the workload dir."""
    from hyperspace_tpu.io import log_store

    return log_store.store_for(conf, workload_root(conf))


# ---------------------------------------------------------------------------
# Fingerprinting
# ---------------------------------------------------------------------------
def _relation_key(rel) -> str:
    return json.dumps({"roots": sorted(rel.root_paths),
                       "format": rel.file_format.lower(),
                       "options": sorted(rel.options)}, sort_keys=True)


def _classify_conjunct(e) -> Optional[Tuple[str, List[str]]]:
    """("eq"|"range", columns) for one conjunct, None when unclassifiable.

    eq = the predicate pins the column to a finite value set (equality or
    IN — the shapes bucket pruning exploits); range = an inequality
    against a literal (the shapes sketch/Z-order pruning exploits)."""
    if isinstance(e, BinOp):
        cols = sorted(e.referenced_columns())
        if not cols:
            return None
        lit_side = isinstance(e.left, Lit) or isinstance(e.right, Lit)
        if e.op == "==" and lit_side:
            return "eq", cols
        if e.op in ("<", "<=", ">", ">=") and lit_side:
            return "range", cols
        return None
    if isinstance(e, IsIn) and isinstance(e.child, Col):
        return "eq", [e.child.name]
    return None


def _resolve_one(col: str, schema: List[str]) -> Optional[str]:
    lowered = col.lower()
    for s in schema:
        if s.lower() == lowered:
            return s
    return None


def fingerprint(session, plan: LogicalPlan) -> Optional[Dict[str, Any]]:
    """The structural fingerprint of ``plan``: per source relation, which
    columns its filters pin (eq) or bound (range), which join keys touch
    it, which columns the query needs from it.  None when the plan has no
    supported source relations (nothing for the advisor to index)."""
    scans = [s for s in plan.leaf_relations()
             if s.relation.index_scan_of is None]
    if not scans:
        return None
    tables: Dict[str, Dict[str, Any]] = {}
    schema_of: Dict[str, List[str]] = {}
    for s in scans:
        key = _relation_key(s.relation)
        if key not in tables:
            tables[key] = {"roots": list(s.relation.root_paths),
                           "format": s.relation.file_format.lower(),
                           "options": [list(kv) for kv in s.relation.options],
                           "eq": [], "range": [], "join": [], "group": [],
                           "projected": []}
            try:
                schema_of[key] = list(session.schema_of(s))
            except Exception:  # noqa: BLE001 — an unreadable relation
                # still fingerprints; column attribution just degrades.
                schema_of[key] = []

    def attribute(cols: List[str], field: str,
                  candidate_keys: List[str]) -> None:
        for c in cols:
            for key in candidate_keys:
                resolved = _resolve_one(c, schema_of.get(key, []))
                if resolved is not None:
                    bucket = tables[key][field]
                    if resolved not in bucket:
                        bucket.append(resolved)
                    break

    all_keys = list(tables)

    def walk(node: LogicalPlan) -> None:
        if isinstance(node, Filter):
            below = [_relation_key(s.relation)
                     for s in node.leaf_relations()
                     if s.relation.index_scan_of is None]
            keys = sorted(set(below)) or all_keys
            for conj in split_conjuncts(node.condition):
                hit = _classify_conjunct(conj)
                if hit is not None:
                    attribute(hit[1], hit[0], keys)
        elif isinstance(node, Join):
            from hyperspace_tpu.plan.expr import as_equi_join_pairs

            for a, b in as_equi_join_pairs(node.condition) or ():
                attribute([a, b], "join", all_keys)
        elif isinstance(node, Aggregate):
            attribute(list(node.group_by), "group", all_keys)
        for c in node.children:
            walk(c)

    walk(plan)
    try:
        output = plan.output_columns(session.schema_of)
    except Exception:  # noqa: BLE001
        output = []
    for key in all_keys:
        needed = list(output) + tables[key]["eq"] + tables[key]["range"] \
            + tables[key]["join"] + tables[key]["group"]
        attribute(needed, "projected", [key])
        for field in ("eq", "range", "join", "group", "projected"):
            tables[key][field] = sorted(tables[key][field])
    return {"tables": [tables[k] for k in sorted(tables)]}


def fingerprint_key(fp: Dict[str, Any]) -> str:
    digest = hashlib.sha1(
        json.dumps(fp, sort_keys=True).encode("utf-8")).hexdigest()[:16]
    return urllib.parse.quote(f"q-{digest}", safe="")


# ---------------------------------------------------------------------------
# The in-process pending cache (the <3%-overhead mechanism)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Pending:
    fp: Dict[str, Any]
    hits: int = 0
    bytes_total: int = 0
    duration_ms_total: float = 0.0
    last: Dict[str, Any] = dataclasses.field(default_factory=dict)
    stored_hits: Optional[int] = None  # None = store state unknown
    dropped: bool = False  # cap hit: stop trying to persist this key


_lock = threading.Lock()
_pending: Dict[Tuple[str, str], _Pending] = {}


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def capture(session, plan: LogicalPlan, report,
            result_rows: Optional[int] = None) -> None:
    """Record one executed query.  Never raises (a capture failure must
    never cost a query its answer); InjectedCrash still propagates —
    a simulated process death is not a capture failure."""
    from hyperspace_tpu.telemetry import metrics
    from hyperspace_tpu.telemetry.trace import span

    try:
        with span("advisor.capture"):
            _capture_inner(session, plan, report, result_rows)
            metrics.inc("advisor.queries_captured")
    except Exception:  # noqa: BLE001 — see docstring
        metrics.inc("advisor.capture.errors")


def _capture_inner(session, plan, report, result_rows) -> None:
    fp = fingerprint(session, plan)
    if fp is None:
        return
    key = fingerprint_key(fp)
    root = workload_root(session.conf)

    bytes_scanned = report.bytes_read() if report is not None else 0
    source_bytes = report.bytes_read(is_index=False) if report else 0
    scans = report.scans() if report is not None else []
    # Per-table measured bytes: match report scan records (relation =
    # ",".join(root_paths) for source scans) back to fingerprint tables.
    by_roots = {",".join(t["roots"]): t for t in fp["tables"]}
    table_bytes = {}
    for d in scans:
        t = by_roots.get(d.get("relation", ""))
        if t is not None:
            tkey = ",".join(t["roots"])
            table_bytes[tkey] = table_bytes.get(tkey, 0) \
                + int(d.get("bytes_read", 0))
    rows_scanned = 0
    stats = session.last_execution_stats or {}
    for s in stats.get("scans", []):
        rows_scanned += int(s.get("rows", 0) or 0)
    selectivity = None
    if result_rows is not None and rows_scanned > 0:
        selectivity = round(min(1.0, result_rows / rows_scanned), 6)

    last = {"bytes_scanned": int(bytes_scanned),
            "source_bytes": int(source_bytes),
            "table_bytes": table_bytes,
            "result_rows": result_rows,
            "selectivity": selectivity,
            "duration_ms": round(getattr(report, "duration_ms", 0.0), 3),
            "ts": time.time()}

    with _lock:
        p = _pending.get((root, key))
        if p is None:
            p = _Pending(fp=fp)
            _pending[(root, key)] = p
        p.hits += 1
        p.bytes_total += int(bytes_scanned)
        p.duration_ms_total += last["duration_ms"]
        p.last = last
        if p.dropped:
            return
        total = (p.stored_hits or 0) + p.hits
        if p.stored_hits is not None and not _is_pow2(total) \
                and p.hits < MAX_PENDING:
            return  # fold into the counter; flush at the next boundary
        _flush_locked(session.conf, key, p)


def _flush_locked(conf, key: str, p: _Pending) -> None:
    """Merge ``p``'s pending counters into the store (generation-CAS,
    bounded retries — losing every race just defers to the next flush)."""
    from hyperspace_tpu.telemetry import metrics

    store = store_for(conf)
    for _ in range(4):
        data, gen = store.read_with_generation(key)
        if data is None:
            if len(store.list_keys()) >= int(conf.advisor_capture_max_entries):
                metrics.inc("advisor.capture.dropped")
                p.dropped = True
                return
            rec = {"v": RECORD_VERSION, "tables": p.fp["tables"],
                   "hits": p.hits, "bytes_scanned_total": p.bytes_total,
                   "duration_ms_total": round(p.duration_ms_total, 3),
                   **{f"last_{k}": v for k, v in p.last.items()}}
            payload = json.dumps(rec).encode("utf-8")
            if store.put_if_absent(key, payload):
                p.stored_hits = p.hits
                p.hits = p.bytes_total = 0
                p.duration_ms_total = 0.0
                return
        else:
            try:
                rec = json.loads(data.decode("utf-8"))
            except (ValueError, UnicodeDecodeError):
                # Torn record: rewrite it wholesale from what we know.
                rec = {"v": RECORD_VERSION, "tables": p.fp["tables"],
                       "hits": 0, "bytes_scanned_total": 0,
                       "duration_ms_total": 0.0}
            rec["hits"] = int(rec.get("hits", 0)) + p.hits
            rec["bytes_scanned_total"] = \
                int(rec.get("bytes_scanned_total", 0)) + p.bytes_total
            rec["duration_ms_total"] = round(
                float(rec.get("duration_ms_total", 0.0))
                + p.duration_ms_total, 3)
            for k, v in p.last.items():
                rec[f"last_{k}"] = v
            payload = json.dumps(rec).encode("utf-8")
            if store.put_if_generation_match(key, payload, gen):
                p.stored_hits = rec["hits"]
                p.hits = p.bytes_total = 0
                p.duration_ms_total = 0.0
                return
    metrics.inc("advisor.capture.cas_giveup")


def flush_pending(conf) -> None:
    """Force every pending hit counter for this conf's workload root out
    to the store — called before any read path (recommend, table dump) so
    the write-behind counter never skews what the advisor sees."""
    root = workload_root(conf)
    with _lock:
        for (r, key), p in list(_pending.items()):
            if r == root and p.hits > 0 and not p.dropped:
                _flush_locked(conf, key, p)


def reset_cache() -> None:
    """Drop the in-process pending cache (tests; a cleared store)."""
    with _lock:
        _pending.clear()


# ---------------------------------------------------------------------------
# Reads
# ---------------------------------------------------------------------------
def records(conf) -> List[Dict[str, Any]]:
    """Every persisted workload record, with this process's pending
    write-behind counters overlaid IN MEMORY — a pure read.  The overlay
    applies the same merge the flush would, so callers see current
    numbers without this path ever touching the store write side: the
    interop ``workload`` verb answers inline during overload
    (blocking-discipline, docs/18), where a store put could stall it.
    Durability still comes from the pow2-boundary flushes (and
    :func:`flush_pending`, which the recommend/daemon paths call before
    scoring).  Unparseable records are skipped — capture is advisory
    data."""
    store = store_for(conf)
    out: List[Dict[str, Any]] = []
    by_key: Dict[str, Dict[str, Any]] = {}
    for key in store.list_keys():
        try:
            rec = json.loads(store.read(key).decode("utf-8"))
        except (FileNotFoundError, ValueError, UnicodeDecodeError):
            continue
        if not isinstance(rec, dict) or "tables" not in rec:
            continue
        rec["key"] = key
        out.append(rec)
        by_key[key] = rec
    root = workload_root(conf)
    with _lock:
        for (r, key), p in _pending.items():
            if r != root or p.hits <= 0 or p.dropped:
                continue
            rec = by_key.get(key)
            if rec is None:
                rec = {"v": RECORD_VERSION, "tables": p.fp["tables"],
                       "hits": 0, "bytes_scanned_total": 0,
                       "duration_ms_total": 0.0, "key": key}
                out.append(rec)
                by_key[key] = rec
            rec["hits"] = int(rec.get("hits", 0)) + p.hits
            rec["bytes_scanned_total"] = \
                int(rec.get("bytes_scanned_total", 0)) + p.bytes_total
            rec["duration_ms_total"] = round(
                float(rec.get("duration_ms_total", 0.0))
                + p.duration_ms_total, 3)
            for k, v in p.last.items():
                rec[f"last_{k}"] = v
    return sorted(out, key=lambda r: (-int(r.get("hits", 0)), r["key"]))


def workload_table(conf):
    """The captured workload as an arrow table (one row per fingerprint),
    the shape ``Hyperspace.captured_workload()`` and the interop
    ``workload`` verb return."""
    import pyarrow as pa

    rows = {"key": [], "hits": [], "relations": [], "eqColumns": [],
            "rangeColumns": [], "joinColumns": [], "groupColumns": [],
            "projectedColumns": [], "lastBytesScanned": [],
            "bytesScannedTotal": [], "lastDurationMs": [],
            "lastSelectivity": []}
    for rec in records(conf):
        tables = rec.get("tables", [])

        def gather(field):
            return sorted({c for t in tables for c in t.get(field, [])})

        rows["key"].append(rec["key"])
        rows["hits"].append(int(rec.get("hits", 0)))
        rows["relations"].append(
            [",".join(t.get("roots", [])) for t in tables])
        rows["eqColumns"].append(gather("eq"))
        rows["rangeColumns"].append(gather("range"))
        rows["joinColumns"].append(gather("join"))
        rows["groupColumns"].append(gather("group"))
        rows["projectedColumns"].append(gather("projected"))
        rows["lastBytesScanned"].append(int(rec.get("last_bytes_scanned", 0)))
        rows["bytesScannedTotal"].append(
            int(rec.get("bytes_scanned_total", 0)))
        rows["lastDurationMs"].append(
            float(rec.get("last_duration_ms", 0.0)))
        sel = rec.get("last_selectivity")
        rows["lastSelectivity"].append(
            float(sel) if sel is not None else None)
    return pa.table(rows)


def clear(conf) -> None:
    """Wipe the captured workload (store + in-process counters)."""
    store = store_for(conf)
    for key in store.list_keys():
        store.delete(key)
    root = workload_root(conf)
    with _lock:
        for rk in [rk for rk in _pending if rk[0] == root]:
            del _pending[rk]
