"""The lifecycle decision journal: every maintenance decision, durable.

An autonomous daemon that refreshes, builds, and drops indexes with
nobody watching is only acceptable if every decision — including "did
nothing, here's why" — is readable after the fact, across restarts,
from any process.  Records append through the PR 2
:class:`~hyperspace_tpu.io.log_store.LogStore` seam under
``<systemPath>/_hyperspace_lifecycle`` (both backends, same
construction as the perf ledger), bounded by
``hyperspace.lifecycle.journal.maxEntries`` (oldest pruned), and come
back via ``Hyperspace.lifecycle_history()`` and the interop
``lifecycle`` verb.

Record shape (one flat JSON object per key, schema in
docs/19-lifecycle.md):

  - ``ts`` / ``cycle``: wall clock + the daemon cycle counter
  - ``decision`` / ``index`` / ``mode`` / ``reason``: the policy output
  - ``outcome``: ``done`` / ``noop`` / ``skipped`` / ``error``
  - ``appended`` / ``deleted`` / ``mutated``: the detection counts
  - ``wall_s`` / ``error``: execution cost / failure detail

Same cost/safety contract as the perf ledger: appends run inside
``faults.quiet()`` (journal IO must never consume an injected-fault
budget aimed at the system under test) and NEVER raise — a journal
failure must not cost a maintenance action its commit.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

JOURNAL_DIR = "_hyperspace_lifecycle"
RECORD_VERSION = 1

_seq_lock = threading.Lock()
_seq = 0


def journal_root(conf) -> str:
    from hyperspace_tpu.index.path_resolver import PathResolver

    return os.path.join(PathResolver(conf).system_path, JOURNAL_DIR)


def _store(conf):
    from hyperspace_tpu.io.log_store import store_for

    return store_for(conf, journal_root(conf))


def _next_key() -> str:
    global _seq
    with _seq_lock:
        _seq += 1
        seq = _seq
    return f"d-{int(time.time() * 1000):013d}-{os.getpid()}-{seq:05d}"


def append(conf, record: Dict[str, Any]) -> Optional[str]:
    """Append one decision record; returns its key, or None on
    failure.  Never raises; runs fault-quiet (see module docstring)."""
    from hyperspace_tpu.io import faults
    from hyperspace_tpu.telemetry import metrics

    try:
        with faults.quiet():
            store = _store(conf)
            rec = {"v": RECORD_VERSION, "ts": time.time(), **record}
            payload = json.dumps(rec, default=str).encode("utf-8")
            key = None
            for _ in range(4):
                key = _next_key()
                if store.put_if_absent(key, payload):
                    break
            else:
                metrics.inc("lifecycle.journal.errors")
                return None
            cap = int(getattr(conf, "lifecycle_journal_max_entries", 1024))
            if cap > 0:
                keys = store.list_keys()
                if len(keys) > cap:
                    for old in sorted(keys)[:len(keys) - cap]:
                        store.delete(old)
            metrics.inc("lifecycle.journal.appends")
            return key
    except Exception:  # noqa: BLE001 — journal IO never fails the daemon
        metrics.inc("lifecycle.journal.errors")
        return None


def records(conf) -> List[Dict[str, Any]]:
    """Every parseable journal record, oldest first.  Torn/unparseable
    records are skipped — the journal is advisory data."""
    from hyperspace_tpu.io import faults

    out: List[Dict[str, Any]] = []
    try:
        with faults.quiet():
            store = _store(conf)
            for key in sorted(store.list_keys()):
                try:
                    rec = json.loads(store.read(key).decode("utf-8"))
                except (FileNotFoundError, ValueError, UnicodeDecodeError):
                    continue
                if not isinstance(rec, dict):
                    continue
                rec["key"] = key
                out.append(rec)
    except Exception:  # noqa: BLE001 — an unreadable journal reads empty
        pass
    return out


def history_table(conf):
    """The journal as an arrow table, oldest first — the shape
    ``Hyperspace.lifecycle_history()`` and the interop ``lifecycle``
    verb return.  The full record rides in ``recordJson`` so the
    columnar schema stays flat and stable."""
    import pyarrow as pa

    recs = records(conf)
    return pa.table({
        "key": pa.array([str(r.get("key", "")) for r in recs],
                        type=pa.string()),
        "ts": pa.array([float(r.get("ts", 0.0)) for r in recs],
                       type=pa.float64()),
        "index": pa.array([str(r.get("index", "")) for r in recs],
                          type=pa.string()),
        "decision": pa.array([str(r.get("decision", "")) for r in recs],
                             type=pa.string()),
        "mode": pa.array([str(r.get("mode", "")) for r in recs],
                         type=pa.string()),
        "reason": pa.array([str(r.get("reason", "")) for r in recs],
                           type=pa.string()),
        "outcome": pa.array([str(r.get("outcome", "")) for r in recs],
                            type=pa.string()),
        "appended": pa.array([int(r.get("appended", 0) or 0)
                              for r in recs], type=pa.int64()),
        "deleted": pa.array([int(r.get("deleted", 0) or 0)
                             for r in recs], type=pa.int64()),
        "mutated": pa.array([int(r.get("mutated", 0) or 0)
                             for r in recs], type=pa.int64()),
        "wallSeconds": pa.array([float(r.get("wall_s", 0.0) or 0.0)
                                 for r in recs], type=pa.float64()),
        "error": pa.array([str(r.get("error", "")) for r in recs],
                          type=pa.string()),
        "recordJson": pa.array([json.dumps(r, default=str) for r in recs],
                               type=pa.string()),
    })


def clear(conf) -> None:
    """Wipe the journal (tests)."""
    from hyperspace_tpu.io import faults

    with faults.quiet():
        store = _store(conf)
        for key in store.list_keys():
            store.delete(key)
