"""Per-file quarantine: the containment layer of the integrity subsystem.

A corrupt index data file used to cost the whole index (PR 2's degraded
fallback re-plans every query against the source).  Quarantine shrinks
the blast radius to the damaged BUCKET: a file that fails verification
(actions/verify.py) or dies mid-query (dataset.collect's containment
path) is recorded here, the rewrite rules then exclude its bucket from
the index side and re-read only that bucket's rows from source
(rules/hybrid.py), and ``refresh_index(mode="repair")`` rebuilds exactly
the quarantined buckets and clears the records.

Records persist through the :class:`~hyperspace_tpu.io.log_store.LogStore`
seam — one key per quarantined file under
``<indexPath>/_hyperspace_quarantine/`` — so the same code works over
:class:`PosixLogStore` and :class:`EmulatedObjectStore` (the backend
follows ``hyperspace.index.logStoreClass``), survives restarts, and is
visible to every process serving the index.  Keys are percent-encoded
relative paths (flat — PosixLogStore keys must not contain ``/``);
values are small JSON records (reason, observed size, timestamp).
``put_if_absent`` makes quarantining idempotent under concurrent
discoverers.
"""

from __future__ import annotations

import json
import os
import time
import urllib.parse
from typing import Dict, List, Optional, Set

from hyperspace_tpu.io.log_store import LogStore, store_for

QUARANTINE_DIR = "_hyperspace_quarantine"


def quarantine_manager_for(conf, index_path: str) -> "QuarantineManager":
    """The one constructor everyone uses (collection manager, rules,
    repair, vacuum): store backend from ``hyperspace.index.logStoreClass``
    rooted inside the index directory."""
    return QuarantineManager(index_path, store_for(
        conf, os.path.join(index_path, QUARANTINE_DIR)))


class QuarantineManager:
    def __init__(self, index_path: str, store: LogStore) -> None:
        self.index_path = os.path.abspath(index_path)
        self.store = store

    # -- key mapping ---------------------------------------------------------
    def _key(self, file_path: str) -> str:
        rel = os.path.relpath(os.path.abspath(file_path), self.index_path)
        return urllib.parse.quote(rel, safe="")

    def _path_of_key(self, key: str) -> str:
        return os.path.join(self.index_path, urllib.parse.unquote(key))

    # -- mutations -----------------------------------------------------------
    def add(self, file_path: str, reason: str,
            size: Optional[int] = None) -> bool:
        """Record ``file_path`` as quarantined (idempotent: a concurrent
        discoverer's record wins and this returns False)."""
        record = {"reason": reason, "ts": time.time()}
        if size is not None:
            record["size"] = int(size)
        payload = json.dumps(record).encode("utf-8")
        return self.store.put_if_absent(self._key(file_path), payload)

    def remove(self, file_path: str) -> None:
        self.store.delete(self._key(file_path))

    def clear(self) -> None:
        for key in self.store.list_keys():
            self.store.delete(key)

    def clear_version(self, version: int) -> None:
        """Drop records for files under ``v__=<version>/`` — called by
        ``IndexDataManager.delete`` so a vacuumed version never leaves
        orphaned quarantine keys behind."""
        from hyperspace_tpu.index.data_manager import INDEX_VERSION_DIR_PREFIX

        prefix = f"{INDEX_VERSION_DIR_PREFIX}{version}{os.sep}"
        for key in self.store.list_keys():
            rel = urllib.parse.unquote(key)
            if rel.startswith(prefix):
                self.store.delete(key)

    # -- reads ---------------------------------------------------------------
    def paths(self) -> Set[str]:
        """Absolute paths of every quarantined file."""
        return {self._path_of_key(k) for k in self.store.list_keys()}

    def records(self) -> List[Dict]:
        """[{"path": abs, "reason": ..., ...}] for reporting."""
        out: List[Dict] = []
        for key in self.store.list_keys():
            rec: Dict = {"path": self._path_of_key(key)}
            try:
                rec.update(json.loads(self.store.read(key).decode("utf-8")))
            except (FileNotFoundError, ValueError, UnicodeDecodeError):
                rec.setdefault("reason", "unreadable quarantine record")
            out.append(rec)
        return out

    def is_quarantined(self, file_path: str) -> bool:
        return self.store.exists(self._key(file_path))
