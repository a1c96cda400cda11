"""HBM-resident index-column cache: pay the transfer once, compute many.

Round-3 verdict item 2: every query re-read parquet into host arrow and
re-shipped columns to the accelerator, so the architecture's payoff —
SURVEY §2.4's "per-core XLA data parallelism over HBM-resident columnar
batches" — was structurally unreachable.  Spark gives the reference this
for free through the block manager's RDD caching; here it is explicit: a
process-wide, byte-budgeted LRU of POST-DECODE device arrays keyed by
file identity.

Keys are ``(files fingerprint, column, kind)`` where the fingerprint
hashes the scan's resolved file list with each file's (size, mtime):
an overwritten or compacted index version can never serve stale arrays —
its fingerprint differs, and the dead entries age out of the LRU.  A
fused join's side also leaves its :class:`SideManifest` under
``(fingerprint, "__side__", "meta")``, so a repeat that finds the
manifest and the columns serves the side without decoding a file.

Residency changes ROUTING, not just speed: once a scan's referenced
columns are resident, the device path's cost is kernel time plus
round-trip latency (no per-row shipping), so the executor compares row
counts against the much smaller ``resident_min_rows`` derived from the
measured profile (utils/calibrate.py) instead of the cold-transfer
threshold.  Population policy (conf ``deviceCachePolicy``):

  - ``auto`` (default): populate whenever the device path runs anyway —
    free where the calibrated cold threshold routes large scans to the
    device organically.
  - ``eager``: ship eligible scan columns on FIRST use even when the
    cold cost model would stay on host — an explicit opt-in for
    repeat-heavy workloads (pay the transfer once, serve every later
    query from HBM).
  - ``off``: never cache.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Dict, Iterable, Optional, Tuple

Key = Tuple[str, str, str]  # (files fingerprint, column name, kind)


def stat_files(paths: Iterable[str]) -> Tuple[Optional[str], int]:
    """One ``stat`` per file: the content-identity hash of a resolved scan
    file list (path order plus each file's size and mtime_ns) and the
    files' total bytes.  The hash is None when any file is unstat-able
    (races with vacuum — safer to skip caching than to key on guesses);
    the bytes then count the files that could be stat-ed."""
    h = hashlib.md5()
    total = 0
    ok = True
    for p in paths:
        try:
            st = os.stat(p)
        except OSError:
            ok = False
            continue
        total += st.st_size
        h.update(p.encode())
        h.update(f":{st.st_size}:{st.st_mtime_ns};".encode())
    return (h.hexdigest() if ok else None), total


def derive_fingerprint(fp: str, transform: str) -> str:
    """Identity of rows derived from ``fp``'s rows by a deterministic
    transform (``filter:<predicate repr>``, ``dropnull:<column>``)."""
    return hashlib.md5(f"{fp}|{transform}".encode()).hexdigest()


def side_manifest_key(fp: str) -> Key:
    """Where a join side's :class:`SideManifest` lives in the cache."""
    return (fp, "__side__", "meta")


class SideManifest:
    """Host record of a materialized join side, kept in the device cache
    beside the side's columns (so it ages out with them): the side's
    arrow schema, row count and per-column null counts — all the fused
    join needs of a side it does not decode."""

    __slots__ = ("schema", "num_rows", "null_counts", "nbytes")

    def __init__(self, schema, num_rows: int,
                 null_counts: Dict[str, int]) -> None:
        self.schema = schema
        self.num_rows = int(num_rows)
        self.null_counts = null_counts
        # Host bytes, charged to the cache's budget like a column.
        self.nbytes = int(schema.serialize().size) + 16 * len(null_counts)

    @classmethod
    def of(cls, table, prior: Optional["SideManifest"] = None
           ) -> "SideManifest":
        """The manifest of ``table``, widened by ``prior``'s columns (the
        same rows under another projection)."""
        fields = list(prior.schema) if prior is not None else []
        counts = dict(prior.null_counts) if prior is not None else {}
        for field in table.schema:
            if field.name not in counts:
                fields.append(field)
                counts[field.name] = table.column(field.name).null_count
        import pyarrow as pa

        return cls(pa.schema(fields), table.num_rows, counts)


class ByteBudgetLRU:
    """Thread-safe byte-budgeted LRU — the eviction mechanism shared by
    the device-column cache below and the serving layer's optimize-result
    plan cache (execution/plan_cache.py): one policy (LRU within an
    explicit byte budget, oversize entries rejected and tombstoned),
    one metric shape (``<prefix>.hits/misses/evictions`` counters plus a
    ``<prefix>.bytes`` gauge when ``metric_prefix`` is set)."""

    _REJECTED_MAX = 4096  # bound the tombstone set; clear-all on overflow

    def __init__(self, metric_prefix: Optional[str] = None) -> None:
        self._entries: "OrderedDict[object, object]" = OrderedDict()
        self._nbytes: Dict[object, int] = {}
        # Keys whose values did not fit the byte budget: callers that
        # make ROUTING decisions off cache presence (the device cache's
        # eager policy) must stop retrying them, or every repeat pays the
        # full cost forever.
        self._rejected: set = set()
        self._lock = threading.Lock()
        self._prefix = metric_prefix
        self.bytes_cached = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _inc(self, name: str) -> None:
        if self._prefix is not None:
            from hyperspace_tpu.telemetry import metrics

            metrics.inc(f"{self._prefix}.{name}")

    def get(self, key):
        with self._lock:
            value = self._entries.get(key)
            if value is None:
                self.misses += 1
                self._inc("misses")
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            self._inc("hits")
            return value

    def contains(self, key) -> bool:
        """Presence probe — no hit/miss accounting (the actual fetch
        follows if the caller decides to use the cache)."""
        with self._lock:
            return key in self._entries

    def peek(self, key):
        """Value without hit/miss accounting or a recency update — for
        callers that must validate an entry before deciding whether the
        lookup counts as a hit (the plan cache's staleness check)."""
        with self._lock:
            return self._entries.get(key)

    def was_rejected(self, key) -> bool:
        with self._lock:
            return key in self._rejected

    def put(self, key, value, nbytes: int, budget_bytes: int) -> bool:
        """Insert ``value`` accounted at ``nbytes``, evicting LRU entries
        to stay within ``budget_bytes``.  Returns False (and tombstones
        the key) when the entry can never fit."""
        nbytes = int(nbytes or 0)
        if nbytes <= 0 or nbytes > budget_bytes:
            with self._lock:
                if len(self._rejected) >= self._REJECTED_MAX:
                    self._rejected.clear()
                self._rejected.add(key)
            return False
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            while self.bytes_cached + nbytes > budget_bytes and self._entries:
                old_key, _old = self._entries.popitem(last=False)
                self.bytes_cached -= self._nbytes.pop(old_key)
                self.evictions += 1
                self._inc("evictions")
            self._entries[key] = value
            self._nbytes[key] = nbytes
            self.bytes_cached += nbytes
            if self._prefix is not None:
                from hyperspace_tpu.telemetry import metrics

                metrics.set_gauge(f"{self._prefix}.bytes", self.bytes_cached)
        return True

    def pop(self, key) -> None:
        """Drop one entry (invalidation)."""
        with self._lock:
            if key in self._entries:
                del self._entries[key]
                self.bytes_cached -= self._nbytes.pop(key)
            self._rejected.discard(key)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._nbytes.clear()
            self._rejected.clear()
            self.bytes_cached = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "entries": len(self._entries),
                    "bytes": self.bytes_cached}


class DeviceColumnCache(ByteBudgetLRU):
    """Byte-budgeted LRU of device arrays (thread-safe).  The byte cost
    of an entry is the array's own ``nbytes``."""

    def __init__(self) -> None:
        super().__init__(metric_prefix="cache.device")

    def put(self, key: Key, arr, budget_bytes: int) -> None:  # type: ignore[override]
        super().put(key, arr, int(getattr(arr, "nbytes", 0) or 0),
                    budget_bytes)

    def side_manifest(self, fp: str) -> Optional[SideManifest]:
        """The :class:`SideManifest` under ``fp``, freshened in the LRU, or
        None.  A host record, not a device column: the lookup leaves the
        ``cache.device.hits/misses`` counters alone."""
        key = side_manifest_key(fp)
        with self._lock:
            value = self._entries.get(key)
            if value is not None:
                self._entries.move_to_end(key)
            return value

    def put_side(self, fp: str, table, budget_bytes: int) -> None:
        """Record ``table``'s :class:`SideManifest` under ``fp``, widening
        a manifest of the same rows that lacks some of its columns."""
        key = side_manifest_key(fp)
        prior = self.peek(key)
        if prior is not None:
            if all(n in prior.null_counts for n in table.column_names):
                return
            self.pop(key)
        self.put(key, SideManifest.of(table, prior), budget_bytes)


# One cache per process: device memory is a process-level resource, and
# fingerprint keys are content-based so sessions can safely share entries.
_CACHE = DeviceColumnCache()


def global_cache() -> DeviceColumnCache:
    return _CACHE
