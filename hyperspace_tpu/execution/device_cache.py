"""HBM-resident index-column cache: pay the transfer once, compute many.

Round-3 verdict item 2: every query re-read parquet into host arrow and
re-shipped columns to the accelerator, so the architecture's payoff —
SURVEY §2.4's "per-core XLA data parallelism over HBM-resident columnar
batches" — was structurally unreachable.  Spark gives the reference this
for free through the block manager's RDD caching; here it is explicit: a
process-wide, byte-budgeted LRU of POST-DECODE device arrays keyed by
file identity.

Keys are ``(files_fingerprint, column, kind)`` where the fingerprint
hashes the scan's resolved file list with each file's (size, mtime):
an overwritten or compacted index version can never serve stale arrays —
its fingerprint differs, and the dead entries age out of the LRU.

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


def files_fingerprint(paths: Iterable[str]) -> Optional[str]:
    """Content-identity hash of a resolved scan file list: path order plus
    each file's size and mtime_ns.  None when any file is unstat-able
    (races with vacuum — safer to skip caching than to key on guesses)."""
    h = hashlib.md5()
    try:
        for p in paths:
            st = os.stat(p)
            h.update(p.encode())
            h.update(f":{st.st_size}:{st.st_mtime_ns};".encode())
    except OSError:
        return None
    return h.hexdigest()


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


# One cache per process: device memory is a process-level resource, and
# fingerprint keys are content-based so sessions can safely share entries.
_CACHE = DeviceColumnCache()


def global_cache() -> DeviceColumnCache:
    return _CACHE
