"""Optimize-result cache for the serving layer: pay optimize once per
query shape, serve repeats straight to the executor.

The serving workload is repeat-heavy (ROADMAP item 2: many clients, a
mixed filter/join/agg template set), and each repeat pays the full
optimizer pass — subquery rewrite, pushdown, pruning, rule matching over
every ACTIVE index — before executing.  This cache keys the OPTIMIZED
plan by:

  - the PR 5 advisor's STRUCTURAL plan fingerprint
    (``advisor/workload.fingerprint``: per-relation filter/join/group
    columns, never literal values) — the coarse bucket, shared with the
    workload-capture subsystem so one fingerprint walk feeds both;
  - a digest of the full plan tree INCLUDING literals
    (``plan.tree_string()``): two queries that share a shape but pin
    different values optimize to different plans (bucket pruning prunes
    different buckets), so literals must be part of the key;
  - the session's hyperspace-enabled switch (same plan, rules on vs off,
    different result).

Entries are invalidated four ways:

  - **generation**: every committed index action (create/refresh/vacuum/
    optimize/delete — actions/base.py) bumps a process-global generation;
    entries carry the generation they were built under and a stale
    generation is a miss.  This is what makes "build an index while the
    server runs" safe: the very next request re-optimizes and picks the
    new index up.
  - **source version**: an entry carries the version of each versioned
    table (Delta, Iceberg) its plan was optimized against
    (``sources/snapshots.py``); a lookup states the versions the query
    reads now, and an entry whose versions differ is dropped
    (``serve.plan_cache.source_stale``) and re-optimized, so a commit is
    seen by the next query.
  - **TTL**: plain file sources have no version and can drift without
    any index action (files appended under a scanned root).  Entries
    expire after ``ttl_s`` — the serving layer passes
    ``hyperspace.index.cache.expiryDurationInSeconds``, the same
    staleness window the index-listing cache already accepts.
  - **explicit**: the serving layer drops an entry whose plan failed at
    execution before running the degraded/containment machinery, so a
    cached plan over quarantined files cannot fail twice.

Eviction is the byte-budget LRU shared with the HBM column cache
(:class:`~hyperspace_tpu.execution.device_cache.ByteBudgetLRU`), entry
size estimated from the rendered tree plus the index scans' materialized
file lists (the dominant cost of a cached plan).  Metrics land under
``serve.plan_cache.*`` (hits/misses/evictions counters, bytes gauge).
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Dict, Optional, Tuple

from hyperspace_tpu.execution.device_cache import ByteBudgetLRU
from hyperspace_tpu.plan.nodes import LogicalPlan, Scan

# Process-global plan generation: bumped by every committed index action.
# Process-global (not per-session) because sessions share the on-disk
# index state — an action through ANY session invalidates every cache.
_generation = 0
_generation_lock = threading.Lock()


def bump_generation() -> None:
    global _generation
    with _generation_lock:
        _generation += 1


def current_generation() -> int:
    with _generation_lock:
        return _generation


def _plan_bytes_estimate(rendered: str, plan: LogicalPlan) -> int:
    """Approximate retained size of a cached plan: the rendered tree plus
    the per-scan file lists (index scans materialize every file path)."""
    total = len(rendered)
    for scan in plan.leaf_relations():
        if isinstance(scan, Scan) and scan.relation.file_paths:
            total += sum(len(p) for p in scan.relation.file_paths)
    return total + 256  # node-object overhead floor


class PlanCache:
    """Thread-safe optimize-result cache (one per serving endpoint)."""

    def __init__(self, budget_bytes: int = 64 << 20,
                 ttl_s: float = 300.0) -> None:
        self.budget_bytes = int(budget_bytes)
        self.ttl_s = float(ttl_s)
        self._lru = ByteBudgetLRU(metric_prefix="serve.plan_cache")

    # -- keying -------------------------------------------------------------
    def key_for(self, session, plan: LogicalPlan) -> Optional[str]:
        """Cache key for the USER plan, or None when the plan is not
        cacheable (no source relations to fingerprint, or fingerprinting
        itself fails — a cache must never fail a query)."""
        try:
            from hyperspace_tpu.advisor import workload

            fp = workload.fingerprint(session, plan)
            if fp is None:
                return None
            structural = workload.fingerprint_key(fp)
            literal = hashlib.sha1(
                plan.tree_string().encode("utf-8")).hexdigest()[:16]
            enabled = "1" if session.is_hyperspace_enabled() else "0"
            return f"{structural}:{literal}:{enabled}"
        except Exception:  # noqa: BLE001 — uncacheable, never fatal
            return None

    # -- lookup / store -----------------------------------------------------
    def get(self, key: str, versions: Optional[Dict[str, int]] = None
            ) -> Optional[LogicalPlan]:
        """The cached plan, or None; ``versions`` are the versioned
        tables' versions the query reads (None or empty: none)."""
        entry: Optional[Tuple[LogicalPlan, int, float, Dict[str, int]]] = \
            self._lru.peek(key)
        if entry is not None:
            plan, generation, stored_at, stored_versions = entry
            moved = stored_versions != (versions or {})
            if not moved and generation == current_generation() \
                    and time.monotonic() - stored_at <= self.ttl_s:
                self._lru.get(key)  # hit accounting + recency bump
                return plan
            # Stale: an index action landed since, a source version
            # moved, or the TTL passed.  Dropped BEFORE the counting
            # lookup so the hit-rate means "served from cache", nothing
            # else.
            self._lru.pop(key)
            from hyperspace_tpu.telemetry import metrics

            metrics.inc("serve.plan_cache.stale")
            if moved:
                metrics.inc("serve.plan_cache.source_stale")
        self._lru.get(key)  # registers the miss
        return None

    def put(self, key: str, plan: LogicalPlan,
            versions: Optional[Dict[str, int]] = None) -> None:
        try:
            rendered = plan.tree_string()
        except Exception:  # noqa: BLE001 — unrenderable = uncacheable
            return
        self._lru.put(key, (plan, current_generation(), time.monotonic(),
                            dict(versions or {})),
                      _plan_bytes_estimate(rendered, plan),
                      self.budget_bytes)

    def invalidate(self, key: str) -> None:
        self._lru.pop(key)

    def clear(self) -> None:
        self._lru.clear()

    def stats(self):
        return self._lru.stats()
