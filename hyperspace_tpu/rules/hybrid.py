"""Hybrid Scan: use a stale index over mutated source data by merging the
index with appended files and filtering out deleted rows via lineage.

Reference contract: index/rules/RuleUtils.scala —
  - candidate math (:79-133): an index whose signature no longer matches is
    still usable when the byte overlap is high enough: appended-bytes ratio
    ≤ conf threshold (0.3), deleted-bytes ratio ≤ threshold (0.2, deletes
    additionally require the lineage column); common bytes are tagged for
    the rankers.
  - plan transform (:302-443): index side gets a Filter(~isin(lineage_col,
    deleted_ids)) when rows were deleted (:399-408); appended files are read
    through a separate scan and merged with BucketUnion (join side, so
    bucketing survives, :422-439) or plain Union (filter side).

Beyond the reference — QUARANTINE CONTAINMENT: index data files recorded
as corrupt (index/quarantine.py; flagged by ``verify_index`` or by an
execution-time read failure) are treated as deleted-from-index.  The
whole hash BUCKET a quarantined file belongs to is dropped from the
index side, and exactly that bucket's source rows are re-read through a
``Filter(BucketIn(indexed, numBuckets, buckets), Scan(common source
files))`` branch unioned back in — the same merge shape the
appended-files path already uses.  One corrupt bucket costs one bucket's
worth of source IO, not the whole index; PR 2's full source fallback
remains the last resort.
"""

from __future__ import annotations

import os
from typing import FrozenSet, List, Optional, Sequence, Tuple

from hyperspace_tpu.actions.create import DATA_FILE_ID_COLUMN
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.index.log_entry import FileInfo, IndexLogEntry, IndexLogEntryTags
from hyperspace_tpu.plan.expr import BucketIn, Col, IsIn, Not
from hyperspace_tpu.plan.nodes import (
    BucketUnion,
    Filter,
    LogicalPlan,
    Project,
    Scan,
    ScanRelation,
    Union,
)
from hyperspace_tpu.rules import rule_utils

_HYBRID_INFO_TAG = "hybridScanFileLists"  # (appended, deleted) FileInfo lists
_QUARANTINE_TAG = "quarantineSplit"  # (excluded paths, buckets | None)


def _file_key(f: FileInfo) -> Tuple[str, int, int]:
    return (f.name, f.size, f.mtime)


def get_hybrid_scan_candidates(session, entries: Sequence[IndexLogEntry],
                               scan: Scan) -> List[IndexLogEntry]:
    """RuleUtils.scala:79-133."""
    relation = session.source_provider_manager.get_relation(scan)
    current = relation.all_files()
    current_by_key = {_file_key(f): f for f in current}
    conf = session.conf
    out: List[IndexLogEntry] = []
    # Multi-version index selection: a time-traveled lake read swaps each
    # candidate for its closest indexed version before the overlap math
    # (RuleUtils.scala:96-101 / DeltaLakeRelation.closestIndex).  Only for
    # entries over THIS relation — swapping an unrelated table's index would
    # load its old log versions per query and discard cached tags for
    # nothing (the overlap math excludes it anyway).
    scan_roots = {os.path.abspath(p) for p in relation.root_paths}

    def _same_relation(e: IndexLogEntry) -> bool:
        return any(os.path.abspath(p) in scan_roots
                   for r in e.relations for p in r.root_paths)

    entries = [relation.closest_index(e) if _same_relation(e) else e
               for e in entries]
    for entry in entries:
        cached = entry.get_tag(IndexLogEntryTags.IS_HYBRIDSCAN_CANDIDATE, scan)
        if cached is not None:
            if cached:
                out.append(entry)
            continue
        indexed_keys = {_file_key(f): f for f in entry.source_file_infos()}
        common_keys = indexed_keys.keys() & current_by_key.keys()
        common_bytes = sum(k[1] for k in common_keys)
        appended = [f for k, f in current_by_key.items() if k not in common_keys]
        deleted = [f for k, f in indexed_keys.items() if k not in common_keys]
        appended_bytes = sum(f.size for f in appended)
        deleted_bytes = sum(f.size for f in deleted)
        total_current = common_bytes + appended_bytes
        total_indexed = common_bytes + deleted_bytes
        ok = common_bytes > 0
        if ok and appended_bytes:
            ok = appended_bytes / total_current <= conf.hybrid_scan_max_appended_ratio
        if ok and deleted_bytes:
            ok = (entry.has_lineage_column()
                  and deleted_bytes / total_indexed <= conf.hybrid_scan_max_deleted_ratio)
        entry.set_tag(IndexLogEntryTags.IS_HYBRIDSCAN_CANDIDATE, ok, scan)
        entry.set_tag(IndexLogEntryTags.COMMON_BYTES, common_bytes, scan)
        entry.set_tag(_HYBRID_INFO_TAG, (appended, deleted), scan)
        if ok:
            out.append(entry)
    return out


def quarantined_split(session, entry: IndexLogEntry
                      ) -> Tuple[FrozenSet[str], Optional[Tuple[int, ...]]]:
    """(excluded index file paths, affected bucket ids) for ``entry``.

    A quarantined file poisons its whole BUCKET (a bucket split across
    several files must drop entirely, or the source branch would
    duplicate the healthy siblings' rows).  ``buckets is None`` with a
    non-empty exclusion means the entry is UNUSABLE for containment — a
    quarantined file whose bucket id cannot be recovered from its name,
    or a quarantine covering every file — and candidate selection drops
    it (the query falls back to source, PR 2's behavior).  Memoized per
    optimize pass through the entry tag (tags reset each pass), so the
    quarantine store is listed once per entry per query.
    """
    cached = entry.get_tag(_QUARANTINE_TAG)
    if cached is not None:
        return cached
    from hyperspace_tpu.io.parquet import bucket_id_of_file

    qpaths = session.index_collection_manager \
        .quarantine_manager(entry.name).paths()
    result: Tuple[FrozenSet[str], Optional[Tuple[int, ...]]]
    if not qpaths:
        result = (frozenset(), ())
    else:
        infos = entry.content.file_infos()
        flagged = [f.name for f in infos if f.name in qpaths]
        if not flagged:
            result = (frozenset(), ())
        else:
            buckets = {bucket_id_of_file(p) for p in flagged}
            if None in buckets:
                result = (frozenset(f.name for f in infos), None)
            else:
                excluded = frozenset(
                    f.name for f in infos
                    if bucket_id_of_file(f.name) in buckets)
                if len(excluded) == len(infos):
                    # Nothing healthy left to scan: containment would be
                    # a pure source scan wearing an index costume.
                    result = (excluded, None)
                else:
                    result = (excluded, tuple(sorted(buckets)))
    entry.set_tag(_QUARANTINE_TAG, result)
    return result


def quarantine_excludes_entry(session, entry: IndexLogEntry) -> bool:
    """True when quarantine leaves no usable containment plan for
    ``entry`` (drop it from the candidates; source answers the query)."""
    excluded, buckets = quarantined_split(session, entry)
    return bool(excluded) and buckets is None


def hybrid_file_lists(entry: IndexLogEntry, scan: Scan
                      ) -> Tuple[List[FileInfo], List[FileInfo]]:
    """(appended, deleted) for this entry vs this scan: the candidate-math
    tag when present (set by get_hybrid_scan_candidates), else the lists a
    quick refresh recorded in the entry itself."""
    info = entry.get_tag(_HYBRID_INFO_TAG, scan)
    if info is not None:
        return info
    return entry.appended_files(), entry.deleted_files()


def transform_plan_to_use_hybrid_scan(session, plan: LogicalPlan, target: Scan,
                                      entry: IndexLogEntry,
                                      bucket_union: bool,
                                      prune_to_buckets=None) -> LogicalPlan:
    """RuleUtils.scala:302-443: build the merged index∪appended subtree and
    swap it for ``target``.  ``prune_to_buckets`` restricts the INDEX side's
    buckets (the appended side is unbucketed raw data and always scans)."""
    appended, deleted = hybrid_file_lists(entry, target)
    excluded, qbuckets = quarantined_split(session, entry)
    if excluded and qbuckets is None:
        # Callers filter unusable entries out of the candidates; reaching
        # here means a caller skipped that check — refuse loudly (the
        # degradable rule boundary turns this into a source-scan plan).
        raise HyperspaceError(
            f"index {entry.name!r} has unusable quarantined files")
    if excluded and bucket_union:
        # The join side's merge is bucket-aligned; a source-side bucket
        # branch has no bucket structure to align.  JoinIndexRule drops
        # quarantined entries from its candidates, so this is a guard.
        raise HyperspaceError(
            f"index {entry.name!r} has quarantined buckets; bucket-aligned "
            "join merge is not possible")
    visible_cols = entry.derived_dataset.all_columns

    index_files = None if not excluded else tuple(
        f.name for f in entry.content.file_infos() if f.name not in excluded)
    index_side: LogicalPlan = Scan(rule_utils.index_scan_relation(
        entry, use_bucket_spec=bucket_union or prune_to_buckets is not None,
        prune_to_buckets=prune_to_buckets, file_paths=index_files))
    if deleted:
        # Filter(Not(In(lineage, deleted ids))) (RuleUtils.scala:399-408).
        deleted_ids = sorted({f.id for f in deleted})
        index_side = Filter(Not(IsIn(Col(DATA_FILE_ID_COLUMN), deleted_ids)),
                            index_side)
    index_side = Project(visible_cols, index_side)

    src_rel = target.relation
    repair_side: Optional[LogicalPlan] = None
    if qbuckets:
        # Containment branch: the quarantined buckets' rows, re-read from
        # the COMMON source files (recorded minus deleted — appended
        # files' rows come through the appended branch for every bucket,
        # and deleted files' rows must not reappear).  BucketIn uses the
        # build kernel's host mirror, so the branch returns exactly the
        # rows the dropped index files held.
        deleted_keys = {_file_key(f) for f in deleted}
        common = [f for f in entry.source_file_infos()
                  if _file_key(f) not in deleted_keys]
        if common:
            repair_scan = Scan(ScanRelation(
                root_paths=src_rel.root_paths,
                file_format=src_rel.file_format,
                options=src_rel.options,
                file_paths=tuple(f.name for f in common),
            ))
            repair_side = Project(visible_cols, Filter(
                BucketIn(tuple(entry.indexed_columns), entry.num_buckets,
                         qbuckets),
                repair_scan))

    if appended:
        # The executor states this version for the table when it reads
        # the branch: a plan reused after a commit states the version its
        # file list is from, not the one resolved for the query.
        version = session.source_provider_manager.get_relation(
            target).source_version()
        appended_scan = Scan(ScanRelation(
            root_paths=src_rel.root_paths,
            file_format=src_rel.file_format,
            options=src_rel.options,
            file_paths=tuple(f.name for f in appended),
            hybrid_appended_of=entry.name,
            source_version=version,
        ))
        appended_side: LogicalPlan = Project(visible_cols, appended_scan)
        cols = tuple(entry.indexed_columns)
        if bucket_union:
            # Join side: appended rows must be routed into the same bucket
            # space so the bucketed merge stays shuffle-free for the index
            # side (RuleUtils.scala:511-570's on-the-fly shuffle).
            merged: LogicalPlan = BucketUnion(
                [index_side, appended_side],
                (entry.num_buckets, cols, cols))
        else:
            # strict: the index ∪ its own source must not silently widen
            # on schema drift (see Union's docstring).
            sides = [index_side, appended_side]
            if repair_side is not None:
                sides.append(repair_side)
            merged = Union(sides, strict=True)
    elif repair_side is not None:
        merged = Union([index_side, repair_side], strict=True)
    else:
        merged = index_side

    def swap(node: LogicalPlan) -> LogicalPlan:
        return merged if node is target else node

    return plan.transform_up(swap)
