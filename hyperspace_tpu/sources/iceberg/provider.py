"""Iceberg source provider: snapshot-based table indexing with time travel.

Reference contract: sources/iceberg/IcebergFileBasedSource.scala:35-110 and
sources/iceberg/IcebergRelation.scala:44-243 —
  - supports relations whose format is "iceberg"; data files come from
    manifest scan planning, never a directory listing (:60-63);
  - signature = snapshot id + table location (:50-55) so index validity is an
    O(1) metadata check, not an O(files) walk;
  - ``create_relation_metadata`` pins ``snapshot-id`` + ``as-of-timestamp``
    of the current snapshot (:85-113);
  - ``refresh_relation_metadata`` drops both pins so refresh sees the latest
    snapshot (IcebergFileBasedSource.scala:45-52);
  - ``enrich_index_properties`` appends "indexLogVersion:snapshotId" pairs
    to the ``icebergSnapshots`` history (the reference passes through here,
    :99-107 — the history powers the beyond-parity multi-version selection
    below);
  - data files are always Parquet (:118-121).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.index.log_entry import (
    Content,
    FileIdTracker,
    FileInfo,
    Relation,
)
from hyperspace_tpu.plan.nodes import Scan
from hyperspace_tpu.io.schemas import arrow_schema_from_iceberg
from hyperspace_tpu.sources.iceberg.metadata import (
    IcebergSnapshot,
    IcebergTable,
    TableMetadata,
)
from hyperspace_tpu.sources import snapshots
from hyperspace_tpu.sources.interfaces import FileBasedRelation, FileBasedSourceProvider
from hyperspace_tpu.telemetry.trace import span

ICEBERG_FORMAT = "iceberg"
ICEBERG_VERSION_HISTORY_PROPERTY = "icebergSnapshots"
INDEX_LOG_VERSION_PROPERTY = "indexLogVersion"


class IcebergRelation(FileBasedRelation):
    def __init__(self, scan: Scan, conf: HyperspaceConf, session=None) -> None:
        super().__init__(scan)
        self._conf = conf
        self._session = session
        if len(self.root_paths) != 1:
            raise ValueError("An Iceberg relation has exactly one table path")
        self._table = IcebergTable(self.root_paths[0])
        self._metadata_cache: Optional[TableMetadata] = None
        self._snapshot_cache: Optional[IcebergSnapshot] = None
        self._files_cache: Optional[List[FileInfo]] = None

    # -- snapshot resolution ------------------------------------------------
    def _metadata(self) -> TableMetadata:
        """The table's metadata, loaded once per query
        (sources/snapshots.py), so its current snapshot is pinned."""
        if self._metadata_cache is None:
            self._metadata_cache = snapshots.resolve_once(
                ("iceberg", self._table.table_path), self._load_metadata)
        return self._metadata_cache

    def _load_metadata(self) -> TableMetadata:
        with span("source.snapshot", table=self._table.table_path) as sp:
            md = self._table.load_metadata()
            current = md.current_snapshot()
            sp.set(version=current.snapshot_id if current else -1)
        return md

    def _snapshot(self) -> Optional[IcebergSnapshot]:
        """Resolve time travel: ``snapshot-id`` wins, then
        ``as-of-timestamp`` (epoch ms), else the current snapshot
        (IcebergRelation.scala:50-55's option handling)."""
        if self._snapshot_cache is None:
            opts = self.options
            md = self._metadata()
            if "snapshot-id" in opts:
                self._snapshot_cache = md.snapshot_by_id(int(opts["snapshot-id"]))
            elif "as-of-timestamp" in opts:
                self._snapshot_cache = md.snapshot_for_timestamp(
                    int(opts["as-of-timestamp"]))
            else:
                self._snapshot_cache = md.current_snapshot()
        return self._snapshot_cache

    @property
    def snapshot_id(self) -> Optional[int]:
        snap = self._snapshot()
        return snap.snapshot_id if snap else None

    def source_version(self) -> int:
        """The snapshot id this relation reads (-1 before the first
        snapshot), noted as the current query's."""
        snap = self._snapshot()
        version = snap.snapshot_id if snap else -1
        snapshots.record_version(self._table.table_path, version)
        return version

    # -- FileBasedRelation --------------------------------------------------
    def all_files(self, tracker: Optional[FileIdTracker] = None) -> List[FileInfo]:
        """Files from manifest scan planning, not a directory walk
        (IcebergRelation.scala:60-63): replaced/deleted files still exist on
        disk but are NOT part of the snapshot.  The planned list is cached on
        the relation (a refresh calls this several times; re-parsing the Avro
        manifests and re-stat'ing every data file per call would multiply the
        metadata IO by file count)."""
        if self._files_cache is None:
            self._files_cache = []
            for f in self._table.plan_files(self._snapshot(), self._metadata()):
                mtime = int(os.stat(f.path).st_mtime * 1000) \
                    if os.path.isfile(f.path) else 0
                self._files_cache.append(FileInfo(f.path, f.size, mtime, -1))
        if tracker is None:
            return list(self._files_cache)
        return [FileInfo(f.name, f.size, f.mtime,
                         tracker.add_file(f.name, f.size, f.mtime))
                for f in self._files_cache]

    def schema(self) -> Dict[str, str]:
        if self._metadata().schema.get("fields"):
            return arrow_schema_from_iceberg(self._metadata().schema)
        files = self.all_files()
        if not files:
            raise FileNotFoundError(
                f"Iceberg table {self.root_paths[0]} has no schema and no files")
        from hyperspace_tpu.io.parquet import read_schema

        return read_schema(files[0].name, "parquet")

    def signature(self) -> str:
        """Snapshot id + location — O(1), no file walk
        (IcebergRelation.scala:50-55)."""
        return f"{self.snapshot_id}{self._metadata().location}"

    def create_relation_metadata(self, tracker: FileIdTracker) -> Relation:
        files = self.all_files(tracker)
        snap = self._snapshot()
        # Pin the indexed snapshot; drop any path-ish options
        # (IcebergRelation.scala:100-105).
        opts = {k: v for k, v in self.options.items() if k != "path"}
        if snap is not None:
            opts["snapshot-id"] = str(snap.snapshot_id)
            opts["as-of-timestamp"] = str(snap.timestamp_ms)
        return Relation(
            root_paths=[self._table.table_path],
            content=Content.from_leaf_files(files)
            or Content.from_directory(self._table.table_path, tracker),
            schema=self.schema(),
            file_format=ICEBERG_FORMAT,
            options=opts,
        )

    # -- multi-version index selection (beyond reference: the Delta-only
    # closestIndex, DeltaLakeRelation.scala:186-243, extended to Iceberg's
    # snapshot timeline) -----------------------------------------------------
    def _snapshot_order(self):
        """snapshot_id -> position on the timestamp-ordered timeline."""
        return {s.snapshot_id: i for i, s in enumerate(
            sorted(self._metadata().snapshots,
                   key=lambda s: s.timestamp_ms))}

    def _version_history(self, entry, order):
        """[(index log version, snapshot position)] ascending; when several
        index versions map to one snapshot (optimize), keep the highest."""
        raw = entry.properties.get(ICEBERG_VERSION_HISTORY_PROPERTY, "")
        if not raw:
            return []
        by_pos = {}
        for pair in raw.split(","):
            index_v, snap_id = (int(x) for x in pair.split(":"))
            pos = order.get(snap_id)
            if pos is None:
                continue  # expired snapshot: its index version can't anchor
            by_pos[pos] = max(index_v, by_pos.get(pos, -1))
        return sorted(((iv, pos) for pos, iv in by_pos.items()),
                      key=lambda t: t[1])

    def closest_index(self, entry):
        """The Delta closestIndex algorithm over Iceberg's snapshot
        timeline (shared FileBasedRelation helper)."""
        snap = self._snapshot()
        if snap is None:
            return entry
        order = self._snapshot_order()
        return self._select_closest_version(
            entry, self._session, self._version_history(entry, order),
            order.get(snap.snapshot_id))


class IcebergSource(FileBasedSourceProvider):
    name = "iceberg"

    def __init__(self, conf: HyperspaceConf) -> None:
        self._conf = conf
        self._session = None

    def bind_session(self, session) -> None:
        """Index-manager access for closest_index (as DeltaLakeSource)."""
        self._session = session

    def is_supported_relation(self, scan: Scan) -> Optional[bool]:
        return True if scan.relation.file_format.lower() == ICEBERG_FORMAT \
            else None

    def get_relation(self, scan: Scan) -> Optional[FileBasedRelation]:
        if not self.is_supported_relation(scan):
            return None
        return IcebergRelation(scan, self._conf, self._session)

    def internal_file_format_name(self, relation: Relation) -> Optional[str]:
        return "parquet" if relation.file_format == ICEBERG_FORMAT else None

    def refresh_relation_metadata(self, relation: Relation) -> Optional[Relation]:
        """Drop the snapshot pins so refresh sees the latest data
        (IcebergFileBasedSource.scala:45-52)."""
        if relation.file_format != ICEBERG_FORMAT:
            return None
        import dataclasses as dc

        opts = {k: v for k, v in relation.options.items()
                if k not in ("snapshot-id", "as-of-timestamp")}
        return dc.replace(relation, options=opts)

    def enrich_index_properties(self, relation: Relation,
                                properties: Dict[str, str]) -> Optional[Dict[str, str]]:
        """Append "indexLogVersion:snapshotId" to the snapshot history so
        time-traveled reads can pick the closest index version (the
        reference passes through here, IcebergFileBasedSource.scala:99-107
        — this history is the beyond-parity Delta symmetry)."""
        if relation.file_format != ICEBERG_FORMAT:
            return None
        out = dict(properties)
        index_version = properties.get(INDEX_LOG_VERSION_PROPERTY)
        snap_id = relation.options.get("snapshot-id")
        if index_version is not None and snap_id is not None:
            pair = f"{index_version}:{snap_id}"
            history = properties.get(ICEBERG_VERSION_HISTORY_PROPERTY)
            out[ICEBERG_VERSION_HISTORY_PROPERTY] = \
                f"{history},{pair}" if history else pair
        return out
