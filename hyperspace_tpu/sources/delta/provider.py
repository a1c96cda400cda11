"""Delta Lake source provider: versioned-table indexing with time travel.

Reference contract: sources/delta/DeltaLakeFileBasedSource.scala:40-123 and
sources/delta/DeltaLakeRelation.scala:33-243 —
  - supports relations whose format is "delta"; data files come from the
    transaction-log snapshot, never a directory listing (:47-56);
  - signature = table version + path (:39-42) so index validity is a version
    check, not an O(files) walk;
  - ``create_relation_metadata`` pins ``versionAsOf`` so refresh/rules know
    which version the index covers (:73-112);
  - ``refresh_relation_metadata`` drops time-travel options so refresh sees
    the latest data (DeltaLakeFileBasedSource.scala:49-55);
  - ``enrich_index_properties`` appends "indexVersion:deltaVersion" pairs to
    the ``deltaVersions`` history property (:107-123);
  - ``closest_index`` picks, for a time-traveled read, the index log version
    whose delta version is nearest — exact match, floor, or the diff-bytes
    tie-break between floor and ceiling (DeltaLakeRelation.scala:186-243).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.index.log_entry import (
    Content,
    FileIdTracker,
    FileInfo,
    IndexLogEntry,
    Relation,
)
from hyperspace_tpu.plan.nodes import Scan
from hyperspace_tpu.sources import snapshots
from hyperspace_tpu.sources.delta.log import DeltaLog, Snapshot
from hyperspace_tpu.sources.interfaces import FileBasedRelation, FileBasedSourceProvider
from hyperspace_tpu.telemetry.trace import span

DELTA_FORMAT = "delta"
DELTA_VERSION_HISTORY_PROPERTY = "deltaVersions"
INDEX_LOG_VERSION_PROPERTY = "indexLogVersion"


def _timestamp_ms(value: str) -> int:
    """``timestampAsOf`` accepts epoch milliseconds or a timestamp string
    (Spark accepts "yyyy-MM-dd[ HH:mm:ss]" and ISO forms)."""
    try:
        return int(value)
    except ValueError:
        pass
    from datetime import datetime, timezone

    text = value.strip().replace(" ", "T")
    try:
        dt = datetime.fromisoformat(text)
    except ValueError:
        raise ValueError(
            f"Cannot parse timestampAsOf value {value!r}: expected epoch "
            f"milliseconds or an ISO timestamp") from None
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=timezone.utc)
    return int(dt.timestamp() * 1000)


class DeltaLakeRelation(FileBasedRelation):
    def __init__(self, scan: Scan, conf: HyperspaceConf, session=None) -> None:
        super().__init__(scan)
        self._conf = conf
        self._session = session
        if len(self.root_paths) != 1:
            raise ValueError("A Delta relation has exactly one table path")
        self._log = DeltaLog(self.root_paths[0])
        self._snapshot_cache: Optional[Snapshot] = None

    # -- snapshot resolution ------------------------------------------------
    @property
    def table_version(self) -> int:
        return self._snapshot().version

    def source_version(self) -> int:
        """The version this relation reads, noted as the current query's
        (sources/snapshots.py); a latest read costs one log listing per
        query and no replay."""
        opts = self.options
        if "versionAsOf" not in opts and "timestampAsOf" not in opts:
            return self._latest_version()
        version = int(opts["versionAsOf"]) if "versionAsOf" in opts \
            else self._snapshot().version
        snapshots.record_version(self._log.table_path, version)
        return version

    def _latest_version(self) -> int:
        def listed() -> int:
            with span("source.snapshot", table=self._log.table_path) as sp:
                version = self._log.latest_version()
                sp.set(version=version)
            return version

        version = snapshots.resolve_once(("delta", self._log.table_path),
                                         listed)
        snapshots.record_version(self._log.table_path, version)
        return version

    def _snapshot(self) -> Snapshot:
        if self._snapshot_cache is None:
            opts = self.options
            if "versionAsOf" in opts:
                version = int(opts["versionAsOf"])
            elif "timestampAsOf" in opts:
                version = self._log.version_for_timestamp(
                    _timestamp_ms(opts["timestampAsOf"]))
            else:
                version = self._latest_version()

            def replayed() -> Snapshot:
                with span("source.snapshot", table=self._log.table_path,
                          version=version) as sp:
                    snap = self._log.snapshot(version)
                    sp.set(files=len(snap.files))
                return snap

            self._snapshot_cache = snapshots.resolve_once(
                ("delta", self._log.table_path, version), replayed)
        return self._snapshot_cache

    # -- FileBasedRelation --------------------------------------------------
    def all_files(self, tracker: Optional[FileIdTracker] = None) -> List[FileInfo]:
        """Files from the snapshot, not a directory walk
        (DeltaLakeRelation.scala:47-56): overwritten/removed files still
        exist on disk but are NOT part of the table."""
        out = []
        for f in self._snapshot().files:
            fid = tracker.add_file(f.path, f.size, f.modification_time) \
                if tracker is not None else -1
            out.append(FileInfo(f.path, f.size, f.modification_time, fid))
        return out

    def schema(self) -> Dict[str, str]:
        meta = self._snapshot().metadata
        if meta.schema_string:
            from hyperspace_tpu.sources.delta.writer import arrow_schema_from_spark

            return arrow_schema_from_spark(meta.schema_string)
        files = self.all_files()
        if not files:
            raise FileNotFoundError(
                f"Delta table {self.root_paths[0]} has no schema and no files")
        from hyperspace_tpu.io.parquet import read_schema

        return read_schema(files[0].name, "parquet")

    def signature(self) -> str:
        """Table version + path — O(1), no file walk
        (DeltaLakeRelation.scala:39-42)."""
        return f"{self.table_version}{self._log.table_path}"

    def create_relation_metadata(self, tracker: FileIdTracker) -> Relation:
        files = self.all_files(tracker)
        # Pin the indexed version; drop any path-ish options
        # (DeltaLakeRelation.scala:93-105).
        opts = {k: v for k, v in self.options.items()
                if k not in ("path", "timestampAsOf")}
        opts["versionAsOf"] = str(self.table_version)
        return Relation(
            root_paths=[self._log.table_path],
            content=Content.from_leaf_files(files)
            or Content.from_directory(self._log.table_path, tracker),
            schema=self.schema(),
            file_format=DELTA_FORMAT,
            options=opts,
        )

    # -- multi-version index selection (DeltaLakeRelation.scala:155-243) ----
    def _version_history(self, entry: IndexLogEntry) -> List[tuple]:
        """[(index log version, delta version)] ascending; when several index
        versions map to one delta version (optimize), keep the highest."""
        raw = entry.properties.get(DELTA_VERSION_HISTORY_PROPERTY, "")
        if not raw:
            return []
        by_delta: Dict[int, int] = {}
        for pair in raw.split(","):
            index_v, delta_v = (int(x) for x in pair.split(":"))
            by_delta[delta_v] = max(index_v, by_delta.get(delta_v, -1))
        return sorted(((iv, dv) for dv, iv in by_delta.items()),
                      key=lambda t: t[1])

    def closest_index(self, entry: IndexLogEntry) -> IndexLogEntry:
        """DeltaLakeRelation.scala:186-243; the algorithm lives in the
        shared FileBasedRelation helper (positions = delta versions)."""
        return self._select_closest_version(
            entry, self._session, self._version_history(entry),
            self.table_version)


class DeltaLakeSource(FileBasedSourceProvider):
    name = "delta"

    def __init__(self, conf: HyperspaceConf) -> None:
        self._conf = conf
        self._session = None

    def bind_session(self, session) -> None:
        """Gives relations access to the index manager for closest_index
        (the Hyperspace.getContext(spark) lookup,
        DeltaLakeRelation.scala:193-199)."""
        self._session = session

    def is_supported_relation(self, scan: Scan) -> Optional[bool]:
        return True if scan.relation.file_format.lower() == DELTA_FORMAT else None

    def get_relation(self, scan: Scan) -> Optional[FileBasedRelation]:
        if not self.is_supported_relation(scan):
            return None
        return DeltaLakeRelation(scan, self._conf, self._session)

    def internal_file_format_name(self, relation: Relation) -> Optional[str]:
        return "parquet" if relation.file_format == DELTA_FORMAT else None

    def refresh_relation_metadata(self, relation: Relation) -> Optional[Relation]:
        if relation.file_format != DELTA_FORMAT:
            return None
        import dataclasses as dc

        opts = {k: v for k, v in relation.options.items()
                if k not in ("versionAsOf", "timestampAsOf")}
        return dc.replace(relation, options=opts)

    def enrich_index_properties(self, relation: Relation,
                                properties: Dict[str, str]) -> Optional[Dict[str, str]]:
        if relation.file_format != DELTA_FORMAT:
            return None
        out = dict(properties)
        index_version = properties.get(INDEX_LOG_VERSION_PROPERTY)
        delta_version = relation.options.get("versionAsOf")
        if index_version is not None and delta_version is not None:
            pair = f"{index_version}:{delta_version}"
            history = properties.get(DELTA_VERSION_HISTORY_PROPERTY)
            out[DELTA_VERSION_HISTORY_PROPERTY] = \
                f"{history},{pair}" if history else pair
        return out
