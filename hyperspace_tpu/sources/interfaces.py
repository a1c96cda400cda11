"""Source provider abstraction: how the engine talks to concrete data
formats/lakes.

Reference contract: sources/interfaces.scala —
  - ``FileBasedRelation`` (:43-146): wraps one plan leaf; exposes file
    listing, signature, partition info, relation-metadata creation for the
    log, lineage pairs, and the ``closest_index`` hook (Delta time travel).
  - ``FileBasedSourceProvider`` (:184-234): decides whether it supports a
    relation, reconstructs relations from logged metadata for refresh, names
    the internal file format, and enriches index properties.

Each provider answers each API with Some/None; the manager dispatches to
exactly one (FileBasedSourceProviderManager.scala:117-155).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from hyperspace_tpu.index.log_entry import FileIdTracker, FileInfo, IndexLogEntry, Relation
from hyperspace_tpu.plan.nodes import Scan


# Lake formats whose data files are a different physical format than the
# table format name.  Single source of truth for the engine's read paths
# (executor scans, hybrid-scan file subsets, schema resolution) — mirrors
# internalFileFormatName (interfaces.scala:210).
LAKE_DATA_FORMATS = {"delta": "parquet", "iceberg": "parquet"}


def physical_read_format(file_format: str) -> str:
    """Format to read a relation's data files with."""
    return LAKE_DATA_FORMATS.get(file_format.lower(), file_format)


class FileBasedRelation:
    """One supported leaf relation of a plan (interfaces.scala:43-146)."""

    def __init__(self, scan: Scan) -> None:
        self.scan = scan

    @property
    def root_paths(self) -> List[str]:
        return list(self.scan.relation.root_paths)

    @property
    def file_format(self) -> str:
        return self.scan.relation.file_format

    @property
    def options(self) -> Dict[str, str]:
        return self.scan.relation.options_dict

    @property
    def read_format(self) -> str:
        """Format to READ data files with (Delta/Iceberg data files are
        Parquet — internalFileFormatName, interfaces.scala:210)."""
        return physical_read_format(self.file_format)

    def all_files(self, tracker: Optional[FileIdTracker] = None) -> List[FileInfo]:
        """Every data file of this relation (interfaces.scala:60-66)."""
        raise NotImplementedError

    def schema(self) -> Dict[str, str]:
        raise NotImplementedError

    def signature(self) -> str:
        """Relation-level validity signature (interfaces.scala:52-58)."""
        raise NotImplementedError

    def source_version(self) -> Optional[int]:
        """The table version this relation reads, for versioned sources
        (Delta version, Iceberg snapshot id); None for plain files."""
        return None

    def create_relation_metadata(self, tracker: FileIdTracker) -> Relation:
        """Snapshot for the log entry (interfaces.scala:101-110)."""
        raise NotImplementedError

    def lineage_pairs(self, tracker: FileIdTracker) -> List[Tuple[str, int]]:
        """(file path, file id) pairs for the lineage column
        (interfaces.scala:120-126)."""
        return [(f.name, f.id) for f in self.all_files(tracker)]

    def closest_index(self, entry: IndexLogEntry) -> IndexLogEntry:
        """Hook for multi-version index selection (Delta time travel,
        interfaces.scala:138-146); default: the entry itself."""
        return entry

    def _select_closest_version(self, entry: IndexLogEntry, session,
                                versions, current_pos) -> IndexLogEntry:
        """Shared floor/exact/diff-bytes selection over a recorded version
        history (DeltaLakeRelation.scala:186-243's algorithm, reused by
        every versioned source).  ``versions`` is [(index log version,
        position)] ascending by position; ``current_pos`` is the read
        snapshot's position in the same ordering."""
        if not versions or session is None or current_pos is None:
            return entry

        def load(log_version: int) -> Optional[IndexLogEntry]:
            return session.index_collection_manager.get_index(
                entry.name, log_version)

        floor_i = -1
        for i, (_, pos) in enumerate(versions):
            if pos <= current_pos:
                floor_i = i
        if floor_i == len(versions) - 1:
            return entry  # at or past the latest indexed version
        if floor_i == -1:
            return load(versions[0][0]) or entry  # before the first
        if versions[floor_i][1] == current_pos:
            return load(versions[floor_i][0]) or entry  # exact
        # Between two indexed versions: fewer diff bytes wins so Hybrid
        # Scan has less to patch.
        current = {(f.name, f.size, f.mtime): f.size
                   for f in self.all_files()}
        total = sum(current.values())

        def diff_bytes(candidate: IndexLogEntry) -> int:
            keys = {(f.name, f.size, f.mtime)
                    for f in candidate.source_file_infos()}
            common = sum(size for key, size in current.items() if key in keys)
            return (total - common) + (candidate.source_files_size() - common)

        prev_log = load(versions[floor_i][0])
        next_log = load(versions[floor_i + 1][0])
        if prev_log is None or next_log is None:
            return next_log or prev_log or entry
        return prev_log if diff_bytes(prev_log) < diff_bytes(next_log) \
            else next_log


class FileBasedSourceProvider:
    """Format plug-in (interfaces.scala:184-234)."""

    name: str = ""

    def is_supported_relation(self, scan: Scan) -> Optional[bool]:
        raise NotImplementedError

    def get_relation(self, scan: Scan) -> Optional[FileBasedRelation]:
        raise NotImplementedError

    def internal_file_format_name(self, relation: Relation) -> Optional[str]:
        raise NotImplementedError

    def refresh_relation_metadata(self, relation: Relation) -> Optional[Relation]:
        """Drop snapshot-pinning options so refresh sees latest data
        (interfaces.scala:193-201)."""
        raise NotImplementedError

    def enrich_index_properties(self, relation: Relation,
                                properties: Dict[str, str]) -> Optional[Dict[str, str]]:
        raise NotImplementedError
