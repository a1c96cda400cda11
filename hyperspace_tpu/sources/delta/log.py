"""Delta transaction log: read and write the `_delta_log` protocol.

A Delta table is a directory of Parquet data files plus an ordered log of
JSON commits under ``_delta_log/``; the active file set at version N is the
replay of add/remove actions through commit N.  This reader speaks the open
Delta protocol (20-digit zero-padded ``N.json`` commits, newline-delimited
action objects, optional ``N.checkpoint.parquet`` + ``_last_checkpoint``)
so it can read tables written by Spark/delta-rs as well as by our writer.

Reference parity: this module replaces what the reference gets from the
``delta-core`` dependency (``TahoeLogFileIndex`` snapshots,
sources/delta/DeltaLakeRelation.scala:47-56's ``getSnapshot`` +
``filesForScan``) — re-implemented host-side because the TPU engine owns its
own reader instead of riding Spark's.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import urllib.parse
from typing import Any, Dict, List, Optional, Tuple

from hyperspace_tpu.exceptions import CorruptMetadataError

DELTA_LOG_DIR = "_delta_log"
_COMMIT_RE = re.compile(r"^(\d{20})\.json$")
_CHECKPOINT_RE = re.compile(r"^(\d{20})\.checkpoint\.parquet$")


@dataclasses.dataclass(frozen=True)
class AddFile:
    """One active data file of a snapshot (absolute path)."""

    path: str
    size: int
    modification_time: int  # milliseconds, from the log — not the filesystem


@dataclasses.dataclass(frozen=True)
class RemoveFile:
    """Tombstone for a removed data file (absolute path).  Carried in
    snapshots and checkpoints until the retention window expires so
    concurrent readers of an older version can still resolve the file —
    the protocol's VACUUM-safety mechanism."""

    path: str
    deletion_timestamp: int  # milliseconds


@dataclasses.dataclass
class DeltaMetadata:
    schema_string: str = ""
    partition_columns: List[str] = dataclasses.field(default_factory=list)
    configuration: Dict[str, str] = dataclasses.field(default_factory=dict)
    id: str = ""  # stable table id; a schema-changing commit must keep it


@dataclasses.dataclass
class Snapshot:
    version: int
    files: List[AddFile]
    metadata: DeltaMetadata
    tombstones: List[RemoveFile] = dataclasses.field(default_factory=list)


class DeltaLog:
    """Reader for one table's ``_delta_log``."""

    def __init__(self, table_path: str) -> None:
        self.table_path = os.path.abspath(table_path)
        self.log_path = os.path.join(self.table_path, DELTA_LOG_DIR)

    # -- discovery ----------------------------------------------------------
    def exists(self) -> bool:
        return os.path.isdir(self.log_path) and bool(
            self.commit_versions() or self.checkpoint_versions())

    def _listing(self) -> Tuple[List[int], List[int]]:
        """(commit versions, checkpoint versions), ascending, from one
        listing of the log directory."""
        if not os.path.isdir(self.log_path):
            return [], []
        commits, checkpoints = [], []
        for name in os.listdir(self.log_path):
            m = _COMMIT_RE.match(name)
            if m:
                commits.append(int(m.group(1)))
                continue
            m = _CHECKPOINT_RE.match(name)
            if m:
                checkpoints.append(int(m.group(1)))
        return sorted(commits), sorted(checkpoints)

    def commit_versions(self) -> List[int]:
        return self._listing()[0]

    def checkpoint_versions(self) -> List[int]:
        return self._listing()[1]

    def latest_version(self) -> int:
        return self._latest(*self._listing())

    def _latest(self, versions: List[int], checkpoints: List[int]) -> int:
        if not versions and not checkpoints:
            raise FileNotFoundError(f"Not a Delta table: {self.table_path}")
        return max(versions + checkpoints)

    def version_for_timestamp(self, timestamp_ms: int) -> int:
        """Latest version committed at or before ``timestamp_ms`` (the
        ``timestampAsOf`` resolution rule)."""
        best: Optional[int] = None
        for v in self.commit_versions():
            ts = self._commit_timestamp(v)
            if ts is not None and ts > timestamp_ms:
                break  # commit timestamps are monotonic — nothing later matches
            if ts is not None:
                best = v
        if best is None:
            raise ValueError(
                f"No commit at or before timestamp {timestamp_ms} in "
                f"{self.table_path}")
        return best

    def _commit_timestamp(self, version: int) -> Optional[int]:
        if not os.path.isfile(self._commit_path(version)):
            return None  # superseded by a checkpoint
        for action in self._commit_actions(version):
            info = action.get("commitInfo")
            if info and "timestamp" in info:
                return int(info["timestamp"])
        # Fall back to the commit file's mtime (protocol-compliant readers do
        # the same when commitInfo is absent).
        path = self._commit_path(version)
        if os.path.isfile(path):
            return int(os.stat(path).st_mtime * 1000)
        return None

    # -- replay -------------------------------------------------------------
    def snapshot(self, version: Optional[int] = None) -> Snapshot:
        all_commits, checkpoints = self._listing()
        latest = self._latest(all_commits, checkpoints)
        if version is None:
            version = latest
        if version > latest or version < 0:
            raise ValueError(
                f"Version {version} does not exist in {self.table_path} "
                f"(latest is {latest})")
        active: Dict[str, AddFile] = {}
        tombstones: Dict[str, RemoveFile] = {}
        metadata = DeltaMetadata()

        # Start from the newest checkpoint at or below the target version.
        start = 0
        usable = [c for c in checkpoints if c <= version]
        if usable:
            cp = usable[-1]
            metadata, active, tombstones = self._read_checkpoint(cp)
            start = cp + 1

        commits = [v for v in all_commits if start <= v <= version]
        expect = list(range(start, version + 1))
        if commits != expect:
            missing = sorted(set(expect) - set(commits))
            raise ValueError(
                f"Delta log is missing commits {missing} for version "
                f"{version} of {self.table_path}")
        for v in commits:
            for action in self._commit_actions(v):
                self._apply(action, active, metadata, tombstones)
        return Snapshot(version, sorted(active.values(), key=lambda f: f.path),
                        metadata,
                        sorted(tombstones.values(), key=lambda f: f.path))

    def _apply(self, action: Dict[str, Any], active: Dict[str, AddFile],
               metadata: DeltaMetadata,
               tombstones: Optional[Dict[str, RemoveFile]] = None) -> None:
        if "add" in action and action["add"]:
            a = action["add"]
            path = self._absolute(a["path"])
            active[path] = AddFile(path, int(a["size"]),
                                   int(a.get("modificationTime", 0)))
            if tombstones is not None:
                tombstones.pop(path, None)
        elif "remove" in action and action["remove"]:
            r = action["remove"]
            path = self._absolute(r["path"])
            active.pop(path, None)
            if tombstones is not None:
                tombstones[path] = RemoveFile(
                    path, int(r.get("deletionTimestamp") or 0))
        elif "metaData" in action and action["metaData"]:
            m = action["metaData"]
            metadata.schema_string = m.get("schemaString", "")
            metadata.partition_columns = list(m.get("partitionColumns", []))
            metadata.configuration = dict(m.get("configuration", {}))
            metadata.id = m.get("id", "")

    def _absolute(self, path: str) -> str:
        path = urllib.parse.unquote(path)
        if os.path.isabs(path):
            return path
        return os.path.join(self.table_path, path)

    def _commit_path(self, version: int) -> str:
        return os.path.join(self.log_path, f"{version:020d}.json")

    def _commit_actions(self, version: int) -> List[Dict[str, Any]]:
        path = self._commit_path(version)
        out: List[Dict[str, Any]] = []
        with open(path, "r", encoding="utf-8") as f:
            for lineno, line in enumerate(f, start=1):
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError as e:
                    # A truncated/torn commit (writer died mid-append, or a
                    # partial object-store upload) must name the bad file —
                    # a bare JSONDecodeError is undebuggable at lake scale.
                    raise CorruptMetadataError(
                        f"Truncated or corrupt Delta log entry {path!r} "
                        f"(action line {lineno}): {e}") from e
        return out

    def _read_checkpoint(self, version: int):
        import pyarrow as pa

        path = os.path.join(self.log_path, f"{version:020d}.checkpoint.parquet")
        from hyperspace_tpu.io.parquet import read_parquet_file

        try:
            table = read_parquet_file(path)
        except pa.ArrowInvalid as e:
            raise CorruptMetadataError(
                f"Truncated or corrupt Delta checkpoint {path!r}: {e}") from e
        metadata = DeltaMetadata()
        active: Dict[str, AddFile] = {}
        tombstones: Dict[str, RemoveFile] = {}
        for row in table.to_pylist():
            self._apply({k: v for k, v in row.items() if v is not None},
                        active, metadata, tombstones)
        return metadata, active, tombstones

    # -- writing ------------------------------------------------------------
    def write_commit(self, version: int, actions: List[Dict[str, Any]]) -> str:
        """Create commit ``version`` atomically; raises if it already exists
        (the same create-if-absent optimistic concurrency as the index
        operation log, IndexLogManager.scala:149-165)."""
        os.makedirs(self.log_path, exist_ok=True)
        path = self._commit_path(version)
        body = "\n".join(json.dumps(a, separators=(",", ":")) for a in actions)
        # 'x' = exclusive create: two writers racing on the same version —
        # exactly one wins, matching the Delta protocol's commit rule.
        with open(path, "x", encoding="utf-8") as f:
            f.write(body + "\n")
        return path
