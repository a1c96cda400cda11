"""OptimizeAction: compact small index files bucket-wise.

Reference contract: actions/OptimizeAction.scala:46-175 —
  - mode "quick": only files below ``optimizeFileSizeThreshold`` (256 MB
    default, IndexConstants.scala:91-92) are compaction candidates; mode
    "full": every file (:70-83);
  - buckets with a single candidate file are skipped — nothing to merge
    (:115-133, using the bucket id recovered from the file name);
  - ``op()`` reads each bucket's candidate files, merges them sorted, and
    writes one file per bucket into a new version dir (:85-99);
  - the committed entry's content keeps non-optimized files and swaps the
    merged ones (:139-170); the source snapshot/fingerprint are untouched —
    this is an index-only operation.
"""

from __future__ import annotations

import copy
import dataclasses
import os
from collections import defaultdict
from typing import Dict, List, Optional

import pyarrow as pa
import pyarrow.parquet as pq

from hyperspace_tpu.actions.base import Action
from hyperspace_tpu.exceptions import HyperspaceError, NoChangesError
from hyperspace_tpu.index.data_manager import IndexDataManager
from hyperspace_tpu.index.log_entry import (
    Content,
    FileIdTracker,
    FileInfo,
    IndexLogEntry,
    States,
)
from hyperspace_tpu.index.log_manager import IndexLogManager
from hyperspace_tpu.io.parquet import (
    bucket_id_of_file,
    read_parquet_file,
    sort_permutation_host,
    write_bucket_run,
)
from hyperspace_tpu.telemetry.events import OptimizeActionEvent


@dataclasses.dataclass(frozen=True)
class OptimizeSummary:
    """What an optimize actually did — the return value of
    ``Hyperspace.optimize_index`` (it used to return None, leaving the
    caller to re-read the log to count the compaction).  ``outcome`` is
    ``"ok"`` for a committed compaction and ``"noop"`` when no bucket
    held mergeable files; ``version`` is the committed log id, or None
    for a no-op."""

    index: str
    mode: str                   # quick | full
    outcome: str                # "ok" | "noop"
    compacted_files: int = 0    # small files merged away
    compacted_buckets: int = 0  # buckets rewritten
    written_files: int = 0      # files the merge produced
    version: Optional[int] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class OptimizeAction(Action):
    transient_state = States.OPTIMIZING
    final_state = States.ACTIVE
    event_class = OptimizeActionEvent

    def __init__(self, log_manager: IndexLogManager, data_manager: IndexDataManager,
                 session, mode: str = "quick") -> None:
        super().__init__(log_manager)
        self.data_manager = data_manager
        self.session = session
        self.mode = mode
        self._new_files: List[str] = []
        self._retained: List[FileInfo] = []

    def _candidates(self) -> Dict[int, List[FileInfo]]:
        """Bucket → files worth merging (OptimizeAction.scala:115-133).
        Memoized: validate() and op() both need it, and the convergence
        check reads Parquet footers."""
        if getattr(self, "_candidates_cache", None) is not None:
            return self._candidates_cache
        entry = self.previous_log_entry
        threshold = self.session.conf.optimize_file_size_threshold
        by_bucket: Dict[int, List[FileInfo]] = defaultdict(list)
        retained: List[FileInfo] = []
        for f in entry.content.file_infos():
            bucket = bucket_id_of_file(f.name)
            if bucket is None or (self.mode == "quick" and f.size >= threshold):
                retained.append(f)
            else:
                by_bucket[bucket].append(f)
        max_rows = self.session.conf.index_max_rows_per_file
        mergeable: Dict[int, List[FileInfo]] = {}
        for b, fs in by_bucket.items():
            if max_rows > 0:
                # With the file-size knob: rewrite when the bucket has more
                # files than its minimal ceil(rows/max_rows) count OR any
                # file exceeds the (possibly lowered) knob — and converge
                # once both hold (re-merging an optimal bucket forever
                # would churn a version per run).
                per_file = [pq.ParquetFile(f.name).metadata.num_rows
                            for f in fs]
                minimal = -(-sum(per_file) // max_rows)
                worth_merging = (len(fs) > minimal
                                 or any(r > max_rows for r in per_file))
            else:
                worth_merging = len(fs) > 1
            if worth_merging:
                mergeable[b] = fs
            else:
                retained.extend(fs)
        self._retained = retained
        self._candidates_cache = mergeable
        return mergeable

    def validate(self) -> None:
        if self.previous_log_entry is None or \
                self.previous_log_entry.state != States.ACTIVE:
            raise HyperspaceError(
                f"Optimize is only supported in {States.ACTIVE} state")
        if not self.previous_log_entry.is_covering:
            # A data-skipping sketch is one small file per version; there is
            # nothing to compact.
            raise HyperspaceError(
                "Optimize applies to covering indexes only")
        if not self._candidates():
            raise NoChangesError(
                "No index files eligible for optimization (every bucket has "
                "a single file or files exceed the size threshold)")

    def op(self) -> None:
        from hyperspace_tpu.io import integrity

        integrity.configure_from_conf(self.session.conf)
        entry = self.previous_log_entry
        mergeable = self._candidates()
        version = self.data_manager.get_next_version()
        out_dir = self.data_manager.version_path(version)
        os.makedirs(out_dir, exist_ok=True)
        sort_cols = entry.indexed_columns
        max_rows = self.session.conf.index_max_rows_per_file
        layout = entry.derived_dataset.properties.get("layout",
                                                      "lexicographic")
        report = self.build_report
        for bucket, files in sorted(mergeable.items()):
            with report.phase("read"):
                merged = pa.concat_tables(
                    [read_parquet_file(f.name) for f in files],
                    promote_options="default")
            report.add_bytes(read=merged.nbytes)
            # Layout-aware: a Z-ordered index must stay Z-ordered through
            # compaction — Morton sort AND Z-cell-aligned file cuts — or its
            # per-file sketches go wide on every non-primary dimension.
            if layout == "zorder":
                from hyperspace_tpu.io.parquet import write_zorder_run

                with report.phase("write"):
                    new = write_zorder_run(merged, bucket, out_dir,
                                           max_rows, sort_cols,
                                           compression=self.session.conf
                                           .index_file_compression)
                self._new_files.extend(new)
                report.add_bytes(
                    written=sum(os.stat(p).st_size for p in new),
                    files=len(new))
                continue
            with report.phase("sort"):
                perm = sort_permutation_host(merged, sort_cols, layout)
                merged = merged.take(pa.array(perm))
            # Honor the file-size knob: collapsing a bucket to ONE file
            # would destroy the per-file sketch pruning granularity the
            # split exists for.
            with report.phase("write"):
                new = write_bucket_run(merged, bucket, out_dir, max_rows,
                                       compression=self.session.conf
                                       .index_file_compression)
            self._new_files.extend(new)
            report.add_bytes(written=sum(os.stat(p).st_size for p in new),
                             files=len(new))
        # Per-file min/max sketch for the compacted version, like every
        # build writes — keeps FilterIndexRule's file pruning effective on
        # optimized indexes.
        from hyperspace_tpu.actions.data_skipping import write_index_file_sketch

        with report.phase("sketch"):
            write_index_file_sketch(out_dir, sort_cols)

    def log_entry(self) -> IndexLogEntry:
        from hyperspace_tpu.io import integrity

        entry = copy.deepcopy(self.previous_log_entry)
        tracker = FileIdTracker()
        new_infos = []
        for path in self._new_files:
            st = os.stat(path)
            # Compacted files carry the digest recorded as they were
            # written (write_bucket_run); retained files keep the digests
            # their own build committed.
            new_infos.append(FileInfo(path, st.st_size, int(st.st_mtime_ns),
                                      -1, integrity.recorded_digest(path)))
        entry.content = Content.from_leaf_files(self._retained + new_infos)
        return entry

    def summary(self, outcome: str) -> OptimizeSummary:
        """The user-facing summary of a completed run (``outcome`` is
        what ``Action.run()`` returned)."""
        mergeable = getattr(self, "_candidates_cache", None) or {}
        return OptimizeSummary(
            index=self.index_name, mode=self.mode,
            outcome="ok" if outcome == "ok" else "noop",
            compacted_files=sum(len(fs) for fs in mergeable.values()),
            compacted_buckets=len(mergeable),
            written_files=len(self._new_files),
            version=self.base_id + 2 if outcome == "ok" else None)
