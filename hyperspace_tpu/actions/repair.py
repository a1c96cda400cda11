"""RepairAction: rebuild ONLY the quarantined buckets of an index.

The self-heal half of the integrity loop (docs/15-integrity.md).  After
scrub/containment has quarantined damaged index data files
(index/quarantine.py), ``refresh_index(name, mode="repair")`` re-derives
exactly those buckets' rows from the RECORDED source snapshot and
commits a new entry whose content keeps every healthy file and swaps the
damaged buckets for fresh ones — an optimize-shaped, index-only commit,
not a full rebuild.  Afterwards the quarantine records the repair made
obsolete are cleared, so the next query serves entirely from the index
again.

Soundness hinges on the snapshot check in validate(): a repaired bucket
must hold the rows the ORIGINAL build put there, so every recorded
source file must still exist with its recorded (size, mtime).  Source
that drifted since indexing is a refresh problem, not a repair problem —
validate says so explicitly.  Bucket membership is recomputed with the
build kernel's bit-identical host mirror (ops/hash.bucket_ids_np), so a
repaired bucket can never capture a different row set than the build
assigned.
"""

from __future__ import annotations

import copy
import os
from typing import List, Optional

import numpy as np
import pyarrow as pa

from hyperspace_tpu.actions.refresh import RefreshActionBase
from hyperspace_tpu.exceptions import HyperspaceError, NoChangesError
from hyperspace_tpu.index.data_manager import IndexDataManager
from hyperspace_tpu.index.log_entry import Content, FileInfo, IndexLogEntry, States
from hyperspace_tpu.index.log_manager import IndexLogManager
from hyperspace_tpu.index.quarantine import QuarantineManager, quarantine_manager_for
from hyperspace_tpu.io import columnar, integrity
from hyperspace_tpu.io.parquet import (
    bucket_id_of_file,
    sort_permutation_host,
    write_bucket_run,
    write_zorder_run,
)
from hyperspace_tpu.ops.hash import bucket_ids_np
from hyperspace_tpu.telemetry.events import RefreshActionEvent


class RepairAction(RefreshActionBase):
    """Partial rebuild of the quarantined buckets; REFRESHING transient
    state (it is a refresh mode), ACTIVE final state."""

    transient_state = States.REFRESHING
    final_state = States.ACTIVE
    event_class = RefreshActionEvent
    mode_name = "repair"

    def __init__(self, log_manager: IndexLogManager,
                 data_manager: IndexDataManager, session,
                 previous: Optional[IndexLogEntry] = None,
                 quarantine: Optional[QuarantineManager] = None) -> None:
        super().__init__(log_manager, data_manager, session, previous)
        self.quarantine = quarantine if quarantine is not None \
            else quarantine_manager_for(session.conf, data_manager.index_path)
        self._new_files: List[str] = []
        self._retained: List[FileInfo] = []
        self._target_buckets: tuple = ()

    # -- validation ----------------------------------------------------------
    def validate(self) -> None:
        if self.previous_log_entry is None or \
                self.previous_log_entry.state != States.ACTIVE:
            raise HyperspaceError(
                f"Repair is only supported in {States.ACTIVE} state")
        entry = self._previous_entry
        if not entry.is_covering:
            raise HyperspaceError(
                "Repair applies to covering indexes; rebuild a "
                "data-skipping index with refresh_index(mode='full')")
        qpaths = self.quarantine.paths()
        flagged = [f for f in entry.content.file_infos()
                   if f.name in qpaths]
        if not flagged:
            raise NoChangesError(
                "no quarantined index files; nothing to repair")
        buckets = {bucket_id_of_file(f.name) for f in flagged}
        if None in buckets:
            raise HyperspaceError(
                "cannot map a quarantined file to its bucket; run "
                "refresh_index(mode='full') instead")
        # The rebuilt buckets must reproduce the INDEXED snapshot, so the
        # snapshot must still be on disk, byte for byte by (size, mtime).
        for f in entry.source_file_infos():
            try:
                st = os.stat(f.name)
            except OSError:
                raise HyperspaceError(
                    f"repair needs the indexed source snapshot, but "
                    f"{f.name!r} is gone; run refresh_index instead")
            if st.st_size != f.size or int(st.st_mtime_ns) != f.mtime:
                raise HyperspaceError(
                    f"source file {f.name!r} changed since indexing; "
                    f"repair would mix snapshots — run refresh_index "
                    f"(mode='full' or 'incremental') instead")
        self._target_buckets = tuple(sorted(buckets))

    # -- the partial rebuild -------------------------------------------------
    def op(self) -> None:
        integrity.configure_from_conf(self.conf)
        entry = self._previous_entry
        resolved = self._resolved_config()
        relation = self._relation()
        lineage = self.lineage_enabled
        columns = resolved.all_columns
        affected = set(self._target_buckets)
        self._retained = [f for f in entry.content.file_infos()
                          if bucket_id_of_file(f.name) not in affected]
        # The recorded snapshot, read through the build's own chunk
        # reader (schema normalization + lineage ids identical to
        # create/refresh).  Monolithic read: repair is bounded by the
        # damaged buckets' share of the source, and runs off the query
        # path — the streaming spill machinery would buy nothing here.
        table = pa.concat_tables(
            [self._read_chunk(f, columns, relation, lineage)
             for f in entry.source_file_infos()],
            promote_options="default")
        word_cols = [np.asarray(columnar.to_hash_words(table.column(c)))
                     for c in resolved.indexed_columns]
        row_buckets = bucket_ids_np(word_cols, self.num_buckets)
        mask = np.isin(row_buckets,
                       np.asarray(self._target_buckets,
                                  dtype=row_buckets.dtype))
        sub = table.filter(pa.array(mask))
        sub_buckets = row_buckets[mask]
        order = np.argsort(sub_buckets, kind="stable")
        routed = sub.take(pa.array(order))
        sorted_buckets = sub_buckets[order]

        version = self.data_manager.get_next_version()
        out_dir = self.data_manager.version_path(version)
        os.makedirs(out_dir, exist_ok=True)
        max_rows = self.conf.index_max_rows_per_file
        compression = self.conf.index_file_compression
        layout = resolved.layout
        new_files: List[str] = []
        starts = np.searchsorted(sorted_buckets, self._target_buckets, "left")
        ends = np.searchsorted(sorted_buckets, self._target_buckets, "right")
        with self.build_report.phase("write"):
            for b, lo, hi in zip(self._target_buckets, starts, ends):
                rows = int(hi - lo)
                if rows == 0:
                    continue
                bt = routed.slice(int(lo), rows)
                if layout == "zorder":
                    new_files.extend(write_zorder_run(
                        bt, int(b), out_dir, max_rows,
                        resolved.indexed_columns, compression=compression))
                else:
                    perm = sort_permutation_host(
                        bt, resolved.indexed_columns, layout)
                    bt = bt.take(pa.array(perm))
                    new_files.extend(write_bucket_run(
                        bt, int(b), out_dir, max_rows,
                        compression=compression))
        self.build_report.add_bytes(
            written=sum(os.stat(p).st_size for p in new_files),
            files=len(new_files))
        # Per-file min/max sketch for the new version dir, like every
        # build/compaction — repaired buckets keep pruning effective.
        from hyperspace_tpu.actions.data_skipping import write_index_file_sketch

        write_index_file_sketch(out_dir, resolved.indexed_columns)
        self._written_version = version
        self._new_files = new_files

    def log_entry(self) -> IndexLogEntry:
        entry = copy.deepcopy(self._previous_entry)
        new_infos = []
        for path in self._new_files:
            st = os.stat(path)
            new_infos.append(FileInfo(path, st.st_size, int(st.st_mtime_ns),
                                      -1, integrity.recorded_digest(path)))
        entry.content = Content.from_leaf_files(self._retained + new_infos)
        return entry

    def run(self) -> None:
        super().run()
        # Commit succeeded (or no-opped): clear every quarantine record
        # the current entry no longer references — the repaired files for
        # a real run, stale leftovers for a no-op.  Records still naming
        # a referenced file (shouldn't exist after a successful repair)
        # are deliberately kept.
        latest = self.log_manager.get_latest_stable_log()
        referenced = {f.name for f in latest.content.file_infos()} \
            if latest is not None else set()
        for path in self.quarantine.paths():
            if path not in referenced:
                self.quarantine.remove(path)
