"""Row-level CDC ingest (ISSUE 20, docs/19-lifecycle.md).

Three halves of the CDC subsystem:

  - **Merge-on-read**: row-level upserts/deletes landing through the
    Delta/Iceberg commit logs become tracked merge debt on the index
    entry — a metadata-only quick refresh records the replaced/removed
    files and the hybrid rule applies the overlay at scan time,
    bit-equal to a rebuild — until the debt outgrows
    ``hyperspace.lifecycle.cdc.mergeDebtRatio`` and the real
    incremental refresh runs.
  - **Push-based detection**: the io/watch.py seam (inotify / store
    notification bus / poll fallback) wakes the daemon on source
    events, so measured staleness is bounded by event latency instead
    of ``lifecycle.intervalS``.
  - **Autonomous compaction**: ``optimizeIndex`` joins the policy
    ladder — small-file counts past the threshold schedule a journaled
    optimize on an otherwise-idle index; a SIGKILL mid-compaction
    leaves the index readable and the next cycle converges, over both
    LogStore backends.
"""

from __future__ import annotations

import glob
import os
import signal
import subprocess
import sys
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (
    Hyperspace,
    HyperspaceSession,
    IndexConfig,
    OptimizeSummary,
    col,
)
from hyperspace_tpu.io import watch
from hyperspace_tpu.lifecycle import cdc, policy
from hyperspace_tpu.lifecycle import journal as lifecycle_journal
from hyperspace_tpu.lifecycle.change_detector import (
    ChangeSummary,
    detect_changes,
)
from hyperspace_tpu.lifecycle.daemon import daemon_for
from hyperspace_tpu.sources.delta import DeltaLog, write_delta
from hyperspace_tpu.sources.delta.writer import (
    delete_rows_delta,
    upsert_delta,
)
from hyperspace_tpu.sources.iceberg.writer import (
    delete_rows_iceberg,
    upsert_iceberg,
    write_iceberg,
)
from hyperspace_tpu.telemetry.doctor import doctor

BOTH_STORES = ["hyperspace_tpu.io.log_store.PosixLogStore",
               "hyperspace_tpu.io.log_store.EmulatedObjectStore"]


def _table(ids, tag: int = 0) -> pa.Table:
    ids = list(ids)
    return pa.table({
        "id": pa.array(ids, type=pa.int64()),
        "name": pa.array([f"n{i}-{tag}" for i in ids]),
        "v": pa.array([i * 10 + tag for i in ids], type=pa.int64()),
    })


def _session(tmp_path, **conf):
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 4
    for k, v in conf.items():
        setattr(s.conf, k, v)
    return s


# ---------------------------------------------------------------------------
# The watch seam (io/watch.py)
# ---------------------------------------------------------------------------
class TestWatchSeam:
    def test_change_dir_finds_the_commit_log(self, tmp_path):
        plain = tmp_path / "plain"
        plain.mkdir()
        assert watch.change_dir(str(plain)) == str(plain)
        delta = tmp_path / "delta"
        (delta / "_delta_log").mkdir(parents=True)
        assert watch.change_dir(str(delta)) == str(delta / "_delta_log")
        ice = tmp_path / "ice"
        (ice / "metadata").mkdir(parents=True)
        assert watch.change_dir(str(ice)) == str(ice / "metadata")

    def _wait_wake(self, watcher, timeout_s: float = 8.0) -> float:
        t0 = time.monotonic()
        assert watcher.wake.wait(timeout_s), \
            f"no wake within {timeout_s}s (mode={watcher.mode})"
        return time.monotonic() - t0

    def test_poll_backend_wakes_on_write(self, tmp_path):
        src = tmp_path / "src"
        src.mkdir()
        s = _session(tmp_path, watch_poll_interval_s=0.05,
                     watch_debounce_ms=10.0)
        w = watch.SourceWatcher(s.conf, [str(src)], mode="poll").start()
        try:
            assert w.mode == "poll"
            pq.write_table(_table([1]), str(src / "a.parquet"))
            self._wait_wake(w)
            events = w.drain()
            assert events and events[0].root == str(src)
        finally:
            w.stop()

    def test_inotify_mode_detects_or_degrades(self, tmp_path):
        """Forced inotify works on Linux; where the kernel refuses it
        must DEGRADE to poll (never raise) and still detect."""
        src = tmp_path / "src"
        src.mkdir()
        s = _session(tmp_path, watch_poll_interval_s=0.05,
                     watch_debounce_ms=10.0)
        w = watch.SourceWatcher(s.conf, [str(src)], mode="inotify").start()
        try:
            assert w.mode in ("inotify", "poll")
            pq.write_table(_table([1]), str(src / "a.parquet"))
            self._wait_wake(w)
        finally:
            w.stop()

    @pytest.mark.parametrize("store_cls", BOTH_STORES)
    def test_store_bus_publish_wakes_watcher(self, tmp_path, store_cls):
        """The emulated object-store notification path: a writer-side
        publish() lands a marker on the LogStore bus; a store-mode
        watcher (constructed BEFORE the publish) wakes on it."""
        src = tmp_path / "src"
        src.mkdir()
        s = _session(tmp_path, log_store_class=store_cls,
                     watch_poll_interval_s=0.05, watch_debounce_ms=10.0)
        w = watch.SourceWatcher(s.conf, [str(src)], mode="store").start()
        try:
            assert w.mode == "store"
            key = watch.publish(s.conf, str(src), detail="commit 7")
            assert key is not None
            self._wait_wake(w)
            events = w.drain()
            assert any(e.root == str(src) and "commit 7" in e.detail
                       for e in events), events
        finally:
            w.stop()

    def test_torn_marker_still_wakes(self, tmp_path):
        """A half-written marker must wake the watcher anyway — losing
        a wake costs an interval, treating garbage as fatal costs the
        thread."""
        s = _session(tmp_path, watch_poll_interval_s=0.05,
                     watch_debounce_ms=0.0)
        w = watch.SourceWatcher(s.conf, [], mode="store").start()
        try:
            from hyperspace_tpu.io.log_store import store_for

            store = store_for(s.conf, watch.watch_store_root(s.conf))
            assert store.put_if_absent("w-torn", b"{not json")
            self._wait_wake(w)
        finally:
            w.stop()

    def test_publish_is_fault_quiet(self, tmp_path):
        """Bus IO must not consume the fault budget (same contract as
        the lifecycle journal): notifications are advisory."""
        from hyperspace_tpu.io import faults

        s = _session(tmp_path)
        plan = faults.FaultPlan(site="store.put", kind="eio", at=1, count=1)
        faults.install(plan)
        try:
            assert watch.publish(s.conf, str(tmp_path)) is not None
            assert plan._calls == 0
        finally:
            faults.clear()

    def test_marker_cap_bounds_the_bus(self, tmp_path):
        s = _session(tmp_path)
        for i in range(watch._MARKER_CAP + 10):
            assert watch.publish(s.conf, str(tmp_path), detail=str(i))
        from hyperspace_tpu.io.log_store import store_for

        store = store_for(s.conf, watch.watch_store_root(s.conf))
        assert len(store.list_keys()) <= watch._MARKER_CAP


class TestDaemonWatchWake:
    def test_event_bounds_staleness_below_the_poll_interval(self, tmp_path):
        """With a 30s cycle interval and the watch seam on, an append
        must be refreshed within seconds — the wake event, not the
        interval, bounds staleness."""
        src = str(tmp_path / "src")
        os.makedirs(src)
        pq.write_table(_table(range(100)), os.path.join(src, "p0.parquet"))
        s = _session(tmp_path, lineage_enabled=True,
                     lifecycle_enabled=True, lifecycle_interval_s=30.0,
                     watch_enabled=True, watch_mode="poll",
                     watch_poll_interval_s=0.05, watch_debounce_ms=10.0)
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("wix", ["id"],
                                                         ["v"]))
        hs.start_maintenance()
        try:
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:  # first cycle ran
                if lifecycle_journal.records(s.conf):
                    break
                time.sleep(0.05)
            else:
                pytest.fail("daemon never completed its first cycle")
            watcher = daemon_for(s).watcher()
            assert watcher is not None and watcher.mode == "poll"
            t0 = time.monotonic()
            pq.write_table(_table(range(100, 120)),
                           os.path.join(src, "p1.parquet"))
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                recs = lifecycle_journal.records(s.conf)
                if any(r.get("decision") == "refresh"
                       and r.get("outcome") == "done" for r in recs):
                    break
                time.sleep(0.05)
            else:
                pytest.fail("append never refreshed")
            elapsed = time.monotonic() - t0
            # The 30s interval never elapsed: the wake event did this.
            assert elapsed < 15.0
        finally:
            hs.stop_maintenance()


# ---------------------------------------------------------------------------
# The CDC policy rung (pure)
# ---------------------------------------------------------------------------
def _change(**kw) -> ChangeSummary:
    base = dict(index="i", appended=0, deleted=0, mutated=0,
                appended_bytes=0, recorded_files=10,
                recorded_bytes=1000, hybrid_debt_bytes=0)
    base.update(kw)
    return ChangeSummary(**base)


class TestPolicyCDC:
    def _decide(self, change, **kw):
        kw.setdefault("quarantined", 0)
        kw.setdefault("lineage", True)
        kw.setdefault("hybrid_scan", True)
        kw.setdefault("quick_append_ratio", 0.1)
        kw.setdefault("full_churn_ratio", 0.5)
        kw.setdefault("cdc_merge_on_read", True)
        kw.setdefault("merge_debt_ratio", 0.2)
        return policy.decide_refresh(change, **kw)

    def test_deletes_ride_quick_as_merge_debt(self):
        d = self._decide(_change(deleted=1, deleted_bytes=50))
        assert (d.kind, d.mode) == ("refresh", "quick")
        assert "CDC merge-on-read" in d.reason

    def test_mutations_ride_quick_too(self):
        d = self._decide(_change(appended=1, deleted=1, mutated=1,
                                 appended_bytes=50, deleted_bytes=50))
        assert (d.kind, d.mode) == ("refresh", "quick")

    def test_debt_past_budget_escalates_to_incremental(self):
        d = self._decide(_change(deleted=1, deleted_bytes=50,
                                 merge_debt_bytes=400))
        assert (d.kind, d.mode) == ("refresh", "incremental")
        assert "merge debt ratio" in d.reason

    def test_accumulated_debt_alone_schedules_the_refresh(self):
        # No NEW changes, but the carried overlay outgrew the budget —
        # and the journaled reason must say THAT, not "appended files".
        d = self._decide(_change(merge_debt_bytes=500))
        assert (d.kind, d.mode) == ("refresh", "incremental")
        assert "accumulated merge debt" in d.reason

    def test_no_lineage_still_full(self):
        d = self._decide(_change(deleted=1), lineage=False)
        assert (d.kind, d.mode) == ("refresh", "full")

    def test_hybrid_off_still_incremental(self):
        d = self._decide(_change(deleted=1), hybrid_scan=False)
        assert (d.kind, d.mode) == ("refresh", "incremental")

    def test_cdc_off_preserves_pr10_ladder(self):
        d = self._decide(_change(deleted=1), cdc_merge_on_read=False)
        assert (d.kind, d.mode) == ("refresh", "incremental")

    def test_compaction_decision_thresholds(self):
        stats = cdc.CompactionStats(index="i", total_files=10,
                                    small_files=6, mergeable_files=5,
                                    mergeable_buckets=2)
        assert cdc.decide_compaction(stats, min_small_files=6) is None
        assert cdc.decide_compaction(stats, min_small_files=0) is None
        d = cdc.decide_compaction(stats, min_small_files=4, mode="quick")
        assert d is not None and d.kind == policy.KIND_OPTIMIZE
        assert d.mode == "quick" and "small index file" in d.reason


# ---------------------------------------------------------------------------
# Merge-on-read over the lake seams (the tentpole acceptance)
# ---------------------------------------------------------------------------
def _seed_lake(fmt: str, path: str, files: int = 10) -> None:
    """``files`` separate commits => ``files`` data files, so one
    rewritten file is LOW churn (the full-rebuild rung must not mask
    the CDC quick path)."""
    writer = write_delta if fmt == "delta" else write_iceberg
    for i in range(files):
        writer(_table(range(i * 10, (i + 1) * 10)), path, mode="append")


def _lake_env(tmp_path, fmt: str, **conf):
    path = str(tmp_path / "t")
    # 20 files: one rewritten file per cycle stays WELL under the
    # full-churn ceiling (0.5), so the CDC rung is what decides.
    _seed_lake(fmt, path, files=20)
    s = _session(tmp_path, lineage_enabled=True, hybrid_scan_enabled=True,
                 lifecycle_cdc_enabled=True, **conf)
    hs = Hyperspace(s)
    reader = s.read.delta if fmt == "delta" else s.read.iceberg
    hs.create_index(reader(path), IndexConfig("cdx", ["id"], ["name"]))
    s.enable_hyperspace()
    return s, hs, path, reader


def _canonical(t: pa.Table) -> list:
    return sorted(zip(t.column("id").to_pylist(),
                      t.column("name").to_pylist()))


class TestMergeOnRead:
    @pytest.mark.parametrize("fmt", ["delta", "iceberg"])
    def test_upsert_stream_rides_quick_bit_equal(self, tmp_path, fmt):
        """A sustained upsert/delete stream: each cycle journals the
        CDC quick refresh, and every stable point answers BIT-EQUAL to
        the source scan (the hybrid overlay is the index's answer)."""
        s, hs, path, reader = _lake_env(
            tmp_path, fmt, lifecycle_cdc_merge_debt_ratio=5.0)
        upsert = upsert_delta if fmt == "delta" else upsert_iceberg
        del_rows = delete_rows_delta if fmt == "delta" \
            else delete_rows_iceberg
        quicks = 0
        for i in range(3):
            upsert(_table([5 + i, 200 + i], tag=i + 1), path, "id")
            del_rows(path, "id", [17 + i])
            recs = hs.maintenance_cycle()
            quick = [r for r in recs if r["decision"] == "refresh"
                     and r["mode"] == "quick" and r["outcome"] == "done"]
            assert quick, recs
            assert "CDC merge-on-read" in quick[0]["reason"]
            quicks += 1
            got = (reader(path).filter(col("id") >= 0)
                   .select("id", "name").collect())
            s.disable_hyperspace()
            try:
                want = (reader(path).filter(col("id") >= 0)
                        .select("id", "name").collect())
            finally:
                s.enable_hyperspace()
            assert _canonical(got) == _canonical(want)
            # Row-level semantics really applied: the upserted key
            # reads its NEW payload, the deleted key is gone.
            rows = dict(_canonical(got))
            assert rows[5 + i] == f"n{5 + i}-{i + 1}"
            assert 17 + i not in rows
        assert quicks == 3

    @pytest.mark.parametrize("fmt", ["delta", "iceberg"])
    def test_merge_debt_is_measured_on_the_entry(self, tmp_path, fmt):
        s, hs, path, reader = _lake_env(
            tmp_path, fmt, lifecycle_cdc_merge_debt_ratio=5.0)
        upsert = upsert_delta if fmt == "delta" else upsert_iceberg
        upsert(_table([3, 300], tag=9), path, "id")
        hs.maintenance_cycle()
        entry = s.index_collection_manager.get_index("cdx")
        debt = cdc.merge_debt(entry)
        assert debt.deleted_files >= 1 and debt.appended_files >= 1
        assert debt.total_bytes > 0 and debt.ratio > 0
        assert debt.readable  # lineage on: overlay applies at scan time
        assert debt.to_dict()["index"] == "cdx"

    def test_tight_budget_escalates_to_incremental(self, tmp_path):
        s, hs, path, reader = _lake_env(
            tmp_path, "delta", lifecycle_cdc_merge_debt_ratio=0.0001)
        upsert_delta(_table([3, 300], tag=9), path, "id")
        recs = hs.maintenance_cycle()
        inc = [r for r in recs if r["decision"] == "refresh"
               and r["mode"] == "incremental" and r["outcome"] == "done"]
        assert inc, recs
        # The incremental pass cleared the debt.
        entry = s.index_collection_manager.get_index("cdx")
        assert cdc.merge_debt(entry).total_bytes == 0

    def test_delete_rows_noop_when_nothing_matches(self, tmp_path):
        path = str(tmp_path / "t")
        write_delta(_table(range(10)), path)
        v = DeltaLog(path).latest_version()
        assert delete_rows_delta(path, "id", [999]) == v
        path2 = str(tmp_path / "t2")
        write_iceberg(_table(range(10)), path2)
        from hyperspace_tpu.sources.iceberg.metadata import IcebergTable

        snap = IcebergTable(path2).load_metadata().current_snapshot_id
        assert delete_rows_iceberg(path2, "id", [999]) == snap


# ---------------------------------------------------------------------------
# Mutated-file detection over both lake seams (satellite)
# ---------------------------------------------------------------------------
class TestMutatedFileDetection:
    def test_delta_inplace_rewrite_reads_as_mutated(self, tmp_path):
        """A commit re-adding the SAME path with drifted size/mtime —
        the shape an in-place data-file rewrite leaves in the commit
        log — must read as mutated (both triple sets + the name
        intersection), not as an unrelated append."""
        s, hs, path, reader = _lake_env(tmp_path, "delta")
        log = DeltaLog(path)
        victim = log.snapshot().files[0]
        rel = victim.path[len(log.table_path.rstrip("/")) + 1:]
        bigger = pa.concat_tables([pq.read_table(victim.path)] * 2)
        pq.write_table(bigger, victim.path)
        now_ms = int(time.time() * 1000)
        log.write_commit(log.latest_version() + 1, [
            {"remove": {"path": rel, "deletionTimestamp": now_ms,
                        "dataChange": True}},
            {"add": {"path": rel, "partitionValues": {},
                     "size": os.stat(victim.path).st_size,
                     "modificationTime": victim.modification_time + 1,
                     "dataChange": True}},
            {"commitInfo": {"timestamp": now_ms, "operation": "WRITE"}},
        ])
        entry = s.index_collection_manager.get_index("cdx")
        change = detect_changes(s, entry)
        assert change.mutated == 1
        assert change.appended == 1 and change.deleted == 1
        assert change.deleted_bytes > 0

    def test_iceberg_inplace_rewrite_reads_as_mutated(self, tmp_path):
        """Iceberg sizes come from the manifest but mtimes from
        ``os.stat`` — an in-place rewrite surfaces through the stat
        seam with NO new snapshot at all."""
        s, hs, path, reader = _lake_env(tmp_path, "iceberg")
        entry = s.index_collection_manager.get_index("cdx")
        victim = entry.source_file_infos()[0]
        time.sleep(0.02)  # mtime is ms-resolution: force a drift
        pq.write_table(pq.read_table(victim.name), victim.name)
        change = detect_changes(s, entry)
        assert change.mutated == 1
        assert change.appended == 1 and change.deleted == 1


# ---------------------------------------------------------------------------
# OptimizeSummary + autonomous compaction
# ---------------------------------------------------------------------------
def _shred_index(tmp_path, store_cls=None, rounds: int = 3):
    """An index shredded into small per-bucket files: initial build +
    ``rounds`` incremental refreshes (each lands one small file per
    touched bucket)."""
    src = str(tmp_path / "src")
    os.makedirs(src, exist_ok=True)
    pq.write_table(_table(range(200)), os.path.join(src, "p0.parquet"))
    s = _session(tmp_path, lineage_enabled=True)
    s.conf.num_buckets = 2
    if store_cls:
        s.conf.log_store_class = store_cls
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(src), IndexConfig("cix", ["id"], ["v"]))
    for i in range(rounds):
        pq.write_table(_table(range(1000 + i * 100, 1000 + i * 100 + 50)),
                       os.path.join(src, f"p{i + 1}.parquet"))
        hs.refresh_index("cix", "incremental")
    return s, hs, src


class TestOptimizeSummary:
    def test_optimize_returns_counts_and_version(self, tmp_path):
        s, hs, src = _shred_index(tmp_path)
        entry = s.index_collection_manager.get_index("cix")
        stats = cdc.compaction_stats(entry,
                                     s.conf.optimize_file_size_threshold)
        assert stats.mergeable_files >= 2 and stats.mergeable_buckets >= 1
        summary = hs.optimize_index("cix")
        assert isinstance(summary, OptimizeSummary)
        assert summary.outcome == "ok" and summary.mode == "quick"
        assert summary.compacted_files == stats.mergeable_files
        assert summary.compacted_buckets == stats.mergeable_buckets
        assert 0 < summary.written_files < summary.compacted_files
        assert summary.version is not None
        assert summary.to_dict()["index"] == "cix"
        # A second optimize has nothing to merge: a noop summary, not
        # an exception.
        again = hs.optimize_index("cix")
        assert again.outcome == "noop" and again.version is None
        assert again.compacted_files == 0

    def test_compaction_stats_skip_non_covering(self, tmp_path):
        s, hs, src = _shred_index(tmp_path, rounds=0)
        entry = s.index_collection_manager.get_index("cix")
        big = cdc.compaction_stats(entry, size_threshold=1)
        assert big.small_files == 0 and big.mergeable_files == 0


class TestAutonomousCompaction:
    def test_daemon_journals_the_optimize(self, tmp_path):
        """An idle-but-shredded index: the refresh ladder says none,
        the compaction rung schedules the optimize, the journal proves
        it — and answers stay bit-equal after."""
        s, hs, src = _shred_index(tmp_path)
        s.conf.lifecycle_compaction_enabled = True
        s.conf.lifecycle_compaction_min_small_files = 2
        s.enable_hyperspace()
        recs = hs.maintenance_cycle()
        opt = [r for r in recs if r["decision"] == "optimize"]
        assert opt and opt[0]["outcome"] == "done", recs
        assert "small index file" in opt[0]["reason"]
        assert opt[0]["mode"] == "quick"
        # Converged: the next cycle has nothing to compact.
        recs = hs.maintenance_cycle()
        assert all(r["decision"] != "optimize" or r["outcome"] == "noop"
                   for r in recs), recs
        got = (s.read.parquet(src).filter(col("id") >= 0)
               .select("id", "v").collect())
        want = pq.read_table(sorted(glob.glob(os.path.join(src, "*.parquet"))),
                             columns=["id", "v"])
        assert sorted(zip(got.column("id").to_pylist(),
                          got.column("v").to_pylist())) == \
            sorted(zip(want.column("id").to_pylist(),
                       want.column("v").to_pylist()))

    def test_compaction_never_masks_a_refresh(self, tmp_path):
        s, hs, src = _shred_index(tmp_path)
        s.conf.lifecycle_compaction_enabled = True
        s.conf.lifecycle_compaction_min_small_files = 2
        pq.write_table(_table(range(5000, 5050)),
                       os.path.join(src, "late.parquet"))
        recs = hs.maintenance_cycle()
        assert any(r["decision"] == "refresh" and r["outcome"] == "done"
                   for r in recs), recs
        assert all(r["decision"] != "optimize" for r in recs), recs

    @pytest.mark.parametrize("store_cls", BOTH_STORES)
    def test_sigkill_mid_compaction_converges(self, tmp_path, store_cls):
        """A REAL SIGKILL mid-optimize (after the first bucket file is
        written, before commit): the stable entry still serves, the
        transient OPTIMIZING corpse is visible, and the next cycle
        recovers + lands the compaction — journal-proven, both
        backends."""
        s, hs, src = _shred_index(tmp_path, store_cls=store_cls)
        child = f"""
import os, signal, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import hyperspace_tpu.actions.optimize as opt
from hyperspace_tpu import Hyperspace, HyperspaceSession

s = HyperspaceSession(system_path={str(tmp_path / 'ix')!r})
s.conf.log_store_class = {store_cls!r}
s.conf.num_buckets = 2
s.conf.parallel_build = "off"
_orig = opt.write_bucket_run
def _killer(*a, **kw):
    out = _orig(*a, **kw)
    os.kill(os.getpid(), signal.SIGKILL)
    return out
opt.write_bucket_run = _killer
Hyperspace(s).optimize_index("cix", "quick")
print("UNREACHABLE")
"""
        proc = subprocess.run([sys.executable, "-c", child],
                              capture_output=True, text=True, timeout=240)
        assert proc.returncode == -signal.SIGKILL, (proc.stdout,
                                                    proc.stderr)
        assert "UNREACHABLE" not in proc.stdout
        # The kill landed mid-action: transient OPTIMIZING atop a
        # stable ACTIVE entry — the index is still readable.
        mgr = s.index_collection_manager._log_manager("cix")
        assert mgr.get_latest_log().state == "OPTIMIZING"
        entry = s.index_collection_manager.get_index("cix")
        assert entry is not None and entry.state == "ACTIVE"
        s.enable_hyperspace()
        got = (s.read.parquet(src).filter(col("id") == 3)
               .select("id", "v").collect())
        assert got.column("v").to_pylist() == [30]
        # Next cycle: auto-recovery rolls the corpse back, the
        # compaction rung re-schedules, the journal proves convergence.
        s.conf.auto_recovery_enabled = True
        s.conf.lifecycle_compaction_enabled = True
        s.conf.lifecycle_compaction_min_small_files = 2
        recs = hs.maintenance_cycle()
        opt_recs = [r for r in recs if r["decision"] == "optimize"]
        assert opt_recs and opt_recs[0]["outcome"] == "done", recs
        assert mgr.get_latest_log().state == "ACTIVE"
        recs = hs.maintenance_cycle()
        assert all(r["decision"] != "optimize" or r["outcome"] == "noop"
                   for r in recs), recs


# ---------------------------------------------------------------------------
# doctor(): the cdc.merge_debt check (satellite)
# ---------------------------------------------------------------------------
class TestDoctorMergeDebt:
    def test_clean_tree_is_ok(self, tmp_path):
        s, hs, src = _shred_index(tmp_path, rounds=0)
        check = doctor(s).check("cdc.merge_debt")
        assert check is not None and check.status == "ok"

    def test_debt_past_budget_warns(self, tmp_path):
        src = str(tmp_path / "src")
        os.makedirs(src)
        pq.write_table(_table(range(100)), os.path.join(src, "p0.parquet"))
        s = _session(tmp_path, lineage_enabled=True,
                     hybrid_scan_enabled=True)
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("dix", ["id"],
                                                         ["v"]))
        pq.write_table(_table(range(100, 120)),
                       os.path.join(src, "p1.parquet"))
        hs.refresh_index("dix", "quick")
        s.conf.lifecycle_cdc_merge_debt_ratio = 1e-9
        check = doctor(s).check("cdc.merge_debt")
        assert check.status == "warn"
        assert "dix" in check.data["over_budget"]

    def test_unreadable_delete_overlay_is_crit(self, tmp_path):
        """A delete overlay WITHOUT lineage: hybrid candidate math
        drops the entry, every query silently full-scans the source —
        the index serves nothing.  That is a crit, not a warn."""
        src = str(tmp_path / "src")
        os.makedirs(src)
        for i in range(4):
            pq.write_table(_table(range(i * 25, (i + 1) * 25)),
                           os.path.join(src, f"p{i}.parquet"))
        s = _session(tmp_path, lineage_enabled=False,
                     hybrid_scan_enabled=True)
        hs = Hyperspace(s)
        hs.create_index(s.read.parquet(src), IndexConfig("nix", ["id"],
                                                         ["v"]))
        os.remove(os.path.join(src, "p3.parquet"))
        hs.refresh_index("nix", "quick")
        check = doctor(s).check("cdc.merge_debt")
        assert check.status == "crit"
        assert "nix" in check.data["unreadable"]
