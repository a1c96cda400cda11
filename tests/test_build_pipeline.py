"""The overlapped build pipeline (actions/create.py): async prefetch,
fused route+partition kernel, streaming bucket-group finalize.

The contract every test here enforces: the pipeline may change
SCHEDULING, never LAYOUT.  ``hyperspace.index.build.pipeline.enabled``
off is the forced-serial reference (inline reads, inline routing,
sequential finalize); on is the overlapped builder — and the two must
produce BIT-equal index trees, under injected faults, across both
LogStore backends, on both key routes (value-mapped keys with
ride-along sort codes, rank-mapped string keys without)."""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
from collections import defaultdict

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
from hyperspace_tpu.io.parquet import bucket_id_of_file

OBJECT_MANAGER = "hyperspace_tpu.index.object_log_manager.ObjectStoreLogManager"
POSIX_MANAGER = "hyperspace_tpu.index.log_manager.IndexLogManager"


def _write_source(root, n=4000, n_files=5, string_key=False):
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(11)
    cols = {
        "k": pa.array([f"key-{v:06d}" for v in
                       rng.integers(0, 700, n)], type=pa.string())
        if string_key else
        pa.array(rng.integers(0, 700, n), type=pa.int64()),
        "v": pa.array(rng.random(n)),
        "w": pa.array(rng.integers(-50, 50, n), type=pa.int32()),
    }
    t = pa.table(cols)
    step = -(-n // n_files)
    for i in range(n_files):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(root, f"part-{i:05d}.parquet"))


def _build(root, data, name, *, pipelined, batch_rows=512,
           backend=POSIX_MANAGER, **conf):
    """One spill-forced single-chip build under the given pipeline mode;
    returns (session, hyperspace, log entry)."""
    s = HyperspaceSession(system_path=os.path.join(root, f"ix-{name}"))
    s.conf.num_buckets = 4
    s.conf.parallel_build = "off"  # the spill path is single-chip
    s.conf.device_batch_rows = batch_rows
    s.conf.build_pipeline_enabled = pipelined
    s.conf.log_manager_class = backend
    for k, v in conf.items():
        setattr(s.conf, k, v)
    hs = Hyperspace(s)
    hs.create_index(s.read.parquet(data), IndexConfig(name, ["k"], ["v", "w"]))
    return s, hs, s.index_collection_manager.get_index(name)


def _bucket_digests(entry):
    """bucket -> sorted content digests of its files (the bit-equality
    artifact: parquet encode is deterministic for equal tables/codec)."""
    out = defaultdict(list)
    for f in entry.content.file_infos():
        with open(f.name, "rb") as fh:
            out[bucket_id_of_file(f.name)].append(
                hashlib.sha256(fh.read()).hexdigest())
    return {b: sorted(digests) for b, digests in out.items()}


class TestBitEquality:
    def test_pipelined_bit_equal_serial(self, tmp_path):
        data = str(tmp_path / "data")
        _write_source(data)
        _, _, serial = _build(str(tmp_path), data, "ser", pipelined=False)
        _, _, piped = _build(str(tmp_path), data, "pip", pipelined=True)
        assert _bucket_digests(serial) == _bucket_digests(piped)

    def test_pipelined_bit_equal_monolithic(self, tmp_path):
        """Spill + pipeline vs the one-batch fused-kernel build: same
        bytes (the tie-order contract: runs concatenate in chunk order,
        the code merge is stable)."""
        data = str(tmp_path / "data")
        _write_source(data)
        _, _, mono = _build(str(tmp_path), data, "mono", pipelined=True,
                            batch_rows=1 << 20)
        _, _, piped = _build(str(tmp_path), data, "pip", pipelined=True)
        assert _bucket_digests(mono) == _bucket_digests(piped)

    def test_string_key_route_bit_equal(self, tmp_path):
        """Rank-mapped keys (strings) cannot ride chunk-local sort codes
        — the route stays grouped-only and finalize re-derives order
        words per bucket.  Still bit-equal across all three modes."""
        data = str(tmp_path / "data")
        _write_source(data, string_key=True)
        _, _, serial = _build(str(tmp_path), data, "ser", pipelined=False)
        _, _, piped = _build(str(tmp_path), data, "pip", pipelined=True)
        _, _, mono = _build(str(tmp_path), data, "mono", pipelined=True,
                            batch_rows=1 << 20)
        assert _bucket_digests(serial) == _bucket_digests(piped)
        assert _bucket_digests(mono) == _bucket_digests(piped)

    def test_device_route_bit_equal_host_mirror(self, tmp_path):
        """The fused route_partition kernel vs its bit-identical host
        mirror: pinning device_build_min_rows to 0 forces every chunk
        through the device path; a huge pin forces the mirror.  Layout
        must not depend on the route."""
        data = str(tmp_path / "data")
        _write_source(data)
        _, _, dev = _build(str(tmp_path), data, "dev", pipelined=True,
                           device_build_min_rows=0)
        _, _, host = _build(str(tmp_path), data, "host", pipelined=True,
                            device_build_min_rows=1 << 30)
        assert _bucket_digests(dev) == _bucket_digests(host)

    def test_max_rows_split_bit_equal(self, tmp_path):
        data = str(tmp_path / "data")
        _write_source(data)
        _, _, serial = _build(str(tmp_path), data, "ser", pipelined=False,
                              index_max_rows_per_file=257)
        _, _, piped = _build(str(tmp_path), data, "pip", pipelined=True,
                             index_max_rows_per_file=257)
        assert _bucket_digests(serial) == _bucket_digests(piped)

    def test_pipelined_build_answers_queries(self, tmp_path):
        from tests.utils import canonical_rows

        data = str(tmp_path / "data")
        _write_source(data)
        s, _, _ = _build(str(tmp_path), data, "q", pipelined=True)
        s.enable_hyperspace()
        ds = s.read.parquet(data).filter(col("k") == 123).select("k", "v")
        plan = ds.optimized_plan()
        assert [x for x in plan.leaf_relations()
                if x.relation.index_scan_of]
        got = ds.collect()
        s.disable_hyperspace()
        assert canonical_rows(got) == canonical_rows(ds.collect())


class TestKernelParity:
    def test_route_partition_matches_bucket_sort(self):
        """The fused route pass and the monolithic kernel share ONE
        lexsort program — same buckets, same permutation."""
        from hyperspace_tpu.io import columnar
        from hyperspace_tpu.ops.hash import route_partition_np
        from hyperspace_tpu.ops.sort import bucket_sort_permutation_np

        rng = np.random.default_rng(3)
        keys = pa.array(rng.integers(-1000, 1000, 5000), type=pa.int64())
        words = [np.asarray(columnar.to_hash_words(keys))]
        order = [np.asarray(columnar.to_order_words(keys))]
        b1, p1 = route_partition_np(words, order, 8)
        b2, p2 = bucket_sort_permutation_np(words, order, 8)
        np.testing.assert_array_equal(b1, b2)
        np.testing.assert_array_equal(p1, p2)

    def test_route_partition_device_matches_np(self):
        from hyperspace_tpu.io import columnar
        from hyperspace_tpu.ops.hash import (
            route_partition,
            route_partition_np,
        )

        rng = np.random.default_rng(5)
        keys = pa.array(rng.integers(0, 97, 3000), type=pa.int64())
        words = [np.asarray(columnar.to_hash_words(keys))]
        order = [np.asarray(columnar.to_order_words(keys))]
        bd, pd_ = route_partition(words, order, 4, pad_to=1024)
        bn, pn = route_partition_np(words, order, 4)
        np.testing.assert_array_equal(np.asarray(bd), bn)
        np.testing.assert_array_equal(np.asarray(pd_), pn)

    def test_route_partition_grouping_only(self):
        """Empty order_words = partition-only mode: rows grouped by
        bucket, ORIGINAL order preserved within each bucket (what the
        rank-mapped route relies on)."""
        from hyperspace_tpu.io import columnar
        from hyperspace_tpu.ops.hash import route_partition_np

        rng = np.random.default_rng(7)
        keys = pa.array(rng.integers(0, 50, 2000), type=pa.int64())
        words = [np.asarray(columnar.to_hash_words(keys))]
        buckets, perm = route_partition_np(words, [], 4)
        grouped = buckets[perm]
        assert (np.diff(grouped) >= 0).all()  # grouped by bucket
        for b in range(4):
            rows = perm[grouped == b]
            assert (np.diff(rows) > 0).all()  # stable: original order


def _spill_dirs():
    """This process's spill dirs: they are pid-stamped, so builds that
    other test workers run meanwhile in the same temp root do not count."""
    import tempfile

    from hyperspace_tpu.actions.create import (
        _SPILL_DIR_KINDS,
        _spill_dir_prefix,
    )

    mine = tuple(_spill_dir_prefix(kind) for kind in _SPILL_DIR_KINDS)
    return {n for n in os.listdir(tempfile.gettempdir())
            if n.startswith(mine)}


@pytest.fixture(params=["posix", "object_store"])
def backend(request):
    return POSIX_MANAGER if request.param == "posix" else OBJECT_MANAGER


class TestFaultMatrix:
    """eio/enospc/torn at ``data.write``, crash at ``action.commit``,
    ``io.delete`` during finalize — over BOTH LogStore backends.  Every
    failure must leave no spill temp dir behind (the cleanup ``finally``
    covers the route/finalize worker threads), leave no committed
    index, and a post-fault retry must build cleanly."""

    @pytest.mark.parametrize("kind", ["eio", "enospc", "torn"])
    def test_data_write_faults(self, tmp_path, backend, kind):
        from hyperspace_tpu.io import faults

        data = str(tmp_path / "data")
        _write_source(data)
        before = _spill_dirs()
        faults.install(faults.FaultPlan(site="data.write", kind=kind))
        exc = faults.InjectedCrash if kind == "torn" else OSError
        with pytest.raises(exc):
            _build(str(tmp_path), data, "f", pipelined=True,
                   backend=backend)
        faults.clear()
        assert _spill_dirs() == before, "spill temp dir leaked"
        s = HyperspaceSession(system_path=os.path.join(
            str(tmp_path), "ix-f"))
        s.conf.log_manager_class = backend
        assert s.index_collection_manager.get_index("f") is None
        # Post-fault: the same name builds cleanly (the transient entry
        # rolls back through auto-recovery).
        s2, _, entry = _build(str(tmp_path), data, "f", pipelined=True,
                              backend=backend,
                              auto_recovery_enabled=True)
        assert entry is not None and entry.state == "ACTIVE"

    def test_crash_at_commit(self, tmp_path, backend):
        from hyperspace_tpu.io import faults

        data = str(tmp_path / "data")
        _write_source(data)
        before = _spill_dirs()
        faults.install(faults.FaultPlan(site="action.commit",
                                        kind="crash"))
        with pytest.raises(faults.InjectedCrash):
            _build(str(tmp_path), data, "c", pipelined=True,
                   backend=backend)
        faults.clear()
        # The spill dir was consumed by finish() BEFORE the commit
        # checkpoint — a crash there must not find one either.
        assert _spill_dirs() == before
        s = HyperspaceSession(system_path=os.path.join(
            str(tmp_path), "ix-c"))
        s.conf.log_manager_class = backend
        mgr = s.index_collection_manager._log_manager("c")
        assert mgr.get_latest_log().state == "CREATING"
        assert mgr.get_latest_stable_log() is None
        _, _, entry = _build(str(tmp_path), data, "c", pipelined=True,
                             backend=backend, auto_recovery_enabled=True)
        assert entry is not None and entry.state == "ACTIVE"

    def test_io_delete_during_finalize(self, tmp_path, backend):
        """The FIRST io.delete of a pipelined spill build is the
        finalize pool's consumed-group file removal: an eio there must
        fail the build loudly (not silently strand spill bytes), clean
        up, and leave the name rebuildable."""
        from hyperspace_tpu.io import faults

        data = str(tmp_path / "data")
        _write_source(data)
        before = _spill_dirs()
        faults.install(faults.FaultPlan(site="io.delete", kind="eio"))
        with pytest.raises(OSError):
            _build(str(tmp_path), data, "d", pipelined=True,
                   backend=backend)
        faults.clear()
        assert _spill_dirs() == before
        _, _, entry = _build(str(tmp_path), data, "d", pipelined=True,
                             backend=backend, auto_recovery_enabled=True)
        assert entry is not None and entry.state == "ACTIVE"


class TestReportContracts:
    def test_phase_sum_within_band(self, tmp_path):
        """The monolithic (non-overlapped) build's phase seconds must
        still sum to within 10% of the action wall clock — the PR 6
        audit the pipeline must not break.  (Overlapped SPILL builds
        attribute worker-thread seconds and may legitimately exceed
        wall; the band applies to the non-overlapped path.)"""
        data = str(tmp_path / "data")
        _write_source(data)
        _, hs, _ = _build(str(tmp_path), data, "band", pipelined=True,
                          batch_rows=1 << 20)
        report = hs.last_build_report()
        coverage = report.phase_total_s() / max(report.wall_s, 1e-9)
        assert 0.90 <= coverage <= 1.10, report.to_dict()["phases_s"]

    def test_pipelined_report_has_stall_phases(self, tmp_path):
        data = str(tmp_path / "data")
        _write_source(data)
        _, hs, _ = _build(str(tmp_path), data, "ph", pipelined=True)
        report = hs.last_build_report()
        phases = report.phases
        assert phases.get("spill_route", 0) > 0
        assert phases.get("spill_finish", 0) > 0
        assert "prefetch" in phases   # consumer stall attribution
        assert "finalize" in phases   # exposed finalize tail
        assert report.properties["prefetch_depth"] >= 1
        serial_hs = _build(str(tmp_path), data, "ph2",
                           pipelined=False)[1]
        serial_phases = serial_hs.last_build_report().phases
        assert "prefetch" not in serial_phases
        assert "finalize" not in serial_phases

    def test_prefetch_backpressure_bounds_memory(self, tmp_path):
        """The depth bound IS the memory bound: the prefetcher never
        holds more decoded-unconsumed chunks than prefetchDepth, and
        with the timeline sampler on, the per-phase RSS high-water
        marks exist to prove where the build peaks."""
        from hyperspace_tpu.telemetry import timeline as _timeline

        data = str(tmp_path / "data")
        _write_source(data, n=8000, n_files=8)
        try:
            for depth in (1, 3):
                _, hs, _ = _build(
                    str(tmp_path), data, f"bp{depth}", pipelined=True,
                    build_prefetch_depth=depth, timeline_enabled=True,
                    timeline_memory_sample_ms=2.0)
                report = hs.last_build_report()
                assert report.properties["prefetch_depth"] == depth
                assert report.properties["prefetch_peak_chunks"] <= depth
                marks = report.phase_memory_mb()
                assert marks, "no per-phase RSS high-water marks"
                assert max(marks.values()) < 16 * 1024  # sane MB figure
        finally:
            _timeline.disable_timeline()

    def test_busy_matrix_has_pipeline_lanes(self, tmp_path):
        from hyperspace_tpu.telemetry import timeline as _timeline

        data = str(tmp_path / "data")
        _write_source(data)
        try:
            _, hs, _ = _build(str(tmp_path), data, "lanes",
                              pipelined=True, timeline_enabled=True)
            lanes = hs.last_build_report().lane_report()["lanes"]
            for lane in ("read", "spill_route", "spill_finish",
                         "finalize"):
                assert lane in lanes, sorted(lanes)
        finally:
            _timeline.disable_timeline()


class TestRefreshPipeline:
    def test_full_refresh_rides_pipeline_bit_equal(self, tmp_path):
        """Refresh shares RefreshActionBase/_BucketSpill: a spill-forced
        full refresh takes the same pipeline (stall phases present) and
        stays bit-equal to a serial refresh of the same state."""
        data = str(tmp_path / "data")
        _write_source(data)
        results = {}
        for mode, pipelined in (("ser", False), ("pip", True)):
            s, hs, _ = _build(str(tmp_path), data, f"r{mode}",
                              pipelined=pipelined)
            pq.write_table(pa.table({
                "k": pa.array([9999], type=pa.int64()),
                "v": pa.array([0.5]),
                "w": pa.array([1], type=pa.int32()),
            }), os.path.join(data, "part-90000.parquet"))
            hs.refresh_index(f"r{mode}", "full")
            results[mode] = _bucket_digests(
                s.index_collection_manager.get_index(f"r{mode}"))
            if pipelined:
                phases = hs.last_build_report().phases
                assert "finalize" in phases and "prefetch" in phases
            os.unlink(os.path.join(data, "part-90000.parquet"))
        assert results["ser"] == results["pip"]

    def test_incremental_refresh_prefetches_appends(self, tmp_path):
        data = str(tmp_path / "data")
        _write_source(data)
        s, hs, _ = _build(str(tmp_path), data, "inc", pipelined=True,
                          lineage_enabled=True)
        for i in range(3):
            pq.write_table(pa.table({
                "k": pa.array([10000 + i], type=pa.int64()),
                "v": pa.array([0.25]),
                "w": pa.array([i], type=pa.int32()),
            }), os.path.join(data, f"part-9{i:04d}.parquet"))
        summary = hs.refresh_index("inc", "incremental")
        assert summary.outcome == "ok" and summary.appended == 3
        s.enable_hyperspace()
        out = (s.read.parquet(data).filter(col("k") == 10001)
               .select("k", "v").collect())
        assert out.num_rows == 1


class TestOrphanReap:
    def test_reap_only_provably_dead_owners(self, tmp_path):
        from hyperspace_tpu.actions.create import reap_orphan_spill_dirs

        root = str(tmp_path / "tmproot")
        os.makedirs(root)
        # A pid that existed and is now provably dead.
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        dead = os.path.join(root, f"hs_build_spill_{proc.pid}_abc")
        mine = os.path.join(root, f"hs_zbuild_{os.getpid()}_def")
        legacy = os.path.join(root, "hs_build_spill_legacy")
        other = os.path.join(root, "something_else")
        for d in (dead, mine, legacy, other):
            os.makedirs(d)
        assert reap_orphan_spill_dirs(tmp_root=root) == 1
        assert not os.path.exists(dead)
        assert os.path.exists(mine)     # our own live build
        assert os.path.exists(legacy)   # ownership unprovable: left
        assert os.path.exists(other)    # not a spill dir

    def test_build_start_reaps_orphans(self, tmp_path, monkeypatch):
        import tempfile

        from hyperspace_tpu.io import faults

        data = str(tmp_path / "data")
        _write_source(data)
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        orphan = os.path.join(tempfile.gettempdir(),
                              f"hs_build_spill_{proc.pid}_orphan")
        os.makedirs(orphan, exist_ok=True)
        try:
            _build(str(tmp_path), data, "reap", pipelined=True)
            assert not os.path.exists(orphan)
        finally:
            faults.clear()
            if os.path.exists(orphan):
                import shutil

                shutil.rmtree(orphan, ignore_errors=True)
