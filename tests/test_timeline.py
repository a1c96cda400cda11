"""Timeline profiler, kernel attribution, Perfetto export, and the
health doctor (docs/16-observability.md).

Covers the PR's acceptance loop:
  - busy/gap analysis math on hand-built intervals, then on a REAL
    spill-forced build (nonzero read-idle-while-spill fraction);
  - the background memory sampler and per-phase high-water marks;
  - block_until_ready-timed kernel attribution metrics and the
    flight-record ``device_ms`` discriminator;
  - Perfetto/Chrome trace-event export: schema validation, and
    reconstruction from a flight-recorder record and a perf-ledger
    entry;
  - the doctor matrix over BOTH LogStore backends: clean tree → ok,
    seeded quarantine → crit (and ok again after repair), stale
    index → warn;
  - ``perf_history`` index/limit filters (API + verb).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig, col
from hyperspace_tpu.telemetry import metrics, perf_ledger, timeline
from hyperspace_tpu.telemetry.doctor import doctor

BOTH_STORES = ("hyperspace_tpu.io.log_store.PosixLogStore",
               "hyperspace_tpu.io.log_store.EmulatedObjectStore")


@pytest.fixture(autouse=True)
def _timeline_cleanup():
    """The enable flag and the interval ring are process-global (like
    tracing): a test that enables the timeline must not leak it."""
    yield
    timeline.disable_timeline()
    timeline.reset()


def _write_source(path: str, n: int = 40_000, files: int = 4,
                  seed: int = 13) -> None:
    os.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(seed)
    t = pa.table({
        "k": pa.array(rng.integers(0, max(1, n // 8), n), type=pa.int64()),
        "v": rng.random(n),
    })
    step = -(-n // files)
    for i in range(files):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(path, f"part-{i:05d}.parquet"))


def _session(tmp_path, name: str = "ix", **conf) -> HyperspaceSession:
    s = HyperspaceSession(system_path=str(tmp_path / name))
    s.conf.num_buckets = 4
    for k, v in conf.items():
        setattr(s.conf, k, v)
    return s


# ---------------------------------------------------------------------------
# Recorder + gap/overlap math
# ---------------------------------------------------------------------------
class TestRecorder:
    def test_disabled_is_a_noop(self):
        timeline.disable_timeline()
        timeline.reset()
        assert timeline.op_begin() is None
        assert timeline.kernel_begin() is None
        timeline.record_interval("a", "k", 0, 10)
        timeline.kernel_end("x", None, None)   # no sync, no record
        timeline.record_transfer("h2d", 1024)  # no counter
        assert timeline.recorder().intervals() == []
        assert "exec.transfer.h2d.bytes" not in metrics.snapshot()

    def test_enabled_records_and_bounds(self):
        timeline.enable_timeline()
        rec = timeline.recorder()
        rec.set_capacity(8)
        for i in range(20):
            timeline.record_interval("lane", "k", i, i + 1)
        ivs = rec.intervals()
        assert len(ivs) == 8
        assert ivs[0][2] == 12  # oldest 12 dropped
        assert metrics.snapshot().get("timeline.dropped", 0) >= 12
        rec.set_capacity(timeline._DEFAULT_MAX_INTERVALS)

    def test_lane_context_manager(self):
        timeline.enable_timeline()
        timeline.reset()
        with timeline.lane("read", "chunk"):
            pass
        ivs = timeline.recorder().intervals("read")
        assert len(ivs) == 1 and ivs[0][1] == "chunk"

    def test_busy_report_overlap_math(self):
        # A busy [0, 100); B busy [50, 150): window 150.
        report = timeline.busy_report([("A", "x", 0, 100),
                                       ("B", "x", 50, 150)])
        assert report["lanes"]["A"]["busy_fraction"] == pytest.approx(
            100 / 150, abs=1e-3)
        assert report["lanes"]["B"]["busy_fraction"] == pytest.approx(
            100 / 150, abs=1e-3)
        # B runs alone in [100, 150): A idle while B busy = 50/150.
        assert report["idle_while_busy"]["A"]["B"] == pytest.approx(
            50 / 150, abs=1e-3)
        assert report["idle_while_busy"]["B"]["A"] == pytest.approx(
            50 / 150, abs=1e-3)

    def test_busy_report_fully_serialized(self):
        # Strictly sequential lanes: each is idle for ALL of the other's
        # busy time — the shape a serialized build pipeline has.
        report = timeline.busy_report([("read", "x", 0, 100),
                                       ("spill", "x", 100, 200)])
        assert report["idle_while_busy"]["read"]["spill"] \
            == pytest.approx(0.5, abs=1e-3)
        assert report["idle_while_busy"]["spill"]["read"] \
            == pytest.approx(0.5, abs=1e-3)

    def test_busy_report_merges_overlapping_spans(self):
        # Two overlapping intervals on one lane count once.
        report = timeline.busy_report([("A", "x", 0, 60),
                                       ("A", "x", 40, 100)])
        assert report["lanes"]["A"]["busy_fraction"] == pytest.approx(1.0)

    def test_busy_report_empty(self):
        assert timeline.busy_report([]) == {
            "window_s": 0.0, "lanes": {}, "idle_while_busy": {}}


class TestMemorySampler:
    def test_sampler_feeds_sink_and_ring(self):
        timeline.enable_timeline()
        timeline.reset()

        class Sink:
            def __init__(self):
                self.samples = []

            def add_memory_sample(self, ts, rss, dev):
                self.samples.append((ts, rss, dev))

        sink = Sink()
        sampler = timeline.MemorySampler(cadence_ms=2.0, sink=sink)
        sampler.start()
        time.sleep(0.08)
        sampler.stop()
        assert sink.samples, "sampler produced nothing in 80 ms"
        assert timeline.recorder().memory_samples()
        ts, rss, dev = sink.samples[0]
        assert rss > 0  # /proc/self/statm works on this host
        assert dev >= 0

    def test_start_sampler_respects_gate(self, tmp_path):
        s = _session(tmp_path)
        timeline.disable_timeline()
        assert timeline.start_sampler(s.conf) is None
        timeline.enable_timeline()
        s.conf.timeline_memory_sample_ms = 0.0
        assert timeline.start_sampler(s.conf) is None
        s.conf.timeline_memory_sample_ms = 5.0
        sampler = timeline.start_sampler(s.conf)
        assert sampler is not None
        sampler.stop()


# ---------------------------------------------------------------------------
# The spill-forced build: lanes, matrix, per-phase memory
# ---------------------------------------------------------------------------
@pytest.fixture(scope="class")
def spill_build(tmp_path_factory):
    """One spill-forced build with the timeline + a fast sampler on:
    shared by the gap-analysis and export tests (class-scoped — the
    build is the expensive part)."""
    tmp_path = tmp_path_factory.mktemp("spill")
    src = str(tmp_path / "src")
    _write_source(src, n=120_000, files=6)
    session = _session(tmp_path, timeline_enabled=True,
                       timeline_memory_sample_ms=2.0)
    session.conf.device_batch_rows = 8192   # force the external build
    session.conf.parallel_build = "off"
    hs = Hyperspace(session)
    timeline.reset()
    hs.create_index(session.read.parquet(src),
                    IndexConfig("spix", ["k"], ["v"]))
    yield session, hs
    timeline.disable_timeline()
    timeline.reset()


class TestSpillBuildTimeline:
    def test_lanes_matrix_ring_and_live_export(self, spill_build,
                                               tmp_path):
        """First test in the class ON PURPOSE: the per-test cleanup
        wipes the process ring, so the ring/export assertions must run
        in the same test slot the class fixture built in.  Later tests
        read the (per-report) interval copy only."""
        _session_, hs = spill_build
        report = hs.last_build_report()
        assert report.spill_bytes > 0, "build did not spill"
        lanes = report.lane_report()
        for lane_name in ("read", "spill_route", "spill_finish"):
            assert lane_name in lanes["lanes"], sorted(lanes["lanes"])
        matrix = lanes["idle_while_busy"]
        # The acceptance number: reads are DONE before the per-bucket
        # finish pass runs, so the read lane must be measurably idle
        # while spill work is busy — the serialization ROADMAP item 2's
        # prefetch rewrite must reduce.
        read_idle_while_spill = max(matrix["read"]["spill_route"],
                                    matrix["read"]["spill_finish"])
        assert read_idle_while_spill > 0.0, matrix

        # Build-phase intervals reached the process ring...
        kinds = {iv[1] for iv in timeline.recorder().intervals()}
        assert "build.phase" in kinds
        # ...and the live-ring Perfetto export renders them plus the
        # sampler's memory counter track, schema-valid.
        path = str(tmp_path / "trace.json")
        hs.export_timeline(path)
        with open(path, "r", encoding="utf-8") as f:
            events = json.load(f)["traceEvents"]
        _validate_trace_events(events)
        names = {e["name"] for e in events}
        assert "build.phase" in names
        assert "memory" in names
        ring_lanes = {e["args"]["name"] for e in events
                      if e["ph"] == "M"}
        assert "read" in ring_lanes and "spill_route" in ring_lanes

    def test_memory_sampler_ran_and_phase_high_water(self, spill_build):
        _session_, hs = spill_build
        report = hs.last_build_report()
        assert report.memory_samples, "no background memory samples"
        peaks = report.phase_memory_mb()
        assert isinstance(peaks, dict)
        assert peaks, "no sample landed inside any phase interval"
        assert all(v > 0 for v in peaks.values()), peaks

    def test_to_dict_carries_lanes_and_peaks(self, spill_build):
        _session_, hs = spill_build
        d = hs.last_build_report().to_dict()
        assert "lanes" in d and "idle_while_busy" in d["lanes"]
        assert "phase_peak_rss_mb" in d

    def test_disabled_build_records_nothing(self, tmp_path):
        timeline.disable_timeline()
        timeline.reset()
        src = str(tmp_path / "src")
        _write_source(src, n=5_000, files=2)
        session = _session(tmp_path)
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(src),
                        IndexConfig("offix", ["k"], ["v"]))
        report = hs.last_build_report()
        assert report.intervals == []
        assert report.memory_samples == []
        assert "lanes" not in report.to_dict()


# ---------------------------------------------------------------------------
# Kernel attribution
# ---------------------------------------------------------------------------
class TestKernelAttribution:
    def test_device_filter_emits_kernel_metrics(self, tmp_path):
        src = str(tmp_path / "src")
        _write_source(src, n=10_000, files=2)
        session = _session(tmp_path, timeline_enabled=True)
        session.conf.device_filter_min_rows = 1  # force the device path
        metrics.reset()
        ds = session.read.parquet(src).filter(col("k") < 100)
        out = ds.collect()
        assert out.num_rows > 0
        snap = metrics.snapshot()
        hist = snap.get("exec.kernel.filter.device_ms")
        assert isinstance(hist, dict) and hist["count"] >= 1, sorted(snap)
        device_counters = [k for k in snap
                           if k.startswith("exec.device.")
                           and k.endswith(".kernel_ms")]
        assert device_counters, sorted(snap)
        assert snap.get("exec.transfer.d2h.bytes", 0) > 0
        # The kernel decision landed on the run report → device_ms
        # summary nonzero.
        rep = session.last_run_report_value
        kernels = [d for d in rep.decisions if d.get("kind") == "kernel"]
        assert kernels and kernels[0]["name"] == "filter"
        assert timeline.device_ms_summary(rep) > 0
        # ...and on a device:<id> timeline lane.
        lanes = {iv[0] for iv in timeline.recorder().intervals()}
        assert any(ln.startswith("device:") for ln in lanes), lanes

    def test_timeline_off_means_no_kernel_sync_or_metrics(self, tmp_path):
        timeline.disable_timeline()
        src = str(tmp_path / "src")
        _write_source(src, n=10_000, files=2)
        session = _session(tmp_path)
        session.conf.device_filter_min_rows = 1
        metrics.reset()
        session.read.parquet(src).filter(col("k") < 100).collect()
        assert "exec.kernel.filter.device_ms" not in metrics.snapshot()

    def test_flight_record_carries_device_ms(self, tmp_path):
        from hyperspace_tpu.telemetry import flight_recorder

        src = str(tmp_path / "src")
        _write_source(src, n=10_000, files=2)
        session = _session(tmp_path, timeline_enabled=True)
        session.conf.device_filter_min_rows = 1
        session.conf.flight_recorder_slow_ms = 0.001  # retain everything
        flight_recorder.reset()
        session.read.parquet(src).filter(col("k") < 100).collect()
        table = flight_recorder.slow_queries_table(session.conf)
        assert table.num_rows >= 1
        assert "deviceMs" in table.column_names
        assert max(table.column("deviceMs").to_pylist()) > 0
        rec = flight_recorder.recorder().records()[-1]
        assert rec["device_ms"] > 0

    def test_executor_operator_intervals(self, tmp_path):
        src = str(tmp_path / "src")
        _write_source(src, n=5_000, files=2)
        session = _session(tmp_path, timeline_enabled=True)
        timeline.reset()
        session.read.parquet(src).collect()
        kinds = {iv[1] for iv in timeline.recorder().intervals("exec")}
        assert "Scan" in kinds, kinds


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------
def _validate_trace_events(events) -> None:
    """Chrome trace-event schema: every event has ph/pid/ts-or-metadata;
    X events carry name + ts + dur; C events carry numeric args."""
    assert isinstance(events, list) and events
    for ev in events:
        assert isinstance(ev, dict)
        assert ev.get("ph") in ("X", "C", "M"), ev
        assert isinstance(ev.get("pid"), int)
        if ev["ph"] == "M":
            assert ev.get("name") == "thread_name"
            assert isinstance(ev["args"]["name"], str)
            continue
        assert isinstance(ev.get("name"), str) and ev["name"]
        assert isinstance(ev.get("ts"), (int, float))
        if ev["ph"] == "X":
            assert isinstance(ev.get("dur"), (int, float))
            assert ev["dur"] >= 0
        if ev["ph"] == "C":
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values()), ev


class TestPerfettoExport:
    def test_trace_event_builder_schema(self):
        events = timeline.to_trace_events(
            intervals=[("read", "build.phase", 1000, 5000),
                       ("spill_route", "build.phase", 2000, 9000)],
            memory_samples=[(1500, 123.4, 1 << 20)])
        _validate_trace_events(events)
        # One Perfetto thread per lane, named via metadata events.
        named = {e["args"]["name"] for e in events if e["ph"] == "M"}
        assert named == {"read", "spill_route"}
        x = [e for e in events if e["ph"] == "X"]
        assert {e["args"]["lane"] for e in x} == {"read", "spill_route"}
        # ns → µs conversion.
        assert min(e["ts"] for e in x) == pytest.approx(1.0)
        c = [e for e in events if e["ph"] == "C"]
        assert c and c[0]["args"]["host_rss_mb"] == pytest.approx(123.4)

    def test_roundtrip_from_flight_record(self, tmp_path):
        from hyperspace_tpu.telemetry import flight_recorder, trace

        src = str(tmp_path / "src")
        _write_source(src, n=5_000, files=2)
        session = _session(tmp_path, timeline_enabled=True)
        session.conf.flight_recorder_slow_ms = 0.001
        session.conf.telemetry_tracing_enabled = True
        flight_recorder.reset()
        try:
            hs = Hyperspace(session)
            session.read.parquet(src).collect()
        finally:
            trace.disable_tracing()
        rec = flight_recorder.recorder().records()[-1]
        assert rec["spans"], "tracing was on; the record must carry spans"
        path = str(tmp_path / "from_record.json")
        hs.export_timeline(path, trace_id=rec["trace_id"])
        with open(path, "r", encoding="utf-8") as f:
            events = json.load(f)["traceEvents"]
        _validate_trace_events(events)
        names = {e["name"] for e in events}
        assert "query.collect" in names, names

    def test_export_unknown_trace_id_raises(self, tmp_path):
        session = _session(tmp_path)
        hs = Hyperspace(session)
        with pytest.raises(ValueError, match="no retained flight record"):
            hs.export_timeline(str(tmp_path / "x.json"),
                               trace_id="deadbeefdeadbeef")

    def test_reconstruct_from_perf_ledger_entry(self, tmp_path):
        src = str(tmp_path / "src")
        _write_source(src, n=5_000, files=2)
        session = _session(tmp_path)
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(src),
                        IndexConfig("lx", ["k"], ["v"]))
        history = hs.perf_history(index="lx")
        assert history.num_rows >= 1
        key = history.column("key").to_pylist()[-1]
        path = str(tmp_path / "from_ledger.json")
        hs.export_timeline(path, ledger_key=key)
        with open(path, "r", encoding="utf-8") as f:
            events = json.load(f)["traceEvents"]
        _validate_trace_events(events)
        names = {e["name"] for e in events}
        assert any(n.startswith("phase.") for n in names), names

    def test_export_unknown_ledger_key_raises(self, tmp_path):
        session = _session(tmp_path)
        hs = Hyperspace(session)
        with pytest.raises(ValueError, match="no perf-ledger record"):
            hs.export_timeline(str(tmp_path / "x.json"),
                               ledger_key="r-0000000000000-0-00000")


# ---------------------------------------------------------------------------
# The doctor
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("store_cls", BOTH_STORES)
class TestDoctorMatrix:
    def _built(self, tmp_path, store_cls):
        src = str(tmp_path / "src")
        _write_source(src, n=8_000, files=2)
        session = _session(tmp_path, log_store_class=store_cls)
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(src),
                        IndexConfig("dix", ["k"], ["v"]))
        return session, hs, src

    def test_clean_tree_is_ok(self, tmp_path, store_cls):
        session, hs, _src = self._built(tmp_path, store_cls)
        metrics.reset()  # degraded counters are process-global
        report = hs.doctor()
        assert report.status == "ok", report.render()
        assert {c.name for c in report.checks} == {
            "integrity", "staleness", "cdc.merge_debt", "maintenance",
            "perf", "serving", "degraded", "lint", "device_skew",
            "client"}
        assert metrics.snapshot().get("health.status") == 0

    def test_seeded_quarantine_is_crit_and_repair_restores_ok(
            self, tmp_path, store_cls):
        session, hs, _src = self._built(tmp_path, store_cls)
        metrics.reset()
        manager = session.index_collection_manager
        entry = manager.get_index("dix")
        victim = entry.content.file_infos()[0].name
        qm = manager.quarantine_manager("dix")
        assert qm.add(victim, reason="test-seeded")
        report = hs.doctor()
        assert report.status == "crit", report.render()
        check = report.check("integrity")
        assert check.status == "crit"
        assert check.data["quarantined"] == {"dix": 1}
        assert metrics.snapshot().get("health.status") == 2
        # Repair rebuilds the quarantined bucket and clears the record:
        # the doctor must grade the tree ok again.
        hs.refresh_index("dix", mode="repair")
        metrics.reset()
        report = hs.doctor()
        assert report.status == "ok", report.render()
        assert metrics.snapshot().get("health.status") == 0

    def test_stale_index_is_warn(self, tmp_path, store_cls):
        session, hs, src = self._built(tmp_path, store_cls)
        metrics.reset()
        # Append a source file AFTER the build: the index is now behind.
        extra = pa.table({"k": pa.array([1, 2, 3], type=pa.int64()),
                          "v": [0.1, 0.2, 0.3]})
        pq.write_table(extra, os.path.join(src, "part-99999.parquet"))
        report = hs.doctor()
        assert report.status == "warn", report.render()
        check = report.check("staleness")
        assert check.status == "warn"
        assert check.data["stale"]["dix"]["appended"] == 1
        assert metrics.snapshot().get("health.status") == 1


class TestDoctorChecks:
    def test_serving_overload_grades_crit(self, tmp_path):
        session = _session(tmp_path)
        metrics.reset()
        metrics.inc("serve.requests", 100)
        metrics.inc("serve.shed", 50)  # 0.5 >= 5 * 0.05
        report = doctor(session)
        assert report.check("serving").status == "crit"
        assert report.status == "crit"

    def test_serving_slo_burn_grades_warn(self, tmp_path):
        session = _session(tmp_path)
        session.conf.doctor_latency_slo_ms = 100.0
        metrics.reset()
        metrics.inc("serve.requests", 10)
        for _ in range(8):
            metrics.observe("serve.latency_ms", 10.0)
        for _ in range(2):
            metrics.observe("serve.latency_ms", 5000.0)  # 20% over SLO
        report = doctor(session)
        check = report.check("serving")
        assert check.status == "warn", check.to_dict()
        assert check.data["slo_burn"] == pytest.approx(0.2)

    def test_perf_trend_regression_grades_warn(self, tmp_path):
        session = _session(tmp_path)
        metrics.reset()
        for wall in (1.0, 1.1, 0.9, 1.0, 10.0):  # latest 10x the median
            perf_ledger.append(session.conf, {
                "kind": "action", "name": "CreateAction(trendix)",
                "wall_s": wall, "outcome": "ok"})
        report = doctor(session)
        check = report.check("perf")
        assert check.status == "warn", check.to_dict()
        assert "CreateAction(trendix)" in check.data["regressions"]

    def test_degraded_counters_grade_warn(self, tmp_path):
        session = _session(tmp_path)
        metrics.reset()
        metrics.inc("degraded.fallbacks")
        report = doctor(session)
        assert report.check("degraded").status == "warn"
        assert report.status == "warn"

    def test_maintenance_backoff_grades_warn(self, tmp_path):
        from hyperspace_tpu.lifecycle.daemon import daemon_for

        session = _session(tmp_path)
        metrics.reset()
        d = daemon_for(session)
        d._backoff["dix"] = (3, time.monotonic() + 60.0)
        report = doctor(session)
        check = report.check("maintenance")
        assert check.status == "warn"
        assert check.data["backoffs"]["dix"]["failures"] == 3

    def test_blind_check_is_warn_not_crash(self, tmp_path, monkeypatch):
        """A check that raises must degrade to warn, never propagate.
        (``session.index_collection_manager`` is a property minting a
        fresh manager per access, so the CLASS method is patched.)"""
        from hyperspace_tpu.index.cache import (
            CachingIndexCollectionManager,
        )

        session = _session(tmp_path)
        metrics.reset()
        monkeypatch.setattr(CachingIndexCollectionManager, "get_indexes",
                            _boom)
        report = doctor(session)
        assert report.check("integrity").status == "warn"
        assert "check failed" in report.check("integrity").summary
        assert report.status == "warn"

    def test_report_render_and_table(self, tmp_path):
        session = _session(tmp_path)
        metrics.reset()
        report = doctor(session)
        assert report.status in ("ok", "warn", "crit")
        assert "Doctor:" in report.render()
        table = report.table()
        assert table.column("check").to_pylist()[0] == "overall"
        assert len(table.column("check").to_pylist()) \
            == len(report.checks) + 1

    def test_doctor_verb(self, tmp_path):
        from hyperspace_tpu.interop.server import _serve_verb

        session = _session(tmp_path)
        metrics.reset()
        table = _serve_verb(session, {"verb": "doctor"})
        checks = table.column("check").to_pylist()
        assert "overall" in checks and "integrity" in checks
        statuses = set(table.column("status").to_pylist())
        assert statuses <= {"ok", "warn", "crit"}


def _boom(*_a, **_k):
    raise RuntimeError("listing exploded")


# ---------------------------------------------------------------------------
# perf_history ergonomics
# ---------------------------------------------------------------------------
class TestPerfHistoryFilters:
    @pytest.fixture()
    def seeded(self, tmp_path):
        src = str(tmp_path / "src")
        _write_source(src, n=6_000, files=2)
        session = _session(tmp_path)
        hs = Hyperspace(session)
        ds = session.read.parquet(src)
        hs.create_index(ds, IndexConfig("aa", ["k"], ["v"]))
        hs.create_index(ds, IndexConfig("bb", ["k"], ["v"]))
        perf_ledger.append(session.conf, {
            "kind": "action", "name": "RefreshAction(bb)", "outcome": "ok",
            "wall_s": 1.0})
        return session, hs

    def test_index_filter(self, seeded):
        _session_, hs = seeded
        table = hs.perf_history(index="aa")
        names = table.column("name").to_pylist()
        assert names and all(n.endswith("(aa)") for n in names)
        assert hs.perf_history(index="nope").num_rows == 0

    def test_limit_keeps_most_recent(self, seeded):
        _session_, hs = seeded
        full = hs.perf_history()
        assert full.num_rows >= 3
        table = hs.perf_history(limit=2)
        assert table.num_rows == 2
        assert table.column("key").to_pylist() \
            == full.column("key").to_pylist()[-2:]

    def test_verb_mirrors_filters(self, seeded):
        from hyperspace_tpu.interop.server import _serve_verb

        session, hs = seeded
        table = _serve_verb(session, {"verb": "perf_history",
                                      "index": "bb"})
        assert table.column("name").to_pylist()[-1] == "RefreshAction(bb)"
        assert table.num_rows == hs.perf_history(index="bb").num_rows
        table = _serve_verb(session, {"verb": "perf_history", "limit": 1})
        assert table.num_rows == 1
        with pytest.raises(ValueError, match='"limit"'):
            _serve_verb(session, {"verb": "perf_history", "limit": -1})
        with pytest.raises(ValueError, match='"index"'):
            _serve_verb(session, {"verb": "perf_history", "index": 3})
