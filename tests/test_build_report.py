"""Build-pipeline profiler and perf ledger tests
(docs/16-observability.md "Build reports & perf ledger").

Covers the PR's acceptance loop:
  - a toy build's BuildReport phase seconds sum to ~the action wall time
    and its spill-bytes figure matches the bytes actually written;
  - the report survives a conflict-retried action;
  - ledger round-trip + bounds over BOTH LogStore backends.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession, IndexConfig
from hyperspace_tpu.telemetry import perf_ledger

BOTH_STORES = ("hyperspace_tpu.io.log_store.PosixLogStore",
               "hyperspace_tpu.io.log_store.EmulatedObjectStore")


def _write_source(path: str, n: int = 40_000, files: int = 4) -> None:
    os.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(11)
    t = pa.table({
        "k": pa.array(rng.integers(0, max(1, n // 8), n), type=pa.int64()),
        "v": rng.random(n),
    })
    step = -(-n // files)
    for i in range(files):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(path, f"part-{i:05d}.parquet"))


@pytest.fixture()
def built(tmp_path):
    src = str(tmp_path / "src")
    # One-batch scale (<= the conftest's 4096-row device batch): these
    # tests assert the MONOLITHIC build's phase taxonomy (kernel/write,
    # no spill).  Multi-batch datasets now stream through the spill
    # builder even under parallel_build=auto — the mesh shards the
    # per-chunk route — which is test_parallel_mesh.py's territory.
    _write_source(src, n=4_000)
    session = HyperspaceSession(system_path=str(tmp_path / "ix"))
    session.conf.num_buckets = 4
    hs = Hyperspace(session)
    hs.create_index(session.read.parquet(src), IndexConfig("bi", ["k"], ["v"]))
    return session, hs, src


# ---------------------------------------------------------------------------
# BuildReport
# ---------------------------------------------------------------------------
class TestBuildReport:
    def test_phases_sum_close_to_wall(self, built):
        _session, hs, _src = built
        report = hs.last_build_report()
        assert report is not None and report.action == "CreateAction"
        assert report.index == "bi" and report.outcome == "ok"
        # The protocol phases (validate/commit) plus the build phases
        # account for nearly the whole run — within 10% at a real size;
        # the test band is slightly looser because a toy build's fixed
        # dispatch overhead is a larger fraction.
        coverage = report.phase_total_s() / max(report.wall_s, 1e-9)
        assert 0.80 <= coverage <= 1.20, report.to_dict()
        for phase in ("read", "kernel", "write", "sketch", "validate",
                      "commit"):
            assert phase in report.phases, report.phases
        # kernel is the device-attributed side; everything else is host.
        assert report.device_s == pytest.approx(report.phases["kernel"])
        assert report.host_s == pytest.approx(
            report.phase_total_s() - report.phases["kernel"])

    def test_bytes_written_matches_disk(self, built):
        session, hs, _src = built
        report = hs.last_build_report()
        entry = session.index_collection_manager.get_index("bi")
        on_disk = sum(f.size for f in entry.content.file_infos())
        assert report.bytes_written == on_disk
        assert report.files_written == len(entry.content.file_infos())
        assert report.bytes_read > 0
        assert report.spill_bytes == 0  # one-batch build never spills

    def test_spill_bytes_match_bytes_actually_written(self, tmp_path,
                                                      monkeypatch):
        from hyperspace_tpu.actions import create as create_mod

        src = str(tmp_path / "src")
        _write_source(src)
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session.conf.num_buckets = 4
        session.conf.device_batch_rows = 4096  # force the external build
        # The suite's virtual 8-device mesh would take the distributed
        # build (which never spills); pin the single-chip streaming path.
        session.conf.parallel_build = "off"
        seen: list = []
        real = create_mod._write_chunk_file

        def teeing_write_chunk(table, path, slices):
            n = real(table, path, slices)
            seen.append((n, len(slices)))
            return n

        monkeypatch.setattr(create_mod, "_write_chunk_file",
                            teeing_write_chunk)
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(src),
                        IndexConfig("si", ["k"], ["v"]))
        report = hs.last_build_report()
        assert seen, "the small batch size should have forced a spill"
        assert report.spill_bytes == sum(n for n, _ in seen)
        assert report.spill_runs == sum(r for _, r in seen)
        assert report.phases.get("spill_route", 0) > 0
        assert report.phases.get("spill_finish", 0) > 0

    def test_report_survives_conflict_retry(self, built):
        from hyperspace_tpu.actions.refresh import RefreshAction
        from hyperspace_tpu.exceptions import ConcurrentWriteError
        from hyperspace_tpu.utils.retry import RetryPolicy

        session, _hs, src = built
        # Touch the source so refresh has work, then make the FIRST log
        # write of the attempt collide — the optimistic loop must rebase
        # and the report must survive with the conflict recorded.
        extra = os.path.join(src, "part-99999.parquet")
        pq.write_table(pa.table({"k": pa.array([1, 2], type=pa.int64()),
                                 "v": [0.5, 0.25]}), extra)
        mgr = session.index_collection_manager
        log_manager = mgr._log_manager("bi")
        action = RefreshAction(log_manager, mgr._data_manager("bi"),
                               session,
                               previous=log_manager.get_latest_stable_log())
        action.concurrency_max_retries = 2
        action.conflict_backoff = RetryPolicy(max_attempts=2,
                                              initial_backoff_ms=1.0,
                                              max_backoff_ms=2.0)
        real_write = log_manager.write_log_or_raise
        fails = {"n": 1}

        def flaky_write(log_id, entry):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise ConcurrentWriteError("injected conflict")
            return real_write(log_id, entry)

        log_manager.write_log_or_raise = flaky_write
        action.run()
        report = action.build_report
        assert report.outcome == "ok"
        assert report.conflict_retries == 1
        assert report.phases.get("read", 0) > 0  # the rebuild still ran
        # The session-published copy is the same object.
        assert session.last_build_report_value is report

    def test_failed_action_still_reports(self, tmp_path):
        from hyperspace_tpu.exceptions import HyperspaceError

        src = str(tmp_path / "src")
        _write_source(src, n=100, files=1)
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        hs = Hyperspace(session)
        with pytest.raises(HyperspaceError):
            hs.create_index(session.read.parquet(src),
                            IndexConfig("bad", ["nope"], []))
        report = session.last_build_report_value
        assert report is not None
        assert report.outcome == "error"
        assert "nope" in report.error

    def test_optimize_reports_phases_and_bytes(self, tmp_path):
        src = str(tmp_path / "src")
        _write_source(src)
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session.conf.num_buckets = 2
        session.conf.index_max_rows_per_file = 2_000  # many small files
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(src),
                        IndexConfig("oi", ["k"], ["v"]))
        # Lift the knob so the compaction has something to merge (each
        # bucket's file count is already minimal under the build's knob).
        session.conf.index_max_rows_per_file = 0
        hs.optimize_index("oi", mode="full")
        report = hs.last_build_report()
        assert report.action == "OptimizeAction" and report.index == "oi"
        assert report.outcome == "ok"
        for phase in ("read", "sort", "write", "sketch"):
            assert report.phases.get(phase, 0) > 0, report.phases
        assert report.bytes_written > 0 and report.bytes_read > 0

    def test_disabled_profiling_skips_sampling_and_ledger(self, tmp_path):
        from hyperspace_tpu.telemetry import metrics

        src = str(tmp_path / "src")
        _write_source(src, n=2_000, files=2)
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session.conf.build_profiling_enabled = False
        hs = Hyperspace(session)
        before = metrics.registry().counter("build.actions")
        hs.create_index(session.read.parquet(src),
                        IndexConfig("di", ["k"], ["v"]))
        report = hs.last_build_report()
        # The report itself still exists (phase timing predates the
        # profiler and stays on) but sampling/export/ledger are skipped.
        assert report is not None and report.peak_rss_mb is None
        assert metrics.registry().counter("build.actions") == before
        assert hs.perf_history().num_rows == 0

    def test_metrics_and_span_export(self, tmp_path):
        from hyperspace_tpu.telemetry import metrics, trace

        src = str(tmp_path / "src")
        _write_source(src, n=2_000, files=2)
        sink = trace.add_sink(trace.CollectingTraceSink())
        trace.enable_tracing()
        try:
            session = HyperspaceSession(system_path=str(tmp_path / "ix"))
            before = metrics.registry().counter("build.actions")
            hs = Hyperspace(session)
            hs.create_index(session.read.parquet(src),
                            IndexConfig("mi", ["k"], ["v"]))
        finally:
            trace.disable_tracing()
            trace.remove_sink(sink)
        assert metrics.registry().counter("build.actions") == before + 1
        assert metrics.registry().counter(
            "build.phase.read.seconds") > 0
        # The phases are spans of their own, opened where they ran: the
        # action's thread nests them under its span, worker threads
        # deliver them as roots (tests/test_measured_spans.py holds the
        # benchmark's paths to emitting them).
        assert sink.find("action.CreateAction")
        names = {s.name for root in sink.spans for s in root.walk()}
        for phase in ("validate", "read", "kernel", "write", "commit"):
            assert f"build.phase.{phase}" in names, names

    def test_phase_spans_are_timed_where_they_run(self, tmp_path):
        """A spilled create plus a full refresh: every build.phase span
        lies inside its action's interval, on the thread that did the
        work (worker phases arrive as roots from their pool's threads),
        the spans start at different moments, and each phase's spans
        add up to its BuildReport seconds."""
        import threading

        from hyperspace_tpu.telemetry import trace

        class ThreadSink(trace.CollectingTraceSink):
            def __init__(self):
                super().__init__()
                self.thread_of = {}

            def emit(self, root):
                self.thread_of[id(root)] = threading.current_thread().name
                super().emit(root)

        src = str(tmp_path / "src")
        _write_source(src, n=20_000, files=8)
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session.conf.num_buckets = 4
        session.conf.device_batch_rows = 4096  # force the spill build
        session.conf.parallel_build = "off"
        hs = Hyperspace(session)
        sink = trace.add_sink(ThreadSink())
        trace.enable_tracing()
        try:
            hs.create_index(session.read.parquet(src),
                            IndexConfig("ti", ["k"], ["v"]))
            reports = [hs.last_build_report()]
            pq.write_table(pa.table({"k": pa.array([3], type=pa.int64()),
                                     "v": [0.5]}),
                           os.path.join(src, "part-99999.parquet"))
            hs.refresh_index("ti", "full")
            reports.append(hs.last_build_report())
        finally:
            trace.disable_tracing()
            trace.remove_sink(sink)
        actions = [r for r in sink.spans if r.name.startswith("action.")]
        assert [a.name for a in actions] == ["action.CreateAction",
                                             "action.RefreshAction"]
        worker_thread = {"read": "hs-prefetch", "spill_route": "hs-route",
                         "spill_finish": "hs-finalize"}
        slack = 0.002  # span starts are wall clock, durations monotonic

        def end(s):
            return s.start_s + s.duration_ms / 1000.0

        for action, report in zip(actions, reports):
            inside = [s for s in action.walk()
                      if s.name.startswith("build.phase.")]
            for root in sink.spans:
                if root.name.startswith("build.phase.") and \
                        action.start_s - slack <= root.start_s and \
                        end(root) <= end(action) + slack:
                    phase = root.name[len("build.phase."):]
                    assert sink.thread_of[id(root)].startswith(
                        worker_thread[phase]), (phase, sink.thread_of)
                    inside.append(root)
            for s in inside:
                assert action.start_s - slack <= s.start_s
                assert end(s) <= end(action) + slack
            assert any(s.start_s - action.start_s > 1e-3 for s in inside)
            summed = {}
            for s in inside:
                name = s.name[len("build.phase."):]
                summed[name] = summed.get(name, 0.0) + s.duration_ms / 1e3
            assert set(summed) == set(report.phases), report.phases
            for name, seconds in report.phases.items():
                assert summed[name] == pytest.approx(seconds, rel=0.01), name


# ---------------------------------------------------------------------------
# Perf ledger
# ---------------------------------------------------------------------------
class TestPerfLedger:
    @pytest.mark.parametrize("store_cls", BOTH_STORES)
    def test_round_trip_and_restart(self, tmp_path, store_cls):
        src = str(tmp_path / "src")
        _write_source(src, n=2_000, files=2)
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session.conf.log_store_class = store_cls
        hs = Hyperspace(session)
        hs.create_index(session.read.parquet(src),
                        IndexConfig("li", ["k"], ["v"]))
        hs.optimize_index("li", mode="full")
        table = hs.perf_history()
        assert table.num_rows >= 1
        kinds = set(table.column("kind").to_pylist())
        assert kinds == {"action"}
        names = table.column("name").to_pylist()
        assert any("CreateAction" in n for n in names)
        rec = json.loads(table.column("recordJson").to_pylist()[0])
        assert rec["fingerprint"]["num_buckets"] == 200
        assert "phases_s" in rec and rec["wall_s"] > 0
        # Restart: a NEW session over the same system path reads the
        # same ledger (the records persisted through the store seam).
        session2 = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session2.conf.log_store_class = store_cls
        assert Hyperspace(session2).perf_history().num_rows \
            == table.num_rows

    @pytest.mark.parametrize("store_cls", BOTH_STORES)
    def test_bounded_keeps_newest(self, tmp_path, store_cls):
        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        session.conf.log_store_class = store_cls
        session.conf.perf_ledger_max_entries = 3
        for i in range(6):
            perf_ledger.append(session.conf,
                               {"kind": "action", "name": f"s{i}",
                                "wall_s": float(i)})
        recs = perf_ledger.records(session.conf)
        assert len(recs) == 3
        assert [r["name"] for r in recs] == ["s3", "s4", "s5"]

    def test_append_never_consumes_fault_budget(self, tmp_path):
        """A ledger append through the store seam must not shift an armed
        fault plan's call counter (faults.quiet)."""
        from hyperspace_tpu.io import faults

        session = HyperspaceSession(system_path=str(tmp_path / "ix"))
        plan = faults.FaultPlan(site="store.put", kind="eio", at=1,
                                count=1)
        faults.install(plan)
        try:
            assert perf_ledger.append(session.conf,
                                      {"kind": "action", "name": "x",
                                       "wall_s": 0.0}) is not None
            assert plan._calls == 0  # the armed site never saw the put
        finally:
            faults.clear()

    def test_index_listing_ignores_ledger_dir(self, built):
        session, hs, _src = built
        assert os.path.isdir(os.path.join(
            session.conf.system_path, perf_ledger.PERF_DIR))
        assert hs.indexes().column("name").to_pylist() == ["bi"]


# ---------------------------------------------------------------------------
# Interop surface
# ---------------------------------------------------------------------------
class TestInteropSurface:
    def test_perf_history_and_build_report_verbs(self, built):
        from hyperspace_tpu.interop.server import QueryServer, request_query

        session, _hs, _src = built
        with QueryServer(session) as server:
            hist = request_query(server.address, {"verb": "perf_history"})
            assert hist.num_rows >= 1
            assert "CreateAction" in hist.column("name").to_pylist()[0]
            rep = request_query(server.address, {"verb": "build_report"})
            payload = json.loads(rep.column("report_json").to_pylist()[0])
            assert payload["action"] == "CreateAction"
            assert payload["phases_s"]

    def test_metrics_scrape_server(self, built):
        import urllib.request

        from hyperspace_tpu.interop.server import MetricsScrapeServer

        with MetricsScrapeServer() as ms:
            host, port = ms.address
            with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=10) as resp:
                body = resp.read().decode("utf-8")
                ctype = resp.headers["Content-Type"]
        assert "text/plain" in ctype
        assert "hyperspace_build_actions" in body
        assert "hyperspace_build_phase_read_seconds" in body

    def test_scrape_server_refuses_non_loopback_without_optin(self):
        from hyperspace_tpu.interop.server import MetricsScrapeServer

        with pytest.raises(ValueError):
            MetricsScrapeServer(host="0.0.0.0")
