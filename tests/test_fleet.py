"""Fleet telemetry federation (telemetry/fleet.py; docs/16).

Covers the acceptance loop of the fleet observability plane with REAL
subprocesses over one index tree and both LogStore backends: heartbeat
publish/CAS-refresh/prune, merge semantics (counters by sum, gauges
per-process, histograms by bucket-sum with exemplar carry), federated
slow-query/trace resolution (live snapshots + persisted bundles), the
cluster doctor (stale heartbeat crit within two publish intervals,
duplicate-daemon warn, aggregate overload, kernel-ms skew), the
single-process device-skew doctor check, the inline ``fleet_status``
verb, the fleet scrape mode, and the fault matrix proving the publisher
never consumes an armed fault budget or breaks a query.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession
from hyperspace_tpu.telemetry import fleet, flight_recorder, metrics

POSIX = "hyperspace_tpu.io.log_store.PosixLogStore"
EMULATED = "hyperspace_tpu.io.log_store.EmulatedObjectStore"
BACKENDS = [POSIX, EMULATED]

# Child process: mint a trace id, retain one interesting flight record,
# bump a test counter, publish — then either exit ("once") or keep the
# publisher heartbeating until killed ("hold").
_CHILD = r"""
import json, os, sys, time
from hyperspace_tpu import HyperspaceSession
from hyperspace_tpu.interop.query import mint_trace_id
from hyperspace_tpu.telemetry import fleet, flight_recorder, metrics

system_path, store_class, mode, counter, interval = sys.argv[1:6]
s = HyperspaceSession(system_path=system_path)
s.conf.set("hyperspace.index.logStoreClass", store_class)
s.conf.set("hyperspace.fleet.telemetry.enabled", True)
s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", float(interval))
tid = mint_trace_id()
metrics.inc("fleet.test.queries", float(counter))
flight_recorder.record(
    s.conf, kind="spec", outcome="FAILED", latency_ms=12.5,
    trace_id=tid, request_id=mint_trace_id(), error="seeded in child")
if mode == "hold":
    fleet.publisher_for(s).start()
else:
    assert fleet.publish_once(s.conf)
print(json.dumps({"process": fleet.process_identity(), "trace": tid,
                  "pid": os.getpid()}), flush=True)
if mode == "hold":
    time.sleep(600)
"""


def _spawn(system_path, store_class, mode, counter, interval):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(system_path), store_class,
         mode, str(counter), str(interval)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env)


def _read_children(procs):
    out = []
    for p in procs:
        line = p.stdout.readline()
        assert line, p.stderr.read()
        out.append(json.loads(line))
    return out


def _session(tmp_path, store_class=EMULATED, interval=30.0):
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.set("hyperspace.index.logStoreClass", store_class)
    s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", interval)
    return s


def _put_snapshot(conf, snap):
    """Plant a foreign snapshot directly (a process we don't spawn)."""
    from hyperspace_tpu.io.log_store import store_for

    store = store_for(conf, fleet.fleet_root(conf))
    key = "hb-" + snap["process"]
    payload = json.dumps(snap, default=str).encode("utf-8")
    assert store.put_if_generation_match(key, payload,
                                         store.generation(key))


def _foreign(process, ts=None, role="client", counters=None,
             gauges=None, histograms=None, records=None,
             device_kernel_ms=None):
    return {
        "v": 1, "ts": time.time() if ts is None else ts,
        "process": process, "host": "h", "pid": 1, "role": role,
        "health": None,
        "metrics": {"counters": counters or {}, "gauges": gauges or {},
                    "histograms": histograms or {}},
        "device_kernel_ms": device_kernel_ms or {},
        "records": records or [],
    }


# ---------------------------------------------------------------------------
# Merge semantics (pure)
# ---------------------------------------------------------------------------
class TestMergeSemantics:
    def test_counters_sum_and_gauges_per_process(self):
        merged = fleet.merge_metrics([
            _foreign("a", counters={"x": 2.0, "y": 1.0},
                     gauges={"g": 5.0}),
            _foreign("b", counters={"x": 3.0}, gauges={"g": 7.0}),
        ])
        assert merged["counters"]["x"] == 5.0
        assert merged["counters"]["y"] == 1.0
        assert merged["gauges"]["g"] == {"a": 5.0, "b": 7.0}
        assert merged["processes"] == ["a", "b"]

    def test_histograms_bucket_sum_with_exemplar_carry(self):
        h1 = {"count": 2, "sum": 30.0, "min": 10.0, "max": 20.0,
              "buckets": {"10.0": 1, "25.0": 1},
              "exemplars": {"3": ["aaaa000011112222", 10.0]}}
        h2 = {"count": 1, "sum": 5.0, "min": 5.0, "max": 5.0,
              "buckets": {"5.0": 1},
              "exemplars": {"2": ["bbbb000011112222", 5.0]}}
        merged = fleet.merge_metrics([
            _foreign("a", histograms={"lat": h1}),
            _foreign("b", histograms={"lat": h2}),
        ])["histograms"]["lat"]
        assert merged["count"] == 3
        assert merged["sum"] == 35.0
        assert merged["min"] == 5.0 and merged["max"] == 20.0
        assert merged["mean"] == pytest.approx(35.0 / 3)
        assert merged["buckets"] == {"10.0": 1, "25.0": 1, "5.0": 1}
        assert merged["exemplars"]["3"] == ["aaaa000011112222", 10.0]
        assert merged["exemplars"]["2"] == ["bbbb000011112222", 5.0]

    def test_typed_snapshot_round_trips_through_json(self):
        metrics.reset()
        metrics.inc("c", 2.0)
        metrics.set_gauge("g", 1.5)
        metrics.observe("h", 3.0, exemplar="cccc000011112222")
        typed = json.loads(json.dumps(
            metrics.registry().typed_snapshot()))
        merged = fleet.merge_metrics([
            {"process": "p", "metrics": typed}])
        assert merged["counters"]["c"] == 2.0
        assert merged["gauges"]["g"] == {"p": 1.5}
        assert merged["histograms"]["h"]["count"] == 1
        assert any(ex[0] == "cccc000011112222"
                   for ex in merged["histograms"]["h"]
                   ["exemplars"].values())

    def test_skew_ratio(self):
        assert fleet.skew_ratio([100.0]) == 0.0
        assert fleet.skew_ratio([1.0, 2.0]) == 0.0  # under the floor
        assert fleet.skew_ratio([100.0, 100.0, 800.0]) == 8.0


# ---------------------------------------------------------------------------
# Snapshot + publisher (in-process)
# ---------------------------------------------------------------------------
class TestPublisher:
    def test_snapshot_shape_and_interesting_records(self, tmp_path):
        s = _session(tmp_path)
        metrics.reset()
        metrics.inc("exec.device.0.kernel_ms", 12.0)
        flight_recorder.reset()
        s.conf.set("hyperspace.serving.flightRecorder.healthySampleN", 1)
        flight_recorder.record(
            s.conf, kind="local", outcome="ok", latency_ms=1.0,
            trace_id="a" * 16, request_id="a" * 16)  # healthy sample
        flight_recorder.record(
            s.conf, kind="spec", outcome="FAILED", latency_ms=1.0,
            trace_id="b" * 16, request_id="b" * 16, error="x")
        snap = fleet.build_snapshot(s.conf)
        assert snap["process"] == fleet.process_identity()
        assert snap["role"] in ("client", "daemon", "server")
        assert snap["device_kernel_ms"] == {"0": 12.0}
        # Only the INTERESTING record rides the snapshot.
        assert [r["trace_id"] for r in snap["records"]] == ["b" * 16]
        flight_recorder.reset()

    def test_publish_disabled_is_noop(self, tmp_path):
        s = _session(tmp_path)
        assert fleet.publish_once(s.conf) is False
        assert fleet.live_snapshots(s.conf) == []

    @pytest.mark.parametrize("store_class", BACKENDS)
    def test_publish_refresh_and_status(self, tmp_path, store_class):
        s = _session(tmp_path, store_class)
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        assert fleet.publish_once(s.conf)
        first = fleet.live_snapshots(s.conf)
        assert len(first) == 1
        ts1 = first[0]["ts"]
        time.sleep(0.02)
        assert fleet.publish_once(s.conf)  # CAS refresh, same key
        snaps = fleet.live_snapshots(s.conf)
        assert len(snaps) == 1
        assert snaps[0]["ts"] > ts1
        table = fleet.fleet_status_table(s.conf)
        assert table.num_rows == 1
        assert table.column("process")[0].as_py() == \
            fleet.process_identity()
        assert table.column("fresh")[0].as_py() is True

    def test_stale_flag_and_prune(self, tmp_path):
        s = _session(tmp_path, interval=30.0)
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        _put_snapshot(s.conf, _foreign("dead-1-1", ts=time.time() - 120))
        _put_snapshot(s.conf, _foreign("old-2-2", ts=time.time() - 9000))
        table = fleet.fleet_status_table(s.conf)
        fresh = dict(zip(table.column("process").to_pylist(),
                         table.column("fresh").to_pylist()))
        assert fresh == {"dead-1-1": False, "old-2-2": False}
        # A publish prunes entries past pruneAfterS (default 600) but
        # keeps the merely-stale one for the doctor to report.
        assert fleet.publish_once(s.conf)
        procs = set(fleet.fleet_status_table(s.conf)
                    .column("process").to_pylist())
        assert "old-2-2" not in procs
        assert "dead-1-1" in procs
        assert fleet.process_identity() in procs
        assert metrics.registry().counter("fleet.pruned") >= 1

    def test_publish_never_consumes_fault_budget(self, tmp_path):
        """An armed store.put fault aimed at the engine is NOT consumed
        by fleet telemetry, and publishing still succeeds."""
        from hyperspace_tpu.io import faults

        s = _session(tmp_path)
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        plan = faults.FaultPlan(site="store.put", kind="eio", at=1,
                                count=1)
        faults.install(plan)
        try:
            assert fleet.publish_once(s.conf)
            assert plan._calls == 0
        finally:
            faults.clear()

    def test_publish_failure_never_breaks_a_query(self, tmp_path):
        """A broken fleet store costs a counter, never a query: point
        the systemPath at an unwritable root, publish (False, no
        raise), and run a real collect."""
        data = tmp_path / "d"
        data.mkdir()
        pq.write_table(pa.table({"a": [1, 2, 3]}),
                       data / "f.parquet")
        s = HyperspaceSession(system_path="/proc/hs-no-such-root/ix")
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        errors0 = metrics.registry().counter("fleet.publish.errors")
        assert fleet.publish_once(s.conf) is False
        assert metrics.registry().counter("fleet.publish.errors") \
            == errors0 + 1
        s2 = _session(tmp_path)
        s2.conf.set("hyperspace.fleet.telemetry.enabled", True)
        ds = s2.read.parquet(str(data))
        assert ds.collect().num_rows == 3

    def test_publisher_thread_start_requires_conf(self, tmp_path):
        from hyperspace_tpu.exceptions import HyperspaceError

        s = _session(tmp_path)
        with pytest.raises(HyperspaceError):
            fleet.publisher_for(s).start()
        assert fleet.maybe_start(s) is None
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 0.05)
        pub = fleet.maybe_start(s)
        try:
            assert pub is not None and pub.running()
            deadline = time.monotonic() + 10
            while not fleet.live_snapshots(s.conf) \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert len(fleet.live_snapshots(s.conf)) == 1
        finally:
            pub.stop()
        assert not pub.running()

    def test_role_escalates_never_lowers(self, monkeypatch):
        monkeypatch.setattr(fleet, "_role", "client")
        fleet.set_process_role("daemon")
        assert fleet.process_role() == "daemon"
        fleet.set_process_role("server")
        assert fleet.process_role() == "server"
        fleet.set_process_role("client")
        assert fleet.process_role() == "server"


# ---------------------------------------------------------------------------
# Doctor: single-process device skew + the fleet checks
# ---------------------------------------------------------------------------
class TestDoctor:
    def test_device_skew_check(self, tmp_path):
        s = _session(tmp_path)
        hs = Hyperspace(s)
        metrics.reset()
        metrics.inc("exec.device.0.kernel_ms", 100.0)
        metrics.inc("exec.device.1.kernel_ms", 100.0)
        metrics.inc("exec.device.2.kernel_ms", 100.0)
        check = hs.doctor().check("device_skew")
        assert check.status == "ok"
        metrics.inc("exec.device.2.kernel_ms", 900.0)  # 10x skew
        check = hs.doctor().check("device_skew")
        assert check.status == "warn"
        assert check.data["ratio"] >= 4.0
        # Conf 0 disables the grading.
        s.conf.set("hyperspace.doctor.deviceSkewWarn", 0.0)
        assert hs.doctor().check("device_skew").status == "ok"
        metrics.reset()

    def test_fleet_checks_absent_without_flag(self, tmp_path):
        hs = Hyperspace(_session(tmp_path))
        report = hs.doctor()
        assert report.check("fleet.heartbeats") is None

    def test_heartbeat_crit_and_daemon_warn(self, tmp_path):
        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        report = hs.doctor(fleet=True)
        assert report.check("fleet.heartbeats").status == "ok"
        _put_snapshot(s.conf, _foreign("p1-1-1", role="daemon"))
        _put_snapshot(s.conf, _foreign("p2-2-2", role="daemon"))
        _put_snapshot(s.conf, _foreign("p3-3-3",
                                       ts=time.time() - 300))
        report = hs.doctor(fleet=True)
        hb = report.check("fleet.heartbeats")
        assert hb.status == "crit"
        assert "p3-3-3" in hb.data["stale"]
        assert report.check("fleet.daemons").status == "warn"
        assert report.status == "crit"
        snap = metrics.snapshot()
        assert snap.get("health.fleet.status") == 2.0

    def test_fleet_serving_aggregate_and_skew(self, tmp_path):
        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        _put_snapshot(s.conf, _foreign(
            "srv1-1-1", counters={"serve.requests": 100.0,
                                  "serve.shed": 60.0}))
        _put_snapshot(s.conf, _foreign(
            "srv2-2-2", counters={"serve.requests": 100.0},
            device_kernel_ms={"0": 100.0}))
        _put_snapshot(s.conf, _foreign(
            "srv3-3-3", device_kernel_ms={"0": 100.0}))
        _put_snapshot(s.conf, _foreign(
            "srv4-4-4", device_kernel_ms={"0": 2000.0}))
        report = hs.doctor(fleet=True)
        serving = report.check("fleet.serving")
        # 60 sheds over 200 aggregate requests = 0.3 ratio: crit past
        # 5 x the default 0.05 warn threshold.
        assert serving.status == "crit"
        assert serving.data["requests"] == 200
        skew = report.check("fleet.skew")
        assert skew.status == "warn"
        assert skew.data["process_ratio"] >= 4.0


# ---------------------------------------------------------------------------
# Federated slow queries / trace (in-process: snapshots + bundles)
# ---------------------------------------------------------------------------
class TestFederatedRecords:
    def test_union_and_precedence(self, tmp_path):
        s = _session(tmp_path)
        flight_recorder.reset()
        flight_recorder.clear_bundles(s.conf)
        flight_recorder.record(
            s.conf, kind="spec", outcome="FAILED", latency_ms=1.0,
            trace_id="1" * 16, request_id="1" * 16, error="local")
        _put_snapshot(s.conf, _foreign(
            "live-9-9", records=[{
                "ts": time.time(), "trace_id": "2" * 16,
                "request_id": "2" * 16, "kind": "sql",
                "outcome": "DEADLINE", "latency_ms": 7.0,
                "slow": True, "reason": "error", "error": "remote"}]))
        # A drained process's record survives only in its bundle.
        flight_recorder.record(
            s.conf, kind="spec", outcome="FAILED", latency_ms=1.0,
            trace_id="3" * 16, request_id="3" * 16, error="bundled")
        assert flight_recorder.dump_diagnostics(s.conf)
        table = fleet.fleet_slow_queries_table(s.conf)
        by_trace = dict(zip(table.column("traceId").to_pylist(),
                            table.column("process").to_pylist()))
        assert by_trace["1" * 16] == fleet.process_identity()
        assert by_trace["2" * 16] == "live-9-9"
        rec = fleet.find_trace(s.conf, "2" * 16)
        assert rec["process"] == "live-9-9"
        assert rec["outcome"] == "DEADLINE"
        # Local ring wins for a locally retained id.
        assert fleet.find_trace(s.conf, "1" * 16)["process"] == \
            fleet.process_identity()
        # After the ring is gone (restart), the bundle still answers.
        flight_recorder.reset()
        rec = fleet.find_trace(s.conf, "3" * 16)
        assert rec is not None
        assert rec["process"].startswith("bundle-")
        assert fleet.find_trace(s.conf, "f" * 16) is None
        flight_recorder.clear_bundles(s.conf)

    def test_hyperspace_api_flags(self, tmp_path):
        s = _session(tmp_path)
        hs = Hyperspace(s)
        flight_recorder.reset()
        local = hs.slow_queries()
        assert "process" not in local.column_names
        fed = hs.slow_queries(fleet=True)
        assert "process" in fed.column_names
        assert hs.trace("e" * 16, fleet=True) is None


# ---------------------------------------------------------------------------
# Interop: the inline verb + the fleet scrape mode
# ---------------------------------------------------------------------------
class TestInterop:
    def test_fleet_status_verb_and_doctor_fleet(self, tmp_path):
        from hyperspace_tpu.interop.server import QueryClient, QueryServer

        s = _session(tmp_path)
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        assert fleet.publish_once(s.conf)
        with QueryServer(s) as server:
            with QueryClient(server.address) as qc:
                table = qc.query({"verb": "fleet_status"})
                assert fleet.process_identity() in \
                    table.column("process").to_pylist()
            with QueryClient(server.address) as qc:
                table = qc.query({"verb": "doctor", "fleet": True})
                assert "fleet.heartbeats" in \
                    table.column("check").to_pylist()

    def test_drain_deregisters_heartbeat(self, tmp_path):
        """A drained server is a PLANNED exit: its heartbeat key is
        deleted, so the fleet doctor never pages crit on a rolling
        restart (SIGKILL skips this path — that's how a dead process
        IS flagged)."""
        from hyperspace_tpu.interop.server import QueryServer

        s = _session(tmp_path)
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 0.05)
        server = QueryServer(s).start()
        try:
            deadline = time.monotonic() + 10
            while not fleet.live_snapshots(s.conf) \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            snaps = fleet.live_snapshots(s.conf)
            assert snaps and snaps[0]["role"] == "server"
            server.drain(grace_s=5.0)
            assert fleet.live_snapshots(s.conf) == []
            assert Hyperspace(s).doctor(fleet=True).check(
                "fleet.heartbeats").status == "ok"
        finally:
            server.stop()
            from hyperspace_tpu.lifecycle import daemon as _daemon

            _daemon.clear_drain()

    def test_scrape_fleet_mode(self, tmp_path):
        from hyperspace_tpu.interop.server import MetricsScrapeServer

        s = _session(tmp_path)
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        _put_snapshot(s.conf, _foreign(
            "peer-8-8", counters={"serve.requests": 3.0}))
        with pytest.raises(ValueError):
            MetricsScrapeServer(fleet=True)
        with MetricsScrapeServer(session=s, fleet=True) as ms:
            host, port = ms.address
            body = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=30) \
                .read().decode("utf-8")
        assert 'process="peer-8-8"' in body
        assert f'process="{fleet.process_identity()}"' in body
        assert 'hyperspace_serve_requests{process="peer-8-8"} 3' in body


# ---------------------------------------------------------------------------
# Real subprocesses over one tree (the acceptance loop)
# ---------------------------------------------------------------------------
class TestSubprocessFleet:
    @pytest.mark.parametrize("store_class", BACKENDS)
    def test_three_process_merge_and_trace(self, tmp_path, store_class):
        """3 real processes publish over the shared tree: merged
        counters equal the per-process sum, and a trace minted in one
        process resolves from THIS one via trace(id, fleet=True)."""
        s = _session(tmp_path, store_class, interval=30.0)
        hs = Hyperspace(s)
        procs = [_spawn(tmp_path / "ix", store_class, "once", c, 30.0)
                 for c in (2, 3, 4)]
        try:
            children = _read_children(procs)
            for p in procs:
                assert p.wait(timeout=60) == 0
            status = hs.fleet_status()
            assert status.num_rows == 3
            assert all(status.column("fresh").to_pylist())
            merged = hs.fleet_metrics()
            assert merged["counters"]["fleet.test.queries"] == 9.0
            for child in children:
                rec = hs.trace(child["trace"], fleet=True)
                assert rec is not None
                assert rec["process"] == child["process"]
                assert rec["error"] == "seeded in child"
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=30)

    def test_acceptance_kill_flips_fleet_doctor_to_crit(self, tmp_path):
        """The end-to-end fleet demo: 3 live publishers -> all fresh in
        fleet_status -> counters merge -> a record from process B
        resolves from here -> SIGKILL B -> doctor(fleet=True) goes crit
        naming B within 2 publish intervals."""
        interval = 0.4
        s = _session(tmp_path, interval=interval)
        hs = Hyperspace(s)
        procs = [_spawn(tmp_path / "ix", EMULATED, "hold", 5, interval)
                 for _ in range(3)]
        try:
            children = _read_children(procs)
            # Steady state: every publisher fresh, the merged counter
            # carrying the 3-process sum, and the fleet doctor ok —
            # polled together (a 0.4s heartbeat can transiently look
            # stale on a loaded box).
            deadline = time.monotonic() + 60
            state = {}
            while time.monotonic() < deadline:
                status = hs.fleet_status()
                fresh = dict(zip(status.column("process").to_pylist(),
                                 status.column("fresh").to_pylist()))
                merged = hs.fleet_metrics()["counters"].get(
                    "fleet.test.queries", 0.0)
                hb = hs.doctor(fleet=True).check("fleet.heartbeats")
                state = {"fresh": fresh, "merged": merged,
                         "hb": hb.status}
                if all(fresh.get(c["process"]) for c in children) \
                        and merged == 15.0 and hb.status == "ok":
                    break
                time.sleep(0.05)
            else:
                pytest.fail(f"fleet never reached steady state: "
                            f"{state}")
            victim = children[1]
            assert hs.trace(victim["trace"], fleet=True)["process"] \
                == victim["process"]
            os.kill(victim["pid"], signal.SIGKILL)
            t_kill = time.monotonic()
            while time.monotonic() < t_kill + 2 * interval + 2.0:
                hb = hs.doctor(fleet=True).check("fleet.heartbeats")
                if hb.status == "crit":
                    break
                time.sleep(0.05)
            assert hb.status == "crit"
            assert victim["process"] in hb.data["stale"]
            # Within 2 publish intervals of the last heartbeat (the
            # conf-derived stale threshold), plus polling slack.
            assert time.monotonic() - t_kill <= 2 * interval + 2.0
            # The dead process's record is STILL resolvable — its last
            # snapshot outlives it until pruneAfterS.
            assert hs.trace(victim["trace"], fleet=True) is not None
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=30)

    def test_restart_mints_new_identity(self, tmp_path):
        """A restarted process (same tree, new pid/start) publishes
        under a NEW key; the old process's interesting records stay
        resolvable from its last snapshot."""
        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        p1 = _spawn(tmp_path / "ix", EMULATED, "once", 1, 30.0)
        first = _read_children([p1])[0]
        assert p1.wait(timeout=60) == 0
        p2 = _spawn(tmp_path / "ix", EMULATED, "once", 1, 30.0)
        second = _read_children([p2])[0]
        assert p2.wait(timeout=60) == 0
        assert first["process"] != second["process"]
        procs = set(hs.fleet_status().column("process").to_pylist())
        assert {first["process"], second["process"]} <= procs
        assert hs.trace(first["trace"], fleet=True)["process"] \
            == first["process"]
        assert hs.trace(second["trace"], fleet=True)["process"] \
            == second["process"]


# ---------------------------------------------------------------------------
# Multi-host build claims check (docs/21)
# ---------------------------------------------------------------------------
class TestBuildClaimsCheck:
    """``fleet.build_claims`` grades leftover multi-host build claims
    (parallel/multihost_build.scan_build_claims) against the
    heartbeats: expired + nobody alive = reclaimable debris (warn);
    fresh + dead holder = a build stalling a full TTL (crit)."""

    def _plant_claim(self, conf, holder, ttl_s, build="build-1-abc"):
        from hyperspace_tpu.lifecycle.lease import WorkClaims
        from hyperspace_tpu.parallel import multihost_build
        from hyperspace_tpu.io.log_store import store_for

        store = store_for(conf, os.path.join(
            multihost_build.build_root(conf), build))
        claims = WorkClaims(store, conf, owner=holder, ttl_s=ttl_s)
        assert claims.try_claim("chunk-00000") is not None

    def test_no_claims_is_ok(self, tmp_path):
        hs = Hyperspace(_session(tmp_path, interval=30.0))
        assert hs.doctor(fleet=True).check(
            "fleet.build_claims").status == "ok"

    def test_expired_claim_no_heartbeat_warns(self, tmp_path):
        from hyperspace_tpu.lifecycle import journal as lifecycle_journal

        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        self._plant_claim(s.conf, "dead-host-1-1", ttl_s=0.2)
        time.sleep(0.3)
        before = len(lifecycle_journal.records(s.conf))
        check = hs.doctor(fleet=True).check("fleet.build_claims")
        assert check.status == "warn"
        assert check.data["expired_no_heartbeat"][0]["holder"] \
            == "dead-host-1-1"
        # The check is READ-ONLY (the doctor verb serves inline while
        # the admission queue sheds): grading must not write anything.
        # The journaled trail comes from the claim protocol itself —
        # the coordinator's expired-sighting records and WorkClaims'
        # reclaim/fence records, covered in test_multihost_build.
        assert len(lifecycle_journal.records(s.conf)) == before

    def test_fresh_claim_dead_holder_is_crit(self, tmp_path):
        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        self._plant_claim(s.conf, "dead-host-1-1", ttl_s=60.0)
        # SOMEBODY heartbeats (so liveness is gradeable) — but not the
        # claim's holder.
        _put_snapshot(s.conf, _foreign("other-host-2-2"))
        check = hs.doctor(fleet=True).check("fleet.build_claims")
        assert check.status == "crit"
        assert check.data["fresh_dead_holder"][0]["item"] == "chunk-00000"

    def test_fresh_claim_heartbeating_holder_is_ok(self, tmp_path):
        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        self._plant_claim(s.conf, "live-host-3-3", ttl_s=60.0)
        _put_snapshot(s.conf, _foreign("live-host-3-3"))
        check = hs.doctor(fleet=True).check("fleet.build_claims")
        assert check.status == "ok"
        assert check.data["pending"] == 1

    def test_fresh_claim_without_any_heartbeats_not_crit(self, tmp_path):
        # Fleet telemetry off: nothing to cross-check a live claim
        # against — the check must not page crit on a healthy build.
        s = _session(tmp_path, interval=30.0)
        hs = Hyperspace(s)
        self._plant_claim(s.conf, "host-4-4", ttl_s=60.0)
        assert hs.doctor(fleet=True).check(
            "fleet.build_claims").status == "ok"
