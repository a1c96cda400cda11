"""Serving fleet: front door, failover, tenants, async io (docs/20).

The acceptance loop: a 3-server harness behind ``FleetQueryClient``
answers a burst bit-equal while one server is SIGKILLed mid-burst
(zero retryable requests lost, retries visible as ``client.retry.*`` /
``client.failover``); draining rows are skipped by the router during
the grace window; permanent errors are never retried; per-tenant
quotas shed the hot tenant while others keep being admitted; the
``async`` io mode answers bit-equal with the threaded path; and the
lease-aware ``fleet.daemons`` doctor check grades holder-vs-heartbeat
mismatches.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, HyperspaceSession
from hyperspace_tpu.interop import (
    FleetQueryClient,
    QueryClient,
    QueryFailedError,
    QueryServer,
    ServerBusyError,
)
from hyperspace_tpu.telemetry import fleet, metrics


def _counter(name):
    return metrics.registry().counter(name)


@pytest.fixture()
def env(tmp_path):
    data = str(tmp_path / "data")
    os.makedirs(data)
    rng = np.random.default_rng(11)
    n = 1000
    pq.write_table(pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array(rng.integers(0, 100, n), type=pa.int64()),
    }), os.path.join(data, "f.parquet"))
    s = HyperspaceSession(system_path=str(tmp_path / "ix"))
    s.conf.num_buckets = 4
    return s, data


@pytest.fixture(scope="module")
def slow_dir(tmp_path_factory):
    """Big enough that a group-by holds a worker for real wall time."""
    d = str(tmp_path_factory.mktemp("fleetserv") / "big")
    os.makedirs(d)
    rng = np.random.default_rng(7)
    n = 8_000_000
    pq.write_table(pa.table({
        "g": pa.array(rng.integers(0, 2_000_000, n), type=pa.int64()),
        "x": pa.array(rng.random(n)),
        "y": pa.array(rng.random(n)),
    }), os.path.join(d, "p.parquet"))
    return d


def _point_spec(data, k):
    return {"source": {"format": "parquet", "path": data},
            "filter": {"op": "==", "col": "k", "value": int(k)},
            "select": ["k", "v"]}


def _slow_spec(slow_dir):
    return {"source": {"format": "parquet", "path": slow_dir},
            "group_by": ["g"],
            "aggs": {"t": ["x", "sum"], "m": ["x", "mean"],
                     "y2": ["y", "sum"]},
            "sort": [["t", False]], "limit": 5}


# ---------------------------------------------------------------------------
# Front-door routing and retry policy (in-process endpoints)
# ---------------------------------------------------------------------------
class _BusyEndpoint:
    """A fake server that answers every request line with a retryable
    ``ERR BUSY`` carrying a retry-after hint, then closes — the
    overload shape the front door must route around."""

    def __init__(self, retry_after_ms=120):
        self._listener = socket.create_server(("127.0.0.1", 0))
        self.address = self._listener.getsockname()
        self._hint = retry_after_ms
        self.hits = 0
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            try:
                f = conn.makefile("rb")
                if f.readline():
                    self.hits += 1
                    conn.sendall(
                        f"ERR BUSY admission queue full; retry later "
                        f"retry-after-ms={self._hint}\n".encode())
                conn.close()
            except OSError:
                pass

    def close(self):
        self._stop = True
        try:
            self._listener.close()
        except OSError:
            pass


class TestFrontDoor:
    def test_busy_retries_on_other_endpoint(self, env):
        s, data = env
        busy = _BusyEndpoint(retry_after_ms=120)
        retry0 = _counter("client.retry.busy")
        fail0 = _counter("client.failover")
        try:
            with QueryServer(s) as real:
                with FleetQueryClient([busy.address, real.address]) as fc:
                    for k in range(6):
                        t = fc.query(_point_spec(data, k))
                        assert t.column("k").to_pylist() == [k]
        finally:
            busy.close()
        # Round-robin over equal loads sent SOME requests into the busy
        # endpoint; every one of them was retried onto the survivor.
        assert busy.hits >= 1
        assert _counter("client.retry.busy") - retry0 >= busy.hits
        assert _counter("client.failover") - fail0 >= 1

    def test_busy_endpoint_penalized_by_hint(self, env):
        s, data = env
        busy = _BusyEndpoint(retry_after_ms=30_000)  # park it for good
        try:
            with QueryServer(s) as real:
                with FleetQueryClient([busy.address, real.address]) as fc:
                    for k in range(8):
                        fc.query(_point_spec(data, k))
                    hits_mid = busy.hits
                    # The 30 s penalty outlives the loop: once hit, the
                    # busy endpoint never gets picked again.
                    for k in range(8):
                        fc.query(_point_spec(data, k))
                    assert busy.hits == hits_mid
                    ep = fc._endpoints[0]
                    assert ep.penalized_until > time.monotonic()
        finally:
            busy.close()

    def test_permanent_errors_not_retried(self, env):
        s, data = env
        bad = {"source": {"format": "parquet", "path": data},
               "filter": {"op": "==", "col": "no_such_col", "value": 1}}
        retry0 = _counter("client.retry")
        with QueryServer(s) as a, QueryServer(s) as b:
            with FleetQueryClient([a.address, b.address]) as fc:
                with pytest.raises(QueryFailedError) as ei:
                    fc.query(bad)
                assert ei.value.code == "FAILED"
                with pytest.raises(QueryFailedError) as ei:
                    fc.query({"sql": 123, "tables": {}})
                assert ei.value.code == "BADREQ"
        # A permanent error re-run elsewhere fails N times for nothing:
        # neither attempt above consumed a single retry.
        assert _counter("client.retry") - retry0 == 0

    def test_draining_row_skipped(self, env):
        """The drain-grace routing hole: a draining server's heartbeat
        row says so, and the router stops picking it — requests go to
        the survivor instead of bouncing off ERR BUSY."""
        from hyperspace_tpu.io.log_store import store_for

        s, data = env
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 30.0)
        with QueryServer(s) as a, QueryServer(s) as b:
            store = store_for(s.conf, fleet.fleet_root(s.conf))
            for srv, draining in ((a, True), (b, False)):
                addr = f"{srv.address[0]}:{srv.address[1]}"
                snap = {"v": 1, "ts": time.time(),
                        "process": f"p-{srv.address[1]}", "host": "h",
                        "pid": 1, "role": "server", "health": None,
                        "address": addr, "draining": draining,
                        "metrics": {"counters": {}, "gauges": {},
                                    "histograms": {}},
                        "device_kernel_ms": {}, "records": []}
                key = "hb-" + snap["process"]
                assert store.put_if_generation_match(
                    key, json.dumps(snap).encode(), store.generation(key))
            with FleetQueryClient([a.address, b.address],
                                  conf=s.conf) as fc:
                for k in range(6):
                    assert fc.query(_point_spec(data, k)) \
                        .column("k").to_pylist() == [k]
                assert fc._endpoints[0].draining is True
                assert fc._endpoints[1].draining is False
                # Every request routed around the draining endpoint.
                assert fc._endpoints[0].inflight == 0
                assert not fc._endpoints[0].idle


# ---------------------------------------------------------------------------
# Per-tenant admission
# ---------------------------------------------------------------------------
class TestTenantAdmission:
    def test_quota_sheds_hot_tenant_only(self, env, slow_dir):
        s, data = env
        s.conf.serving_workers = 1
        s.conf.set("hyperspace.serving.tenant.maxQueued", 1)
        shed0 = _counter("serve.shed.tenant")
        with QueryServer(s) as server:
            out = {}

            def hot():
                with QueryClient(server.address, tenant="hot") as c:
                    out["slow"] = c.query(_slow_spec(slow_dir))

            t = threading.Thread(target=hot)
            t.start()
            time.sleep(0.4)  # the hot tenant's query is queued-or-active
            with QueryClient(server.address, tenant="hot") as c:
                with pytest.raises(ServerBusyError, match="quota") as ei:
                    c.query(_point_spec(data, 1))
                assert ei.value.retryable
                assert ei.value.retry_after_ms is not None
            # Another tenant is admitted while "hot" is at its quota —
            # it waits for the worker rather than being shed.
            with QueryClient(server.address, tenant="cold") as c:
                assert c.query(_point_spec(data, 2)) \
                    .column("k").to_pylist() == [2]
            t.join(timeout=120)
        assert out["slow"].num_rows == 5
        assert _counter("serve.shed.tenant") - shed0 >= 1
        snap = metrics.snapshot()
        assert snap.get("serve.tenant.hot.shed", 0.0) >= 1.0

    def test_tenants_verb_reports(self, env, slow_dir):
        s, data = env
        s.conf.serving_workers = 1
        s.conf.set("hyperspace.serving.tenant.maxQueued", 1)
        with QueryServer(s) as server:
            done = {}

            def hot():
                with QueryClient(server.address, tenant="tv-a") as c:
                    done["t"] = c.query(_slow_spec(slow_dir))

            t = threading.Thread(target=hot)
            t.start()
            time.sleep(0.4)
            with QueryClient(server.address, tenant="tv-a") as c:
                with pytest.raises(ServerBusyError):
                    c.query(_point_spec(data, 1))
            # Verbs answer inline — exactly while the worker is pinned.
            with QueryClient(server.address) as c:
                table = c.query({"verb": "tenants"})
            rows = {t_: (q, sh) for t_, q, sh in zip(
                table.column("tenant").to_pylist(),
                table.column("queued").to_pylist(),
                table.column("shed").to_pylist())}
            assert rows["tv-a"][0] >= 1  # still queued-or-active
            assert rows["tv-a"][1] >= 1  # and it was shed once
            t.join(timeout=120)
        assert done["t"].num_rows == 5

    def test_tenant_must_be_string(self, env):
        s, data = env
        with QueryServer(s) as server:
            with QueryClient(server.address) as c:
                with pytest.raises(QueryFailedError, match="tenant") as ei:
                    c.query({**_point_spec(data, 1), "tenant": 7})
            assert ei.value.code == "BADREQ"


# ---------------------------------------------------------------------------
# Async io mode: bit-equal with the threaded path
# ---------------------------------------------------------------------------
class TestAsyncIOMode:
    def test_bad_mode_rejected(self, env):
        s, _data = env
        s.conf.set("hyperspace.serving.ioMode", "fiber")
        with pytest.raises(ValueError, match="ioMode"):
            QueryServer(s)
        s.conf.set("hyperspace.serving.ioMode", "threaded")

    def test_bit_equal_results_and_errors(self, env):
        s, data = env
        specs = [_point_spec(data, 3),
                 {"source": {"format": "parquet", "path": data},
                  "group_by": ["v"], "aggs": {"n": ["k", "count"]},
                  "sort": [["v", True]], "limit": 10},
                 {"verb": "metrics"}]
        with QueryServer(s) as threaded:
            with QueryClient(threaded.address) as c:
                want = [c.query(sp) for sp in specs]
            with pytest.raises(QueryFailedError) as ei:
                with QueryClient(threaded.address) as c:
                    c.query({"sql": 123, "tables": {}})
            want_err = (ei.value.code, ei.value.message)
        s.conf.set("hyperspace.serving.ioMode", "async")
        try:
            with QueryServer(s) as asy:
                with QueryClient(asy.address) as c:
                    got = [c.query(sp) for sp in specs]  # pipelined
                with pytest.raises(QueryFailedError) as ei:
                    with QueryClient(asy.address) as c:
                        c.query({"sql": 123, "tables": {}})
                got_err = (ei.value.code, ei.value.message)
        finally:
            s.conf.set("hyperspace.serving.ioMode", "threaded")
        # Query results are bit-equal; the metrics verb shares a schema
        # (values differ between two live processes, by design).
        assert got[0].equals(want[0])
        assert got[1].equals(want[1])
        assert got[2].schema == want[2].schema
        assert got_err == want_err

    def test_async_connection_cap_and_drain(self, env):
        s, data = env
        s.conf.serving_max_connections = 1
        s.conf.set("hyperspace.serving.ioMode", "async")
        try:
            server = QueryServer(s).start()
            c1 = QueryClient(server.address)
            assert c1.query(_point_spec(data, 5)) \
                .column("k").to_pylist() == [5]
            # Beyond the cap: the loop answers ERR BUSY without ever
            # registering the connection.
            with pytest.raises(ServerBusyError, match="capacity"):
                QueryClient(server.address).query(_point_spec(data, 6))
            c1.close()
            assert server.drain(grace_s=10) is True
            with pytest.raises(OSError):
                socket.create_connection(server.address, timeout=2)
        finally:
            from hyperspace_tpu.lifecycle import daemon as _daemon

            _daemon.clear_drain()
            s.conf.set("hyperspace.serving.ioMode", "threaded")


# ---------------------------------------------------------------------------
# Drain publishes a draining heartbeat during the grace window
# ---------------------------------------------------------------------------
class TestDrainingHeartbeat:
    def test_drain_flags_row_then_deregisters(self, env, slow_dir):
        s, data = env
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 0.2)
        try:
            server = QueryServer(s).start()
            addr = f"{server.address[0]}:{server.address[1]}"
            done = {}

            def slow():
                with QueryClient(server.address) as c:
                    done["t"] = c.query(_slow_spec(slow_dir))

            t = threading.Thread(target=slow)
            t.start()
            time.sleep(0.4)  # in flight — drain will wait on it
            drainer = threading.Thread(
                target=lambda: done.update(
                    clean=server.drain(grace_s=120)))
            drainer.start()
            # During the grace window the heartbeat says draining=True:
            # the front door routes around this server instead of
            # bouncing off its ERR BUSY.
            deadline = time.monotonic() + 10
            row = None
            while time.monotonic() < deadline:
                rows = [r for r in fleet.fresh_snapshots(s.conf)
                        if r.get("address") == addr]
                if rows and rows[0].get("draining"):
                    row = rows[0]
                    break
                time.sleep(0.05)
            assert row is not None, "no draining heartbeat published"
            t.join(timeout=120)
            drainer.join(timeout=120)
            assert done["clean"] is True
            assert done["t"].num_rows == 5
            # A completed drain is a PLANNED exit: deregistered, not a
            # corpse for the doctor to page on.
            assert all(r.get("address") != addr
                       for r in fleet.live_snapshots(s.conf))
        finally:
            from hyperspace_tpu.lifecycle import daemon as _daemon

            _daemon.clear_drain()
            fleet.set_serving_draining(False)
            s.conf.set("hyperspace.fleet.telemetry.enabled", False)


# ---------------------------------------------------------------------------
# Lease-aware fleet.daemons doctor check
# ---------------------------------------------------------------------------
class TestDaemonsCheck:
    def _put_snapshot(self, conf, snap):
        from hyperspace_tpu.io.log_store import store_for

        store = store_for(conf, fleet.fleet_root(conf))
        key = "hb-" + snap["process"]
        payload = json.dumps(snap, default=str).encode("utf-8")
        assert store.put_if_generation_match(key, payload,
                                             store.generation(key))

    def _foreign(self, process, role="server"):
        return {"v": 1, "ts": time.time(), "process": process,
                "host": "h", "pid": 1, "role": role, "health": None,
                "address": "", "draining": False,
                "metrics": {"counters": {}, "gauges": {},
                            "histograms": {}},
                "device_kernel_ms": {}, "records": []}

    def _session(self, tmp_path, ttl=30.0):
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 30.0)
        s.conf.set("hyperspace.lifecycle.lease.enabled", True)
        s.conf.set("hyperspace.lifecycle.lease.ttlS", ttl)
        return s

    def test_crit_when_holder_has_no_heartbeat(self, tmp_path):
        from hyperspace_tpu.lifecycle import lease

        s = self._session(tmp_path)
        held = lease.MaintenanceLease(s.conf, owner="ghost-9-9")
        assert held.ensure() is True
        self._put_snapshot(s.conf, self._foreign("live-1-1"))
        report = Hyperspace(s).doctor(fleet=True)
        check = report.check("fleet.daemons")
        assert check.status == "crit"
        assert "ghost-9-9" in check.summary
        assert check.data["holder"] == "ghost-9-9"

    def test_ok_when_holder_is_live(self, tmp_path):
        from hyperspace_tpu.lifecycle import lease

        s = self._session(tmp_path)
        held = lease.MaintenanceLease(s.conf, owner="live-1-1")
        assert held.ensure() is True
        self._put_snapshot(s.conf, self._foreign("live-1-1"))
        self._put_snapshot(s.conf,
                           self._foreign("standby-2-2", role="daemon"))
        check = Hyperspace(s).doctor(fleet=True).check("fleet.daemons")
        assert check.status == "ok"
        assert check.data["holder"] == "live-1-1"

    def test_warn_when_expired_with_candidates(self, tmp_path):
        from hyperspace_tpu.lifecycle import lease

        s = self._session(tmp_path, ttl=0.2)
        held = lease.MaintenanceLease(s.conf, owner="was-1-1")
        assert held.ensure() is True
        time.sleep(0.3)  # lease expires un-renewed
        self._put_snapshot(s.conf, self._foreign("cand-2-2",
                                                 role="daemon"))
        check = Hyperspace(s).doctor(fleet=True).check("fleet.daemons")
        assert check.status == "warn"
        assert "takeover" in check.summary

    def test_legacy_warn_without_lease_preserved(self, tmp_path):
        s = self._session(tmp_path)
        s.conf.set("hyperspace.lifecycle.lease.enabled", False)
        self._put_snapshot(s.conf, self._foreign("d1-1-1", role="daemon"))
        self._put_snapshot(s.conf, self._foreign("d2-2-2", role="daemon"))
        check = Hyperspace(s).doctor(fleet=True).check("fleet.daemons")
        assert check.status == "warn"
        assert "lease" in check.summary


# ---------------------------------------------------------------------------
# The 3-server churn drill (subprocess harness)
# ---------------------------------------------------------------------------
_SERVER_CHILD = r"""
import json, os, sys
from hyperspace_tpu import HyperspaceSession
from hyperspace_tpu.interop import QueryServer

system_path, interval = sys.argv[1], float(sys.argv[2])
s = HyperspaceSession(system_path=system_path)
s.conf.set("hyperspace.fleet.telemetry.enabled", True)
s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", interval)
server = QueryServer(s, handle_sigterm=True).start()
print(json.dumps({"port": server.address[1], "pid": os.getpid()}),
      flush=True)
server.drained.wait()
sys.exit(0)
"""


class TestFleetChurn:
    def test_sigkill_mid_burst_loses_nothing(self, tmp_path):
        data = str(tmp_path / "data")
        os.makedirs(data)
        n = 1000
        pq.write_table(pa.table({
            "k": pa.array(np.arange(n, dtype=np.int64)),
            "v": pa.array(np.arange(n, dtype=np.int64) * 2),
        }), os.path.join(data, "f.parquet"))
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 0.2)
        env_vars = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen(
            [sys.executable, "-c", _SERVER_CHILD, str(tmp_path / "ix"),
             "0.2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env_vars) for _ in range(3)]
        try:
            children = []
            for p in procs:
                line = p.stdout.readline()
                assert line, p.stderr.read()
                children.append(json.loads(line))
            endpoints = [("127.0.0.1", c["port"]) for c in children]
            retry0 = _counter("client.retry")
            conn0 = _counter("client.retry.connection")
            fail0 = _counter("client.failover")
            with FleetQueryClient(endpoints, conf=s.conf) as fc:
                def check(k):
                    t = fc.query({
                        "source": {"format": "parquet", "path": data},
                        "filter": {"op": "==", "col": "k",
                                   "value": int(k)},
                        "select": ["k", "v"]})
                    assert t.column("v").to_pylist() == [2 * k], k

                for k in range(20):      # warm: all three serving
                    check(k)
                # The router folds heartbeats in once a second and drops
                # any older than two publish intervals (0.4 s), which a
                # loaded host's publishers can all miss at that instant:
                # keep warming until one refresh lands on a fresh row.
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline and all(
                        ep.load is None for ep in fc._endpoints):
                    check(0)
                    time.sleep(0.05)
                # Fleet rows surfaced the children (addresses matched).
                assert sum(1 for ep in fc._endpoints
                           if ep.load is not None) >= 1
                os.kill(children[0]["pid"], signal.SIGKILL)
                procs[0].wait(timeout=30)
                for k in range(60):      # mid-burst churn
                    check(k % n)
            # ZERO retryable requests lost (every check asserted
            # bit-equal), and the router visibly failed over.
            assert _counter("client.retry") - retry0 >= 1
            assert _counter("client.retry.connection") - conn0 >= 1
            assert _counter("client.failover") - fail0 >= 1
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=30)

    def test_sigterm_drain_is_planned_exit(self, tmp_path):
        data = str(tmp_path / "data")
        os.makedirs(data)
        pq.write_table(pa.table({
            "k": pa.array(np.arange(100, dtype=np.int64)),
            "v": pa.array(np.arange(100, dtype=np.int64)),
        }), os.path.join(data, "f.parquet"))
        s = HyperspaceSession(system_path=str(tmp_path / "ix"))
        s.conf.set("hyperspace.fleet.telemetry.enabled", True)
        s.conf.set("hyperspace.fleet.telemetry.publishIntervalS", 0.2)
        env_vars = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [subprocess.Popen(
            [sys.executable, "-c", _SERVER_CHILD, str(tmp_path / "ix"),
             "0.2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env_vars) for _ in range(2)]
        try:
            children = []
            for p in procs:
                line = p.stdout.readline()
                assert line, p.stderr.read()
                children.append(json.loads(line))
            endpoints = [("127.0.0.1", c["port"]) for c in children]
            with FleetQueryClient(endpoints, conf=s.conf) as fc:
                for k in range(6):
                    fc.query({"source": {"format": "parquet",
                                         "path": data},
                              "filter": {"op": "==", "col": "k",
                                         "value": int(k)}})
                os.kill(children[0]["pid"], signal.SIGTERM)
                assert procs[0].wait(timeout=60) == 0  # drained, exit 0
                # The drained server deregistered its heartbeat — a
                # planned exit, not a corpse; the survivor still serves.
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    live = {r.get("address")
                            for r in fleet.live_snapshots(s.conf)}
                    if f"127.0.0.1:{children[0]['port']}" not in live:
                        break
                    time.sleep(0.1)
                assert f"127.0.0.1:{children[0]['port']}" not in live
                for k in range(6):
                    t = fc.query({"source": {"format": "parquet",
                                             "path": data},
                                  "filter": {"op": "==", "col": "k",
                                             "value": int(k)}})
                    assert t.num_rows == 1
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=30)
