"""SLO alert engine (telemetry/slo.py + telemetry/alerts.py, docs/16).

Four layers, mirroring the module split:

  1. the PURE math — burn windows under clock skew / counter resets,
     and the flap-damped state machine (zero IO);
  2. persistence — transition records and restart-proof state over BOTH
     LogStore backends;
  3. the end-to-end demo — a served workload, an armed ``net.send``
     wire fault, the fast-burn page within two evaluation intervals, an
     incident bundle readable from a FRESH session with its trace ids
     resolving, then disarm → resolve;
  4. surfacing — ``Hyperspace.alerts()`` / ``alert_history()``, the
     inline interop verb, fleet federation + cluster-doctor grading,
     the notify seam, and the ``tools/doctor.py`` exit-code gate.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from hyperspace_tpu.session import HyperspaceSession
from hyperspace_tpu.telemetry import alerts, slo
from hyperspace_tpu.telemetry import metrics as _metrics

BOTH_STORES = ["hyperspace_tpu.io.log_store.PosixLogStore",
               "hyperspace_tpu.io.log_store.EmulatedObjectStore"]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session(tmp_path, **conf):
    s = HyperspaceSession(system_path=str(tmp_path / "sys"))
    for key, value in conf.items():
        s.conf.set(key, value)
    return s


def _tiny_window_conf(**extra):
    conf = {
        "hyperspace.alerts.enabled": True,
        "hyperspace.alerts.intervalS": 0.05,
        "hyperspace.alerts.availabilityTarget": 0.9,
        "hyperspace.alerts.fastShortS": 0.2,
        "hyperspace.alerts.fastLongS": 0.4,
        "hyperspace.alerts.fastFactor": 1.5,
        "hyperspace.alerts.pendingEvals": 1,
        "hyperspace.alerts.resolveEvals": 1,
    }
    conf.update(extra)
    return conf


def _drive_to_firing(engine, bad_counter="serve.errors",
                     deadline_s=20.0) -> None:
    """Tick the engine with injected bad traffic until availability
    fires (tiny windows: a handful of ticks)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        _metrics.inc(bad_counter, 25)
        engine.run_once()
        st = engine.current_states().get("availability", {})
        if st.get("state") == slo.FIRING:
            return
        time.sleep(0.08)
    raise AssertionError("availability never fired under injected "
                         f"{bad_counter}")


def _drive_to_resolved(engine, deadline_s=20.0) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        _metrics.inc("serve.ok", 50)
        engine.run_once()
        st = engine.current_states().get("availability", {})
        if st.get("state") != slo.FIRING:
            return
        time.sleep(0.08)
    raise AssertionError("availability never resolved after recovery")


# ---------------------------------------------------------------------------
# 1. Pure math: windows under skew, flap damping
# ---------------------------------------------------------------------------
class TestWindowMath:
    def test_basic_delta(self):
        ring = [slo.Sample(0.0, 100, 0), slo.Sample(10.0, 150, 5)]
        good, bad, cov = slo.window_delta(ring, 10.0, 10.0)
        assert (good, bad, cov) == (50, 5, 10.0)

    def test_out_of_order_samples_are_sorted(self):
        # An NTP step that reorders appends must not invert the delta.
        ring = [slo.Sample(10.0, 150, 5), slo.Sample(0.0, 100, 0)]
        good, bad, _cov = slo.window_delta(ring, 10.0, 10.0)
        assert (good, bad) == (50, 5)

    def test_counter_reset_reads_empty(self):
        # Restart inside the window: cumulative counters went BACKWARD.
        # No data beats a huge phantom burn.
        ring = [slo.Sample(0.0, 1000, 50), slo.Sample(10.0, 20, 1)]
        assert slo.window_delta(ring, 10.0, 10.0) == (0.0, 0.0, 0.0)

    def test_window_base_clamps_to_oldest(self):
        ring = [slo.Sample(8.0, 10, 0), slo.Sample(10.0, 20, 2)]
        good, bad, cov = slo.window_delta(ring, 10.0, 100.0)
        assert (good, bad) == (10, 2)
        assert cov == pytest.approx(2.0)

    def test_empty_and_degenerate(self):
        assert slo.window_delta([], 0.0, 5.0) == (0.0, 0.0, 0.0)
        assert slo.burn_rate(0, 0, 0.1) == 0.0
        assert slo.burn_rate(50, 50, 0.0) == 0.0  # target >= 1

    def test_burn_rate(self):
        # 10% bad over a 1% budget burns 10 budgets per window.
        assert slo.burn_rate(90, 10, 0.01) == pytest.approx(10.0)

    def test_incomplete_window_cannot_breach(self):
        rule = slo.BurnRule("fast_burn", 10.0, 100.0, 1.0, "page")
        ring = [slo.Sample(0.0, 0, 0), slo.Sample(3.0, 0, 50)]
        ev = slo.evaluate_rule(ring, 3.0, rule, 0.1)
        assert ev["burn_short"] >= 1.0  # burning hard...
        assert not ev["complete"]       # ...but 3s of a 100s window
        assert not ev["breached"]

    def test_both_windows_must_breach(self):
        rule = slo.BurnRule("fast_burn", 4.0, 8.0, 2.0, "page")
        # Long window burns, short window has recovered: no page.
        ring = [slo.Sample(0.0, 0, 0), slo.Sample(4.0, 0, 100),
                slo.Sample(8.0, 100, 100)]
        ev = slo.evaluate_rule(ring, 8.0, rule, 0.1)
        assert ev["burn_long"] >= 2.0
        assert ev["burn_short"] < 2.0
        assert not ev["breached"]

    def test_objective_page_beats_warn(self):
        rules = [slo.BurnRule("slow_burn", 2.0, 4.0, 1.0, "warn"),
                 slo.BurnRule("fast_burn", 2.0, 4.0, 1.0, "page")]
        ring = [slo.Sample(0.0, 0, 0), slo.Sample(2.0, 0, 50),
                slo.Sample(4.0, 0, 100)]
        out = slo.evaluate_objective(ring, 4.0, rules, 0.9)
        assert out["breached"] and out["severity"] == "page"
        assert out["worst_rule"] == "fast_burn"

    def test_threshold_objective_none_never_breaches(self):
        assert not slo.threshold_objective(None, 1.0, "page")["breached"]
        assert slo.threshold_objective(3.0, 1.0, "page")["breached"]
        assert not slo.threshold_objective(0.5, 1.0, "warn")["breached"]

    def test_hist_split(self):
        # Buckets are per-bin (each observation lands in exactly one),
        # matching metrics._Histogram.snapshot().
        hist = {"count": 10,
                "buckets": {100.0: 4, 1000.0: 3, "+Inf": 3}}
        assert slo.hist_split(hist, 1000.0) == (7.0, 3.0)
        assert slo.hist_split(None, 1000.0) == (0.0, 0.0)
        assert slo.hist_split({"count": 0, "buckets": {}}, 10) == (0, 0)


class TestFlapDamping:
    def test_single_bad_tick_never_pages(self):
        st, tr = slo.step_state(None, True, "page", 1.0,
                                pending_evals=2, resolve_evals=2)
        assert (st["state"], tr) == (slo.PENDING, None)
        st, tr = slo.step_state(st, False, "", 2.0,
                                pending_evals=2, resolve_evals=2)
        assert (st["state"], tr) == (slo.RESOLVED, None)  # no page sent

    def test_sustained_breach_promotes_then_damped_resolve(self):
        st, tr = slo.step_state(None, True, "page", 1.0, 2, 2)
        assert (st["state"], tr) == (slo.PENDING, None)
        st, tr = slo.step_state(st, True, "page", 2.0, 2, 2)
        assert (st["state"], tr) == (slo.FIRING, "firing")
        # One good tick mid-incident must NOT close the page...
        st, tr = slo.step_state(st, False, "", 3.0, 2, 2)
        assert (st["state"], tr) == (slo.FIRING, None)
        # ...and a relapse resets the resolve streak.
        st, tr = slo.step_state(st, True, "page", 4.0, 2, 2)
        assert (st["state"], tr) == (slo.FIRING, None)
        st, tr = slo.step_state(st, False, "", 5.0, 2, 2)
        assert (st["state"], tr) == (slo.FIRING, None)
        st, tr = slo.step_state(st, False, "", 6.0, 2, 2)
        assert (st["state"], tr) == (slo.RESOLVED, "resolved")

    def test_pending_evals_one_fires_immediately(self):
        st, tr = slo.step_state(None, True, "warn", 1.0,
                                pending_evals=1, resolve_evals=1)
        assert (st["state"], tr) == (slo.FIRING, "firing")
        st, tr = slo.step_state(st, False, "", 2.0, 1, 1)
        assert (st["state"], tr) == (slo.RESOLVED, "resolved")

    def test_firing_keeps_since_and_severity(self):
        st, _ = slo.step_state(None, True, "page", 5.0, 1, 2)
        since = st["since"]
        st, tr = slo.step_state(st, True, "page", 9.0, 1, 2)
        assert tr is None and st["since"] == since
        st, _ = slo.step_state(st, False, "", 10.0, 1, 2)
        assert st["severity"] == "page"  # still firing, still a page


# ---------------------------------------------------------------------------
# 2. Persistence: both backends, restart-proof state
# ---------------------------------------------------------------------------
class TestPersistence:
    @pytest.mark.parametrize("store_cls", BOTH_STORES)
    def test_transition_log_round_trip(self, tmp_path, store_cls):
        s = _session(tmp_path)
        s.conf.log_store_class = store_cls
        key = alerts.append_transition(s.conf, {
            "alert": "availability", "state": "firing",
            "prev_state": "pending", "severity": "page",
            "transition": "firing", "since": 1.0,
            "bundle_key": "b-xyz", "detail": {"why": "test"}})
        assert key is not None
        recs = alerts.records(s.conf)
        assert [r["alert"] for r in recs] == ["availability"]
        assert recs[0]["v"] == alerts.RECORD_VERSION
        states = alerts.load_states(s.conf)
        assert states["availability"]["state"] == "firing"
        assert states["availability"]["bundle_key"] == "b-xyz"

    @pytest.mark.parametrize("store_cls", BOTH_STORES)
    def test_firing_survives_restart_and_reresolves(self, tmp_path,
                                                    store_cls):
        conf = _tiny_window_conf()
        s1 = _session(tmp_path, **conf)
        s1.conf.log_store_class = store_cls
        engine1 = alerts.engine_for(s1)
        _drive_to_firing(engine1)
        st = engine1.current_states()["availability"]
        assert st["state"] == slo.FIRING and st["severity"] == "page"

        # "Restart": a fresh session over the same tree, fresh engine.
        s2 = _session(tmp_path, **conf)
        s2.conf.log_store_class = store_cls
        engine2 = alerts.engine_for(s2)
        assert engine2 is not engine1
        st = engine2.current_states()["availability"]
        assert st["state"] == slo.FIRING  # restart-proof
        _drive_to_resolved(engine2)
        last = alerts.records(s2.conf)[-1]
        assert last["alert"] == "availability"
        assert last["transition"] == "resolved"

    def test_prune_never_drops_latest_per_alert(self, tmp_path):
        s = _session(tmp_path)
        s.conf.set("hyperspace.alerts.maxEntries", 4)
        alerts.append_transition(s.conf, {
            "alert": "latency", "state": "firing", "severity": "page",
            "transition": "firing", "since": 1.0})
        for i in range(8):
            alerts.append_transition(s.conf, {
                "alert": "availability",
                "state": "firing" if i % 2 == 0 else "resolved",
                "transition": "firing" if i % 2 == 0 else "resolved",
                "since": float(i)})
        states = alerts.load_states(s.conf)
        # The old latency record outlived eight newer appends: it is the
        # only record carrying that alert's state.
        assert states["latency"]["state"] == "firing"
        assert len(alerts.records(s.conf)) <= 4 + 1

    def test_carried_alerts_store_free_when_disabled(self, tmp_path):
        s = _session(tmp_path)
        assert alerts.carried_alerts(s.conf) == []
        assert not os.path.exists(alerts.alert_root(s.conf))

    def test_engine_start_requires_opt_in(self, tmp_path):
        from hyperspace_tpu.exceptions import HyperspaceError

        s = _session(tmp_path)
        with pytest.raises(HyperspaceError, match="opt-in"):
            alerts.engine_for(s).start()
        assert alerts.maybe_start(s) is None  # never raises


# ---------------------------------------------------------------------------
# 3. End to end: wire fault -> page -> bundle -> disarm -> resolve
# ---------------------------------------------------------------------------
class TestEndToEnd:
    def test_wire_fault_fires_bundles_and_resolves(self, tmp_path):
        from hyperspace_tpu.interop.server import QueryServer
        from hyperspace_tpu.io import faults
        from hyperspace_tpu.telemetry import fleet, flight_recorder

        s = _session(tmp_path)
        server = QueryServer(s, port=0).start()
        port = server.address[1]
        # Enable AFTER start so no background thread races the manual
        # run_once ticks below.
        for key, value in _tiny_window_conf().items():
            s.conf.set(key, value)
        engine = alerts.engine_for(s)

        def probe(read=True):
            sock = socket.create_connection(("127.0.0.1", port),
                                            timeout=2.0)
            try:
                sock.sendall(b'{"verb": "metrics"}\n')
                if read:
                    sock.recv(65536)
            finally:
                sock.close()

        try:
            # Good traffic + ticks until the burn windows have coverage.
            for _ in range(8):
                probe()
                engine.run_once()
                time.sleep(0.08)
            assert engine.current_states().get(
                "availability", {}).get("state") != slo.FIRING

            # Arm the wire fault: every response send black-holes, each
            # probe lands as a serve.send_timeouts bad event.
            faults.install(faults.FaultPlan(
                site="net.send", kind="black-hole", at=1,
                count=10 ** 6, hang_s=0.01))
            deadline = time.monotonic() + 15.0
            fired_after = None
            ticks = 0
            while time.monotonic() < deadline:
                for _ in range(6):
                    try:
                        probe(read=False)
                    except OSError:
                        pass
                time.sleep(0.1)
                engine.run_once()
                ticks += 1
                st = engine.current_states().get("availability", {})
                if st.get("state") == slo.FIRING:
                    fired_after = ticks
                    break
            assert fired_after is not None, "fast burn never fired"
            # Within two evaluation intervals of the windows having bad
            # coverage: one tick to breach+pend... with pendingEvals=1
            # the page lands as soon as the short window turns over.
            assert fired_after <= 1 + int(
                0.4 / 0.1) + 1, f"took {fired_after} ticks to fire"
            st = engine.current_states()["availability"]
            assert st["severity"] == "page"
            bundle_key = st.get("bundle_key")
            assert bundle_key, "firing transition captured no bundle"
            faults.clear()

            # Disarm -> good traffic -> resolve.
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                for _ in range(6):
                    probe()
                time.sleep(0.1)
                engine.run_once()
                if engine.current_states()["availability"]["state"] \
                        != slo.FIRING:
                    break
            assert engine.current_states()["availability"]["state"] \
                == slo.RESOLVED
        finally:
            faults.clear()
            server.stop()

        # A FRESH session (new process's view) reads the incident back:
        # the bundle parses, and its flight-recorder trace ids resolve
        # through the federated diagnostics path.
        fresh = _session(tmp_path, **_tiny_window_conf())
        bundle = next(b for b in flight_recorder.bundles(fresh.conf)
                      if b.get("key") == bundle_key)
        incident = bundle["incident"]
        assert incident["alert"] == "availability"
        assert incident["evaluation"]["breached"]
        assert "window" in incident and "availability" in \
            incident["window"]
        tids = [r.get("trace_id") for r in bundle.get("records", [])
                if isinstance(r, dict) and r.get("trace_id")]
        if tids:  # the served probes left recorded requests
            hit = fleet.find_trace(fresh.conf, tids[0])
            assert hit is not None and hit.get("trace_id") == tids[0]
        # And the persisted state machine replays: fired then resolved.
        transitions = [r["transition"] for r in alerts.records(fresh.conf)
                       if r["alert"] == "availability"]
        assert transitions == ["firing", "resolved"]

    def test_chaos_alert_drill_invariant(self, tmp_path):
        # The exact invariant the chaos drill gates on, via the shared
        # helper.
        from hyperspace_tpu.interop.chaos import _alert_drill

        s = _session(tmp_path)
        out = _alert_drill(s)
        assert out["ok"], out


# ---------------------------------------------------------------------------
# 4. Surfacing: API, interop verb, federation, notify, CLI
# ---------------------------------------------------------------------------
class TestSurfacing:
    def _fired_session(self, tmp_path):
        s = _session(tmp_path, **_tiny_window_conf())
        _drive_to_firing(alerts.engine_for(s))
        return s

    def test_hyperspace_alerts_and_history(self, tmp_path):
        from hyperspace_tpu import Hyperspace

        s = self._fired_session(tmp_path)
        hs = Hyperspace(s)
        table = hs.alerts()
        row = {c: table.column(c)[i].as_py()
               for i, a in enumerate(table.column("alert").to_pylist())
               for c in table.column_names if a == "availability"}
        assert row["state"] == "firing" and row["severity"] == "page"
        assert row["bundleKey"].startswith("b-")
        hist = hs.alert_history()
        assert "firing" in hist.column("transition").to_pylist()
        assert json.loads(hist.column("recordJson")[0].as_py())

    def test_interop_alerts_verb_inline(self, tmp_path):
        from hyperspace_tpu.interop.server import QueryClient, QueryServer

        s = self._fired_session(tmp_path)
        with QueryServer(s) as server:
            with QueryClient(server.address) as qc:
                table = qc.query({"verb": "alerts"})
                assert "availability" in \
                    table.column("alert").to_pylist()
                fleet_t = qc.query({"verb": "alerts", "fleet": True})
                assert all(p for p in
                           fleet_t.column("process").to_pylist())
                with pytest.raises(Exception, match="alerts"):
                    qc.query({"verb": "nonsense"})

    def test_fleet_snapshot_carries_alerts(self, tmp_path):
        from hyperspace_tpu.telemetry import fleet

        s = self._fired_session(tmp_path)
        snap = fleet.build_snapshot(s.conf)
        carried = [a["alert"] for a in snap["alerts"]]
        assert "availability" in carried

    def test_fleet_federation_and_cluster_doctor(self, tmp_path,
                                                 monkeypatch):
        from hyperspace_tpu.telemetry import fleet

        s = self._fired_session(tmp_path)
        remote = {"process": "host-2:9:deadbeef",
                  "alerts": [{"alert": "latency", "state": "firing",
                              "severity": "warn", "since": 1.0,
                              "bundle_key": "b-far"}]}
        monkeypatch.setattr(fleet, "fresh_snapshots",
                            lambda conf: [remote])
        table = alerts.alerts_table(s, fleet=True)
        by_proc = dict(zip(table.column("alert").to_pylist(),
                           table.column("process").to_pylist()))
        assert by_proc["latency"] == "host-2:9:deadbeef"
        assert by_proc["availability"] == fleet.process_identity()

        check = alerts.fleet_alert_check(s)
        assert check.status == "crit"  # local firing page
        firing = check.data["firing"]
        assert {a["alert"] for a in firing} == {"availability",
                                                "latency"}

    def test_notify_seam(self, tmp_path):
        sink = tmp_path / "notify.json"
        s = _session(tmp_path, **_tiny_window_conf())
        s.conf.set("hyperspace.alerts.notify.command",
                   f"cat > {sink}")
        _drive_to_firing(alerts.engine_for(s))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not sink.exists():
            time.sleep(0.05)
        payload = json.loads(sink.read_text())
        assert payload["alert"] == "availability"
        assert payload["transition"] == "firing"

    def test_doctor_cli_exit_codes(self, tmp_path):
        sys_path = str(tmp_path / "sys")

        def run(*args):
            return subprocess.run(
                [sys.executable, os.path.join(REPO, "tools/doctor.py"),
                 "--system-path", sys_path, *args],
                capture_output=True, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))

        ok = run("--json")
        assert ok.returncode == 0, ok.stderr
        report = json.loads(ok.stdout)
        assert report["status"] == "ok"
        assert any(c["name"] == "integrity" for c in report["checks"])

        self._fired_session(tmp_path)  # persists a firing page
        gated = run("--alerts", "--json")
        assert gated.returncode == 2, gated.stdout
        report = json.loads(gated.stdout)
        assert any(c["name"] == "alerts" and c["status"] == "crit"
                   for c in report["checks"])
        # Without --alerts the local checks alone still grade ok.
        assert run().returncode == 0

    def test_alert_metrics_and_catalog(self, tmp_path):
        s = _session(tmp_path, **_tiny_window_conf())
        engine = alerts.engine_for(s)
        e0 = _metrics.registry().counter("alerts.evaluations")
        _drive_to_firing(engine)
        snap = _metrics.snapshot()
        assert _metrics.registry().counter("alerts.evaluations") > e0
        assert snap.get("alerts.firing") == 1.0
        assert snap.get("alerts.bundles_captured", 0) >= 1
        _drive_to_resolved(engine)
        assert _metrics.snapshot().get("alerts.firing") == 0.0
