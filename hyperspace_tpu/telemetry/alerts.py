"""The SLO alert engine: continuous evaluation, a persisted alert state
machine, and auto-captured incident bundles.

``doctor()`` grades only when a human calls it; this module is the loop
that calls it first.  A conf-gated evaluator thread
(``hyperspace.alerts.enabled``, default off; riding the fleet-heartbeat
cadence unless ``hyperspace.alerts.intervalS`` overrides it) samples the
metrics registry every tick and evaluates the declared objectives with
the pure multi-window multi-burn-rate math in telemetry/slo.py:

  ================  =========================================================
  ``availability``  ``serve.ok`` good vs ``serve.errors`` +
                    ``serve.shed`` + ``serve.send_timeouts`` bad (an
                    answer that never reached the wire counts against
                    the caller), against
                    ``hyperspace.alerts.availabilityTarget``
                    (burn-rate rules: 5m+1h fast burn pages, 6h+3d slow
                    burn warns — windows/factors conf-tunable).
  ``latency``       the ``serve.latency_ms`` histogram split at
                    ``hyperspace.doctor.latencySloMs``, against
                    ``hyperspace.alerts.latencyTarget`` (same rules).
  ``staleness``     max ACTIVE-index staleness seconds via the lifecycle
                    change detector, thresholded at
                    ``hyperspace.alerts.stalenessWarnS`` (warn).
  ``build_claims``  fresh multi-host build claims whose holder publishes
                    no fresh heartbeat (a dead host fencing work) —
                    any such claim pages.
  ================  =========================================================

Each alert runs the flap-damped pending → firing → resolved state
machine (slo.step_state); every state CHANGE is persisted through the
PR 2 LogStore seam under ``<systemPath>/_hyperspace_alerts`` (both
backends, fault-quiet, never raises — same contract as the lifecycle
journal), so a firing alert survives a process restart and re-resolves
from the restarted engine.  On the transition to firing the engine
captures an INCIDENT BUNDLE — the flight-recorder interesting tail, a
metrics snapshot, the doctor report, the live timeline's trace events,
and the alert's evaluation window — through the PR 9 diagnostics store
(``_hyperspace_diagnostics``), so federated ``trace``/``slow_queries``
resolve the incident's trace ids from any process, after the fact.

Surfacing: ``Hyperspace.alerts()`` / ``alert_history()``, the inline
interop ``alerts`` verb (works during overload), fleet federation (the
heartbeat snapshot carries active alerts; ``alerts(fleet=True)`` merges
them with process attribution and a firing fleet alert grades the
cluster doctor), and a notification seam:
``hyperspace.alerts.notify.command`` runs OFF the evaluation thread
with the transition record as JSON on stdin.

Metrics: ``alerts.evaluations`` / ``alerts.transitions`` /
``alerts.bundles_captured`` / ``alerts.notifications`` counters and the
``alerts.firing`` gauge; spans ``alert.evaluate`` and ``alert.capture``
(docs/16-observability.md).  The serve path itself is never touched —
a disabled engine costs the serving workload nothing.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from hyperspace_tpu.telemetry import slo

ALERT_DIR = "_hyperspace_alerts"
RECORD_VERSION = 1
# Bound on the in-memory sample ring per objective (at the default 5s
# heartbeat cadence this covers the 3d slow window at ~1/12 resolution;
# shrunken test windows are covered exactly).
MAX_SAMPLES = 4096
# Active (pending/firing) alerts carried per heartbeat snapshot.
FLEET_ALERTS_MAX = 16

_seq_lock = threading.Lock()
_seq = 0


# -- conf accessors -----------------------------------------------------------
def enabled(conf) -> bool:
    return bool(getattr(conf, "alerts_enabled", False))


def interval_s(conf) -> float:
    """Evaluation cadence: ``hyperspace.alerts.intervalS`` when set,
    else the fleet-heartbeat cadence (the engine rides the same clock
    the federation reads on)."""
    explicit = float(getattr(conf, "alerts_interval_s", 0.0))
    if explicit > 0:
        return max(0.05, explicit)
    from hyperspace_tpu.telemetry import fleet

    return fleet.publish_interval_s(conf)


def alert_root(conf) -> str:
    from hyperspace_tpu.index.path_resolver import PathResolver

    return os.path.join(PathResolver(conf).system_path, ALERT_DIR)


def _store(conf):
    from hyperspace_tpu.io.log_store import store_for

    return store_for(conf, alert_root(conf))


def _rules(conf) -> List[slo.BurnRule]:
    return slo.default_rules(
        fast_short_s=float(getattr(conf, "alerts_fast_short_s", 300.0)),
        fast_long_s=float(getattr(conf, "alerts_fast_long_s", 3600.0)),
        fast_factor=float(getattr(conf, "alerts_fast_factor", 14.4)),
        slow_short_s=float(getattr(conf, "alerts_slow_short_s", 21600.0)),
        slow_long_s=float(getattr(conf, "alerts_slow_long_s", 259200.0)),
        slow_factor=float(getattr(conf, "alerts_slow_factor", 1.0)))


def _next_key() -> str:
    global _seq
    with _seq_lock:
        _seq += 1
        seq = _seq
    return f"a-{int(time.time() * 1000):013d}-{os.getpid()}-{seq:05d}"


# -- persistence --------------------------------------------------------------
def append_transition(conf, record: Dict[str, Any]) -> Optional[str]:
    """Persist one state-change record; returns its key, or None on
    failure.  Never raises; runs fault-quiet (the journal contract —
    alert IO must neither fail the engine nor consume an armed fault
    budget aimed at the system under test).  Pruning respects
    ``hyperspace.alerts.maxEntries`` but NEVER drops the latest record
    of any alert — that record IS the restart-proof state."""
    from hyperspace_tpu.io import faults
    from hyperspace_tpu.telemetry import metrics

    try:
        with faults.quiet():
            store = _store(conf)
            rec = {"v": RECORD_VERSION, "ts": time.time(), **record}
            payload = json.dumps(rec, default=str).encode("utf-8")
            key = None
            for _ in range(4):
                key = _next_key()
                if store.put_if_absent(key, payload):
                    break
            else:
                metrics.inc("alerts.errors")
                return None
            cap = int(getattr(conf, "alerts_max_entries", 512))
            if cap > 0:
                keys = sorted(store.list_keys())
                if len(keys) > cap:
                    protected = set(_latest_keys(conf))
                    for old in keys[:len(keys) - cap]:
                        if old not in protected:
                            store.delete(old)
            return key
    except Exception:  # noqa: BLE001 — alert IO never fails the engine
        metrics.inc("alerts.errors")
        return None


def records(conf) -> List[Dict[str, Any]]:
    """Every parseable alert-transition record, oldest first.  Torn or
    unparseable records are skipped — the log is advisory data."""
    from hyperspace_tpu.io import faults

    out: List[Dict[str, Any]] = []
    try:
        with faults.quiet():
            store = _store(conf)
            for key in sorted(store.list_keys()):
                try:
                    rec = json.loads(store.read(key).decode("utf-8"))
                except (FileNotFoundError, ValueError, UnicodeDecodeError):
                    continue
                if not isinstance(rec, dict):
                    continue
                rec["key"] = key
                out.append(rec)
    except Exception:  # noqa: BLE001 — an unreadable log reads empty
        pass
    return out


def _latest_keys(conf) -> List[str]:
    """The newest record key per alert name (pruning protection)."""
    latest: Dict[str, str] = {}
    for rec in records(conf):
        name = str(rec.get("alert", ""))
        if name:
            latest[name] = str(rec.get("key", ""))
    return list(latest.values())


def load_states(conf) -> Dict[str, Dict[str, Any]]:
    """Rebuild the per-alert state map from the persisted log (newest
    record per alert wins) — how a firing alert survives restart."""
    states: Dict[str, Dict[str, Any]] = {}
    for rec in records(conf):
        name = str(rec.get("alert", ""))
        if not name:
            continue
        states[name] = {"state": str(rec.get("state", slo.RESOLVED)),
                        "streak": 0,
                        "since": float(rec.get("since", rec.get("ts", 0.0))
                                       or 0.0),
                        "severity": str(rec.get("severity", "")),
                        "bundle_key": rec.get("bundle_key"),
                        "detail": rec.get("detail") or {}}
    return states


def clear(conf) -> None:
    """Wipe the persisted alert log (tests)."""
    from hyperspace_tpu.io import faults

    with faults.quiet():
        store = _store(conf)
        for key in store.list_keys():
            store.delete(key)


# -- the engine ---------------------------------------------------------------
class AlertEngine:
    """One evaluator per session (``engine_for``); opt-in via
    ``hyperspace.alerts.enabled`` like the lifecycle daemon and the
    fleet publisher.  ``run_once()`` is the synchronous evaluation the
    thread loops on — tests and the chaos drill
    drive it directly."""

    def __init__(self, session) -> None:
        self.session = session
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._samples: Dict[str, List[slo.Sample]] = {}
        self._states: Optional[Dict[str, Dict[str, Any]]] = None

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "AlertEngine":
        from hyperspace_tpu.exceptions import HyperspaceError

        if not enabled(self.session.conf):
            raise HyperspaceError(
                "The SLO alert engine is opt-in: set "
                "hyperspace.alerts.enabled=true (evaluation rides the "
                "fleet-heartbeat cadence unless "
                "hyperspace.alerts.intervalS overrides it)")
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="hs-alert-engine", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            self._thread = None

    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        while not self._stop.is_set():
            self.run_once()
            self._stop.wait(interval_s(self.session.conf))

    # -- evaluation ---------------------------------------------------------
    def run_once(self) -> List[Dict[str, Any]]:
        """One evaluation tick: sample, evaluate every objective, step
        the state machines, persist/capture/notify on transitions.
        Returns the transition records written (empty most ticks).
        Never raises; runs fault-quiet like every diagnostics path."""
        from hyperspace_tpu.io import faults
        from hyperspace_tpu.telemetry import metrics
        from hyperspace_tpu.telemetry.trace import span

        conf = self.session.conf
        transitions: List[Dict[str, Any]] = []
        try:
            with faults.quiet(), span("alert.evaluate") as sp:
                now = time.time()
                # Every store/filesystem touch stays OUTSIDE the state
                # lock: warm the lazily-loaded states, then run the
                # IO-bearing probes, THEN step the pure state machines
                # under the lock, and only afterwards commit the
                # resulting transitions (bundle capture + log append)
                # back through the store.
                self.current_states()
                probes = {"staleness": self._probe_staleness(),
                          "build_claims": self._probe_dead_claims(conf)}
                changes: List[Dict[str, Any]] = []
                with self._lock:
                    evaluations = self._evaluate_objectives(conf, now,
                                                            probes)
                    for name, ev in evaluations.items():
                        change = self._step_alert(conf, name, ev, now)
                        if change is not None:
                            changes.append(change)
                    firing = sum(1 for st in self._states.values()
                                 if st.get("state") == slo.FIRING)
                for change in changes:
                    transitions.append(
                        self._commit_transition(conf, change, now))
                metrics.inc("alerts.evaluations")
                metrics.set_gauge("alerts.firing", firing)
                if transitions:
                    metrics.inc("alerts.transitions", len(transitions))
                sp.set(firing=firing, transitions=len(transitions))
        except Exception:  # noqa: BLE001 — evaluation never fails callers
            metrics.inc("alerts.errors")
        for rec in transitions:
            _notify(conf, rec)
        return transitions

    def _evaluate_objectives(self, conf, now: float,
                             probes: Dict[str, Optional[float]],
                             ) -> Dict[str, Dict[str, Any]]:
        from hyperspace_tpu.telemetry import metrics

        typed = metrics.registry().typed_snapshot()
        counters = typed["counters"]
        rules = _rules(conf)
        out: Dict[str, Dict[str, Any]] = {}

        # Bad = errors + sheds + responses we failed to DELIVER
        # (``serve.send_timeouts``): a wire fault that eats the answer
        # after a clean execution is still an unavailable request from
        # the caller's side, and it is the only server-side trace some
        # injected net.send faults leave.
        good = float(counters.get("serve.ok", 0.0))
        bad = (float(counters.get("serve.errors", 0.0))
               + float(counters.get("serve.shed", 0.0))
               + float(counters.get("serve.send_timeouts", 0.0)))
        ring = self._append_sample("availability", now, good, bad)
        out["availability"] = slo.evaluate_objective(
            ring, now, rules,
            float(getattr(conf, "alerts_availability_target", 0.999)))

        slo_ms = float(getattr(conf, "doctor_latency_slo_ms", 1000.0))
        g_lat, b_lat = slo.hist_split(
            typed["histograms"].get("serve.latency_ms"), slo_ms)
        ring = self._append_sample("latency", now, g_lat, b_lat)
        out["latency"] = slo.evaluate_objective(
            ring, now, rules,
            float(getattr(conf, "alerts_latency_target", 0.99)))

        out["staleness"] = slo.threshold_objective(
            probes.get("staleness"),
            float(getattr(conf, "alerts_staleness_warn_s", 600.0)),
            "warn")
        out["build_claims"] = slo.threshold_objective(
            probes.get("build_claims"), 1.0, "page")
        return out

    def _append_sample(self, objective: str, now: float, good: float,
                       bad: float) -> List[slo.Sample]:
        ring = self._samples.setdefault(objective, [])
        ring.append(slo.Sample(now, good, bad))
        if len(ring) > MAX_SAMPLES:
            del ring[:len(ring) - MAX_SAMPLES]
        return ring

    def _probe_staleness(self) -> Optional[float]:
        """Max staleness seconds across ACTIVE indexes (stat-level, the
        doctor's detector); None when the probe cannot run."""
        try:
            from hyperspace_tpu.index.log_entry import States
            from hyperspace_tpu.lifecycle.change_detector import (
                detect_changes,
            )

            manager = self.session.index_collection_manager
            worst = 0.0
            now = time.time()
            for entry in manager.get_indexes():
                if entry.state != States.ACTIVE:
                    continue
                change = detect_changes(self.session, entry)
                if change.changed:
                    age = (max(0.0, now - change.newest_change_ms / 1000.0)
                           if change.newest_change_ms > 0 else 0.0)
                    worst = max(worst, age)
            return worst
        except Exception:  # noqa: BLE001 — a blind probe never pages
            return None

    def _probe_dead_claims(self, conf) -> Optional[float]:
        """Count of FRESH multi-host build claims whose holder publishes
        no fresh heartbeat (the fleet.build_claims crit condition);
        None when ungradeable (no heartbeats to cross-check)."""
        try:
            from hyperspace_tpu.parallel.multihost_build import (
                scan_build_claims,
            )
            from hyperspace_tpu.telemetry import fleet

            claims = scan_build_claims(conf)
            if not claims:
                return 0.0
            fresh = {str(s.get("process", ""))
                     for s in fleet.fresh_snapshots(conf)}
            if not fresh:
                return None
            now = time.time()
            return float(sum(
                1 for rec in claims
                if float(rec.get("expires_at", 0.0)) >= now
                and str(rec.get("holder", "")) not in fresh))
        except Exception:  # noqa: BLE001 — a blind probe never pages
            return None

    def _step_alert(self, conf, name: str, evaluation: Dict[str, Any],
                    now: float) -> Optional[Dict[str, Any]]:
        """Advance one alert's state machine (pure; caller holds the
        state lock).  Returns a change descriptor on a state change —
        the store-touching commit happens in :meth:`_commit_transition`,
        outside the lock."""
        prev = self._states.get(name)
        prev_state = str(prev.get("state", slo.RESOLVED)) if prev \
            else slo.RESOLVED
        new_state, transition = slo.step_state(
            prev, bool(evaluation.get("breached")),
            str(evaluation.get("severity", "")), now,
            pending_evals=int(getattr(conf, "alerts_pending_evals", 2)),
            resolve_evals=int(getattr(conf, "alerts_resolve_evals", 2)))
        new_state["detail"] = evaluation
        if prev is not None and prev.get("bundle_key") \
                and new_state["state"] != slo.RESOLVED:
            new_state["bundle_key"] = prev["bundle_key"]
        self._states[name] = new_state
        if new_state["state"] == prev_state:
            return None
        return {"name": name, "prev_state": prev_state,
                "transition": transition or "",
                "state": new_state["state"],
                "severity": new_state.get("severity", ""),
                "since": new_state.get("since", now),
                "evaluation": evaluation}

    def _commit_transition(self, conf, change: Dict[str, Any],
                           now: float) -> Dict[str, Any]:
        """Persist one state change: capture the incident bundle on a
        transition to firing, then append the transition record — all
        store IO, run after the state lock is released."""
        name = change["name"]
        bundle_key = None
        if change["transition"] == "firing":
            bundle_key = self._capture_incident(conf, name,
                                                change["evaluation"])
            with self._lock:
                st = self._states.get(name)
                if st is not None and st["state"] != slo.RESOLVED:
                    st["bundle_key"] = bundle_key
        rec = {"alert": name, "state": change["state"],
               "prev_state": change["prev_state"],
               "severity": change["severity"],
               "transition": change["transition"],
               "since": change["since"],
               "bundle_key": bundle_key, "detail": change["evaluation"]}
        rec["key"] = append_transition(conf, rec)
        return rec

    def _capture_incident(self, conf, name: str,
                          evaluation: Dict[str, Any]) -> Optional[str]:
        """Freeze the "why" at the moment of the page: the diagnostics
        bundle (flight tail + metrics + perf tail) plus the doctor
        report, the live timeline's trace events, and this alert's
        evaluation window, persisted through the PR 9 diagnostics store
        so federated trace/slow-queries readers resolve it after the
        fact.  Returns the bundle key, or None on failure (a capture
        failure must not lose the transition record)."""
        from hyperspace_tpu.telemetry import (
            flight_recorder,
            metrics,
            timeline,
        )
        from hyperspace_tpu.io.log_store import store_for
        from hyperspace_tpu.telemetry.trace import span

        try:
            with span("alert.capture", alert=name) as sp:
                bundle = flight_recorder.diagnostics_bundle(conf)
                try:
                    from hyperspace_tpu.telemetry.doctor import doctor

                    report = doctor(self.session).to_dict()
                except Exception:  # noqa: BLE001 — a blind doctor is
                    report = None  # still a capturable incident
                rec = timeline.recorder()
                window = {
                    obj: [[s.ts, s.good, s.bad] for s in ring[-256:]]
                    for obj, ring in self._samples.items()}
                bundle["incident"] = {
                    "alert": name,
                    "ts": time.time(),
                    "evaluation": evaluation,
                    "doctor": report,
                    "timeline": timeline.to_trace_events(
                        rec.intervals(), rec.memory_samples(), ()),
                    "window": window,
                }
                store = store_for(conf,
                                  flight_recorder.flight_root(conf))
                payload = json.dumps(bundle,
                                     default=str).encode("utf-8")
                key = None
                for _ in range(4):
                    key = (f"b-{int(time.time() * 1000):013d}-"
                           f"{os.getpid()}-i{_next_seq():05d}")
                    if store.put_if_absent(key, payload):
                        break
                else:
                    return None
                cap = max(1, int(getattr(conf,
                                         "flight_recorder_max_bundles",
                                         8)))
                keys = store.list_keys()
                if len(keys) > cap:
                    for old in sorted(keys)[:len(keys) - cap]:
                        store.delete(old)
                metrics.inc("alerts.bundles_captured")
                sp.set(key=key, bytes=len(payload))
                return key
        except Exception:  # noqa: BLE001 — capture never loses the page
            return None

    # -- reads --------------------------------------------------------------
    def current_states(self) -> Dict[str, Dict[str, Any]]:
        """The per-alert state map (loaded from the persisted log on
        first read, so it answers before the first evaluation too).
        The store read happens outside the state lock; the first loader
        to take the lock wins."""
        with self._lock:
            if self._states is not None:
                return {k: dict(v) for k, v in self._states.items()}
        loaded = load_states(self.session.conf)
        with self._lock:
            if self._states is None:
                self._states = loaded
            return {k: dict(v) for k, v in self._states.items()}

    def active_alerts(self) -> List[Dict[str, Any]]:
        """Pending/firing alerts as compact dicts — what the fleet
        heartbeat snapshot carries."""
        out = []
        for name, st in sorted(self.current_states().items()):
            if st.get("state") in (slo.PENDING, slo.FIRING):
                out.append({"alert": name, "state": st["state"],
                            "severity": st.get("severity", ""),
                            "since": st.get("since", 0.0),
                            "bundle_key": st.get("bundle_key")})
        return out[:FLEET_ALERTS_MAX]


def _next_seq() -> int:
    global _seq
    with _seq_lock:
        _seq += 1
        return _seq


def engine_for(session) -> AlertEngine:
    """The session's engine, created lazily (thread starts only via
    :meth:`AlertEngine.start`)."""
    e = getattr(session, "_alert_engine", None)
    if e is None:
        e = AlertEngine(session)
        session._alert_engine = e
    return e


def maybe_start(session) -> Optional[AlertEngine]:
    """Start the engine when the conf gate is on; never raises (an
    alerting failure must not break session construction or server
    start)."""
    try:
        if not enabled(session.conf):
            return None
        return engine_for(session).start()
    except Exception:  # noqa: BLE001 — telemetry never breaks callers
        return None


def carried_alerts(conf) -> List[Dict[str, Any]]:
    """Active (pending/firing) alerts for the fleet heartbeat snapshot,
    rebuilt from the persisted log — conf-only, so the publisher thread
    needs no session.  Empty (and store-free) when the engine is
    disabled.  Never raises."""
    try:
        if not enabled(conf):
            return []
        out = []
        for name, st in sorted(load_states(conf).items()):
            if st.get("state") in (slo.PENDING, slo.FIRING):
                out.append({"alert": name, "state": st["state"],
                            "severity": st.get("severity", ""),
                            "since": st.get("since", 0.0),
                            "bundle_key": st.get("bundle_key")})
        return out[:FLEET_ALERTS_MAX]
    except Exception:  # noqa: BLE001 — telemetry never breaks publishers
        return []


# -- notification seam --------------------------------------------------------
def _notify(conf, record: Dict[str, Any]) -> None:
    """Run ``hyperspace.alerts.notify.command`` with the transition
    record as JSON on stdin, on a dedicated short-lived thread — the
    evaluation thread never blocks on a webhook.  Fires for ``firing``
    and ``resolved`` transitions only.  Never raises."""
    command = str(getattr(conf, "alerts_notify_command", "") or "")
    if not command or record.get("transition") not in ("firing",
                                                       "resolved"):
        return

    def run() -> None:
        import subprocess

        from hyperspace_tpu.telemetry import metrics

        try:
            payload = json.dumps(record, default=str).encode("utf-8")
            env = dict(os.environ)
            env["HYPERSPACE_ALERT"] = str(record.get("alert", ""))
            env["HYPERSPACE_ALERT_STATE"] = str(record.get("state", ""))
            proc = subprocess.Popen(  # noqa: S602 — operator-configured
                command, shell=True, stdin=subprocess.PIPE, env=env)
            proc.communicate(payload, timeout=30.0)
            metrics.inc("alerts.notifications")
        except Exception:  # noqa: BLE001 — a webhook failure never
            metrics.inc("alerts.errors")  # touches the engine

    threading.Thread(target=run, name="hs-alert-notify",
                     daemon=True).start()


# -- tables -------------------------------------------------------------------
def alerts_table(session, fleet: bool = False):
    """Current alert states, one row per alert — the shape
    ``Hyperspace.alerts()`` and the inline interop ``alerts`` verb
    serve.  ``fleet=True`` federates: this process's states plus every
    fresh heartbeat's carried active alerts, with a ``process`` column
    attributing each row."""
    import pyarrow as pa

    rows: List[Dict[str, Any]] = []
    for name, st in sorted(engine_for(session).current_states().items()):
        rows.append({"process": "", "alert": name,
                     "state": str(st.get("state", "")),
                     "severity": str(st.get("severity", "")),
                     "since": float(st.get("since", 0.0) or 0.0),
                     "bundleKey": str(st.get("bundle_key") or ""),
                     "detailJson": json.dumps(st.get("detail") or {},
                                              default=str)})
    if fleet:
        from hyperspace_tpu.telemetry import fleet as _fleet

        own = _fleet.process_identity()
        for row in rows:
            row["process"] = own
        for snap in _fleet.fresh_snapshots(session.conf):
            proc = str(snap.get("process", ""))
            if proc == own:
                continue
            for a in snap.get("alerts") or []:
                if not isinstance(a, dict):
                    continue
                rows.append({
                    "process": proc,
                    "alert": str(a.get("alert", "")),
                    "state": str(a.get("state", "")),
                    "severity": str(a.get("severity", "")),
                    "since": float(a.get("since", 0.0) or 0.0),
                    "bundleKey": str(a.get("bundle_key") or ""),
                    "detailJson": json.dumps({}),
                })
    return pa.table({
        "process": pa.array([r["process"] for r in rows],
                            type=pa.string()),
        "alert": pa.array([r["alert"] for r in rows], type=pa.string()),
        "state": pa.array([r["state"] for r in rows], type=pa.string()),
        "severity": pa.array([r["severity"] for r in rows],
                             type=pa.string()),
        "since": pa.array([r["since"] for r in rows],
                          type=pa.float64()),
        "bundleKey": pa.array([r["bundleKey"] for r in rows],
                              type=pa.string()),
        "detailJson": pa.array([r["detailJson"] for r in rows],
                               type=pa.string()),
    })


def history_table(conf):
    """The persisted transition log as an arrow table, oldest first —
    the shape ``Hyperspace.alert_history()`` returns."""
    import pyarrow as pa

    recs = records(conf)
    return pa.table({
        "key": pa.array([str(r.get("key", "")) for r in recs],
                        type=pa.string()),
        "ts": pa.array([float(r.get("ts", 0.0) or 0.0) for r in recs],
                       type=pa.float64()),
        "alert": pa.array([str(r.get("alert", "")) for r in recs],
                          type=pa.string()),
        "state": pa.array([str(r.get("state", "")) for r in recs],
                          type=pa.string()),
        "prevState": pa.array([str(r.get("prev_state", ""))
                               for r in recs], type=pa.string()),
        "severity": pa.array([str(r.get("severity", "")) for r in recs],
                             type=pa.string()),
        "transition": pa.array([str(r.get("transition", ""))
                                for r in recs], type=pa.string()),
        "bundleKey": pa.array([str(r.get("bundle_key") or "")
                               for r in recs], type=pa.string()),
        "recordJson": pa.array([json.dumps(r, default=str)
                                for r in recs], type=pa.string()),
    })


def fleet_alert_check(session):
    """The cluster-doctor check (``doctor(fleet=True)``): a FIRING alert
    anywhere in the fleet — this process or any fresh heartbeat — is
    the page the engine already decided to send, so it grades the
    cluster ``crit`` (page severity) or ``warn``."""
    from hyperspace_tpu.telemetry import fleet as _fleet
    from hyperspace_tpu.telemetry.doctor import DoctorCheck

    firing: List[Dict[str, Any]] = []
    if enabled(session.conf):
        for a in engine_for(session).active_alerts():
            if a.get("state") == slo.FIRING:
                firing.append({**a,
                               "process": _fleet.process_identity()})
    own = _fleet.process_identity()
    for snap in _fleet.fresh_snapshots(session.conf):
        proc = str(snap.get("process", ""))
        if proc == own:
            continue
        for a in snap.get("alerts") or []:
            if isinstance(a, dict) and a.get("state") == slo.FIRING:
                firing.append({**a, "process": proc})
    if not firing:
        return DoctorCheck("fleet.alerts", "ok",
                           "no firing SLO alerts across the fleet", {})
    pages = [a for a in firing if a.get("severity") == "page"]
    status = "crit" if pages else "warn"
    names = sorted({f"{a.get('alert')}@{a.get('process', '')[:24]}"
                    for a in firing})
    return DoctorCheck(
        "fleet.alerts", status,
        f"{len(firing)} firing SLO alert(s) across the fleet: "
        f"{', '.join(names[:4])} — incident bundles are in "
        f"diagnostics_bundles()", {"firing": firing})
