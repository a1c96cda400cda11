"""Fleet telemetry federation: cross-process heartbeats, merged metrics,
and the inputs of the cluster doctor.

Every observability surface before this PR — the metrics registry, the
flight recorder, the timeline, the doctor — is process-local, while the
index tree itself is a SHARED lake-resident artifact.  ROADMAP item 3's
serving fleet ("N processes behaving as one system") is undebuggable
until telemetry crosses process boundaries the same way the operation
log already does.  This module is that crossing, built on the PR 2
:class:`~hyperspace_tpu.io.log_store.LogStore` seam so the same code
works over ``PosixLogStore`` and ``EmulatedObjectStore`` and survives
restarts:

  - **Heartbeat publisher** (:class:`FleetPublisher`): a conf-gated
    daemon thread (``hyperspace.fleet.telemetry.enabled``, default off;
    ``publishIntervalS`` cadence) that writes ONE bounded snapshot per
    process under ``<systemPath>/_hyperspace_fleet``: process identity
    and role (``server``/``daemon``/``client``), a typed metrics
    snapshot, the ``health.status`` grade, the per-device kernel-ms map
    (PR 14's ``exec.device.<id>.kernel_ms`` counters), and the bounded
    tail of INTERESTING flight-recorder records (error/slow — the ones
    tail-retention always keeps) so federated ``slow_queries``/``trace``
    see LIVE processes, not just drained ones.  First publish is a
    ``put_if_absent``; refreshes ride a generation-CAS loop; ancient
    entries (``pruneAfterS``) are garbage-collected.  Publishing is
    fault-quiet (``faults.quiet()``) and never raises: diagnostic IO
    must neither fail the process it describes nor consume an armed
    fault counter aimed at the system under test.
  - **Federation readers**: :func:`fleet_status_table` (one row per
    heartbeat, freshness-graded), :func:`fleet_metrics` (counters merged
    by SUM, gauges kept per-process — a fleet-wide "sum" of
    ``health.status`` means nothing — and fixed-bucket histograms merged
    by bucket-sum with exemplar carry; the fixed ``metrics._BUCKETS``
    scale is what makes cross-process bucket addition exact),
    :func:`render_fleet_prometheus` (the merged text exposition with a
    ``process="<id>"`` label on every series), and
    :func:`find_trace` / :func:`fleet_slow_queries_table` resolving a
    trace id across the local ring, every live snapshot, and the
    persisted diagnostics bundles of drained processes.
  - **Cluster doctor inputs**: :func:`fleet_checks` — stale heartbeat
    (dead/hung process) crit, more-than-one-lifecycle-daemon warn,
    aggregate shed-ratio/SLO burn over the merged counters, and
    cross-process / cross-device kernel-ms skew — consumed by
    ``Hyperspace.doctor(fleet=True)`` and published as the
    ``health.fleet.status`` gauge.

A snapshot is stale past ``staleAfterS`` (default: 2x the publish
interval — how the fleet doctor flags a SIGKILLed process within two
heartbeats) and pruned past ``pruneAfterS``.  See
docs/16-observability.md for the snapshot schema and merge semantics.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

FLEET_DIR = "_hyperspace_fleet"
SNAPSHOT_VERSION = 1
_KEY_PREFIX = "hb-"
# Bounded tail of interesting flight-recorder records per snapshot.
FLEET_RECORDS_MAX = 32
# Device-skew grading floor: below this many attributed kernel ms the
# max/median ratio is start-up noise, not a straggler.
SKEW_FLOOR_MS = 50.0

# -- process identity and role ------------------------------------------------
_ROLE_RANK = {"client": 0, "daemon": 1, "server": 2}
_role = "client"
_identity: Optional[str] = None
_identity_lock = threading.Lock()
# Serving-process state carried in the heartbeat so the front door
# (interop/server.py FleetQueryClient) can map endpoints to rows and
# skip draining servers during their grace window.  QueryServer
# start()/drain() set these.
_serving_address = ""
_serving_draining = False


def process_identity() -> str:
    """Stable per-process identity: ``<host>-<pid>-<start_ms>`` — a
    restart mints a NEW identity, so the old heartbeat goes stale (and
    is later pruned) instead of being silently overwritten."""
    global _identity
    with _identity_lock:
        if _identity is None:
            import platform

            _identity = (f"{platform.node() or 'host'}-{os.getpid()}-"
                         f"{int(time.time() * 1000)}")
        return _identity


def process_role() -> str:
    return _role


def set_process_role(role: str) -> None:
    """Raise this process's published role (``server`` > ``daemon`` >
    ``client``; a serving process that also runs the lifecycle daemon
    reports ``server``).  Lowering is ignored — roles only escalate."""
    global _role
    if _ROLE_RANK.get(role, -1) > _ROLE_RANK.get(_role, 0):
        _role = role


def set_serving_address(address: str) -> None:
    """The ``host:port`` this process serves on, carried in its
    heartbeat so the front door can match fleet rows to endpoints."""
    global _serving_address
    _serving_address = str(address or "")


def set_serving_draining(draining: bool) -> None:
    """Flip the heartbeat's ``draining`` flag — ``QueryServer.drain``
    sets it (and publishes immediately) so the front door stops
    routing here DURING the grace window, not only after the final
    deregister."""
    global _serving_draining
    _serving_draining = bool(draining)


# -- conf accessors -----------------------------------------------------------
def enabled(conf) -> bool:
    return bool(getattr(conf, "fleet_telemetry_enabled", False))


def publish_interval_s(conf) -> float:
    return max(0.05, float(getattr(conf, "fleet_publish_interval_s", 5.0)))


def stale_after_s(conf) -> float:
    """Age past which a heartbeat counts as a dead/hung process.  The
    conf default of 0 derives 2x the publish interval — the acceptance
    contract that a SIGKILLed process flips the fleet doctor to crit
    within two publish intervals."""
    explicit = float(getattr(conf, "fleet_stale_after_s", 0.0))
    return explicit if explicit > 0 else 2.0 * publish_interval_s(conf)


def prune_after_s(conf) -> float:
    return float(getattr(conf, "fleet_prune_after_s", 600.0))


def fleet_root(conf) -> str:
    from hyperspace_tpu.index.path_resolver import PathResolver

    return os.path.join(PathResolver(conf).system_path, FLEET_DIR)


def _store(conf):
    from hyperspace_tpu.io.log_store import store_for

    return store_for(conf, fleet_root(conf))


# -- the snapshot -------------------------------------------------------------
def device_kernel_ms_map(counters: Dict[str, Any]) -> Dict[str, float]:
    """The per-device attributed kernel-ms map out of a counters dict
    (PR 14's ``exec.device.<id>.kernel_ms`` series)."""
    out: Dict[str, float] = {}
    for name, value in counters.items():
        if not name.startswith("exec.device.") \
                or not name.endswith(".kernel_ms"):
            continue
        dev = name[len("exec.device."):-len(".kernel_ms")]
        try:
            out[dev] = float(value)
        except (TypeError, ValueError):
            continue
    return out


def build_snapshot(conf) -> Dict[str, Any]:
    """This process's current fleet snapshot: identity/role, the typed
    metrics snapshot, the health grade, the per-device kernel-ms map,
    and the bounded interesting flight-recorder tail."""
    from hyperspace_tpu.telemetry import alerts, flight_recorder, metrics

    typed = metrics.registry().typed_snapshot()
    interesting = [r for r in flight_recorder.recorder().records()
                   if r.get("reason") != "sample"]
    return {
        "v": SNAPSHOT_VERSION,
        "ts": time.time(),
        "process": process_identity(),
        "host": process_identity().rsplit("-", 2)[0],
        "pid": os.getpid(),
        "role": process_role(),
        "health": typed["gauges"].get("health.status"),
        "address": _serving_address,
        "draining": _serving_draining,
        "metrics": typed,
        "device_kernel_ms": device_kernel_ms_map(typed["counters"]),
        "records": interesting[-FLEET_RECORDS_MAX:],
        # Active SLO alerts (telemetry/alerts.py; [] when the engine is
        # off) — what alerts(fleet=True) and the fleet.alerts doctor
        # check federate with process attribution.
        "alerts": alerts.carried_alerts(conf),
    }


def publish_once(conf) -> bool:
    """Publish (or CAS-refresh) this process's heartbeat and prune
    ancient entries.  Fault-quiet, never raises — an armed fault budget
    aimed at the engine is never consumed by fleet telemetry, and a
    broken store costs a counter, not a query."""
    from hyperspace_tpu.io import faults
    from hyperspace_tpu.telemetry import metrics
    from hyperspace_tpu.telemetry.trace import span

    if not enabled(conf):
        return False
    try:
        with faults.quiet(), span("fleet.publish") as sp:
            store = _store(conf)
            key = _KEY_PREFIX + process_identity()
            payload = json.dumps(build_snapshot(conf),
                                 default=str).encode("utf-8")
            committed = False
            for _ in range(4):
                # First publish lands via the put_if_absent form
                # (generation 0); refreshes CAS against the generation
                # we just observed — a racing duplicate identity (there
                # is none by construction) would lose cleanly.
                gen = store.generation(key)
                if store.put_if_generation_match(key, payload, gen):
                    committed = True
                    break
            if not committed:
                metrics.inc("fleet.publish.errors")
                return False
            _prune_stale(store, conf)
            metrics.inc("fleet.publishes")
            sp.set(bytes=len(payload))
            return True
    except Exception:  # noqa: BLE001 — fleet telemetry never fails its
        metrics.inc("fleet.publish.errors")  # process
        return False


def _prune_stale(store, conf) -> None:
    """Garbage-collect heartbeats older than ``pruneAfterS`` (long-dead
    processes the doctor already reported).  Unparseable entries are
    left alone — their owner's next CAS refresh replaces them."""
    from hyperspace_tpu.telemetry import metrics

    cutoff = prune_after_s(conf)
    if cutoff <= 0:
        return
    own = _KEY_PREFIX + process_identity()
    now = time.time()
    for key in store.list_keys(_KEY_PREFIX):
        if key == own:
            continue
        try:
            rec = json.loads(store.read(key).decode("utf-8"))
            ts = float(rec.get("ts", 0.0))
        except (FileNotFoundError, ValueError, UnicodeDecodeError,
                TypeError):
            continue
        if now - ts > cutoff:
            store.delete(key)
            metrics.inc("fleet.pruned")


# -- the publisher thread -----------------------------------------------------
class FleetPublisher:
    """One heartbeat thread per session (``publisher_for``); opt-in via
    ``hyperspace.fleet.telemetry.enabled`` like the lifecycle daemon."""

    def __init__(self, session) -> None:
        self.session = session
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "FleetPublisher":
        from hyperspace_tpu.exceptions import HyperspaceError

        if not enabled(self.session.conf):
            raise HyperspaceError(
                "Fleet telemetry is opt-in: set "
                "hyperspace.fleet.telemetry.enabled=true (or publish "
                "one snapshot via telemetry.fleet.publish_once)")
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="hs-fleet-publisher", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout_s: float = 5.0,
             deregister: bool = True) -> None:
        """Stop heartbeating; by default also DEREGISTER (delete this
        process's heartbeat key): a planned exit must not read as a
        dead process to the fleet doctor — a SIGKILLed process never
        runs this, which is exactly how it IS flagged."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            self._thread = None
        if deregister and enabled(self.session.conf):
            deregister_process(self.session.conf)

    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        while not self._stop.is_set():
            publish_once(self.session.conf)
            self._stop.wait(publish_interval_s(self.session.conf))


def publisher_for(session) -> FleetPublisher:
    """The session's publisher, created lazily (thread starts only via
    :meth:`FleetPublisher.start`)."""
    p = getattr(session, "_fleet_publisher", None)
    if p is None:
        p = FleetPublisher(session)
        session._fleet_publisher = p
    return p


def maybe_start(session) -> Optional[FleetPublisher]:
    """Start the publisher when the conf gate is on; never raises (a
    fleet-telemetry failure must not break session construction or
    server start)."""
    try:
        if not enabled(session.conf):
            return None
        return publisher_for(session).start()
    except Exception:  # noqa: BLE001 — telemetry never breaks callers
        return None


# -- federation reads ---------------------------------------------------------
def live_snapshots(conf) -> List[Dict[str, Any]]:
    """Every parseable published heartbeat (stale ones included — the
    doctor grades them), with ``key`` and computed ``age_s`` attached.
    Unreadable stores read empty; torn snapshots are skipped."""
    from hyperspace_tpu.io import faults

    out: List[Dict[str, Any]] = []
    now = time.time()
    try:
        with faults.quiet():
            store = _store(conf)
            for key in sorted(store.list_keys(_KEY_PREFIX)):
                try:
                    rec = json.loads(store.read(key).decode("utf-8"))
                except (FileNotFoundError, ValueError,
                        UnicodeDecodeError):
                    continue
                if not isinstance(rec, dict):
                    continue
                rec["key"] = key
                rec["age_s"] = max(0.0, now - float(rec.get("ts", 0.0)
                                                    or 0.0))
                out.append(rec)
    except Exception:  # noqa: BLE001 — an unreadable fleet reads empty
        pass
    return out


def fresh_snapshots(conf) -> List[Dict[str, Any]]:
    cutoff = stale_after_s(conf)
    return [s for s in live_snapshots(conf) if s["age_s"] <= cutoff]


_HEALTH_NAMES = {0: "ok", 1: "warn", 2: "crit"}


def fleet_status_table(conf):
    """One row per published heartbeat — the shape
    ``Hyperspace.fleet_status()`` and the inline ``fleet_status`` interop
    verb serve.  Columns: process, host, pid, role, address (the
    serving ``host:port``, empty for non-servers), status (the
    process's last published ``health.status`` grade, empty before its
    first ``doctor()``), ageSeconds, fresh, draining (the server is in
    its drain grace window — the front door skips it), records
    (interesting flight records carried), snapshotJson."""
    import pyarrow as pa

    snaps = live_snapshots(conf)
    cutoff = stale_after_s(conf)

    def health_name(s) -> str:
        h = s.get("health")
        try:
            return _HEALTH_NAMES.get(int(h), "") if h is not None else ""
        except (TypeError, ValueError):
            return ""

    return pa.table({
        "process": pa.array([str(s.get("process", "")) for s in snaps],
                            type=pa.string()),
        "host": pa.array([str(s.get("host", "")) for s in snaps],
                         type=pa.string()),
        "pid": pa.array([int(s.get("pid", 0) or 0) for s in snaps],
                        type=pa.int64()),
        "role": pa.array([str(s.get("role", "")) for s in snaps],
                         type=pa.string()),
        "address": pa.array([str(s.get("address", "") or "")
                             for s in snaps], type=pa.string()),
        "status": pa.array([health_name(s) for s in snaps],
                           type=pa.string()),
        "ageSeconds": pa.array([round(float(s.get("age_s", 0.0)), 3)
                                for s in snaps], type=pa.float64()),
        "fresh": pa.array([float(s.get("age_s", 0.0)) <= cutoff
                           for s in snaps], type=pa.bool_()),
        "draining": pa.array([bool(s.get("draining", False))
                              for s in snaps], type=pa.bool_()),
        "records": pa.array([len(s.get("records") or [])
                             for s in snaps], type=pa.int64()),
        "snapshotJson": pa.array([json.dumps(s, default=str)
                                  for s in snaps], type=pa.string()),
    })


# -- merge semantics ----------------------------------------------------------
def merge_metrics(snapshots: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-process typed metric snapshots: counters by SUM (they
    only go up, so the fleet total is meaningful), gauges PER-PROCESS
    (``name -> {process: value}`` — summing ``health.status`` across a
    fleet means nothing), histograms by BUCKET-SUM over the shared
    fixed bucket scale, with count/sum summed, min/max folded, mean
    recomputed, and exemplars carried (per bucket, the last process's
    retained trace link wins).  Pure — no IO, unit-testable."""
    counters: Dict[str, float] = {}
    gauges: Dict[str, Dict[str, float]] = {}
    histograms: Dict[str, Dict[str, Any]] = {}
    processes: List[str] = []
    for snap in snapshots:
        proc = str(snap.get("process", ""))
        processes.append(proc)
        typed = snap.get("metrics") or {}
        for name, value in (typed.get("counters") or {}).items():
            try:
                counters[name] = counters.get(name, 0.0) + float(value)
            except (TypeError, ValueError):
                continue
        for name, value in (typed.get("gauges") or {}).items():
            try:
                gauges.setdefault(name, {})[proc] = float(value)
            except (TypeError, ValueError):
                continue
        for name, h in (typed.get("histograms") or {}).items():
            if not isinstance(h, dict):
                continue
            merged = histograms.setdefault(name, {
                "count": 0, "sum": 0.0, "min": None, "max": None,
                "buckets": {}, "exemplars": {}})
            merged["count"] += int(h.get("count", 0) or 0)
            merged["sum"] += float(h.get("sum", 0.0) or 0.0)
            for bound, n in (h.get("buckets") or {}).items():
                b = str(bound)
                merged["buckets"][b] = merged["buckets"].get(b, 0) \
                    + int(n or 0)
            for side, fold in (("min", min), ("max", max)):
                v = h.get(side)
                if v is not None:
                    cur = merged[side]
                    merged[side] = float(v) if cur is None \
                        else fold(cur, float(v))
            for bucket, ex in (h.get("exemplars") or {}).items():
                merged["exemplars"][str(bucket)] = ex
    for merged in histograms.values():
        merged["mean"] = round(merged["sum"] / merged["count"], 6) \
            if merged["count"] else None
    return {"processes": processes, "counters": counters,
            "gauges": gauges, "histograms": histograms}


def _merge_inputs(conf) -> List[Dict[str, Any]]:
    """Fresh published snapshots, with THIS process's entry replaced by
    its live registry (a scrape must see the local process current even
    between heartbeats — or when its publisher is off entirely)."""
    own = process_identity()
    snaps = [s for s in fresh_snapshots(conf)
             if str(s.get("process", "")) != own]
    snaps.append(build_snapshot(conf))
    return snaps


def fleet_metrics(conf) -> Dict[str, Any]:
    """The fleet-merged metrics view over every FRESH heartbeat plus
    this process's live registry — what ``Hyperspace.fleet_metrics()``
    returns (docs/16-observability.md has the merge semantics)."""
    from hyperspace_tpu.telemetry import metrics
    from hyperspace_tpu.telemetry.trace import span

    with span("fleet.merge") as sp:
        snaps = _merge_inputs(conf)
        merged = merge_metrics(snaps)
        metrics.inc("fleet.merges")
        metrics.set_gauge("fleet.processes", len(merged["processes"]))
        sp.set(processes=len(merged["processes"]))
        return merged


def render_fleet_prometheus(conf) -> str:
    """The merged Prometheus text exposition: every process's series
    with a ``process="<id>"`` label (scrapers aggregate; the label is
    what answers "WHICH server is slow").  Served by
    ``MetricsScrapeServer(fleet=True)``."""
    from hyperspace_tpu.telemetry import metrics
    from hyperspace_tpu.telemetry.trace import span

    def prom(name: str) -> str:
        return "hyperspace_" + name.replace(".", "_").replace("-", "_")

    help_for = metrics.help_lookup()
    with span("fleet.merge") as sp:
        snaps = _merge_inputs(conf)
        metrics.inc("fleet.merges")
        metrics.set_gauge("fleet.processes", len(snaps))
        sp.set(processes=len(snaps))
        lines: List[str] = []
        typed_of = {str(s.get("process", "")): (s.get("metrics") or {})
                    for s in snaps}
        headed: set = set()

        def head(name: str, kind: str) -> None:
            if name in headed:
                return
            headed.add(name)
            doc = help_for(name)
            if doc:
                lines.append(f"# HELP {prom(name)} {doc}")
            lines.append(f"# TYPE {prom(name)} {kind}")

        for proc in sorted(typed_of):
            typed = typed_of[proc]
            label = f'process="{proc}"'
            for name, v in sorted((typed.get("counters") or {}).items()):
                head(name, "counter")
                lines.append(f"{prom(name)}{{{label}}} {float(v):g}")
            for name, v in sorted((typed.get("gauges") or {}).items()):
                head(name, "gauge")
                lines.append(f"{prom(name)}{{{label}}} {float(v):g}")
            for name, h in sorted((typed.get("histograms")
                                   or {}).items()):
                if not isinstance(h, dict):
                    continue
                head(name, "histogram")
                cumulative = 0
                buckets = h.get("buckets") or {}
                exemplars = h.get("exemplars") or {}
                for i, bound in enumerate(_bucket_order(buckets)):
                    cumulative += int(buckets.get(bound, 0) or 0)
                    line = (f'{prom(name)}_bucket{{{label},'
                            f'le="{_le(bound)}"}} {cumulative}')
                    ex = exemplars.get(str(i))
                    if isinstance(ex, (list, tuple)) and len(ex) == 2:
                        line += (f' # {{trace_id="{ex[0]}"}} '
                                 f'{float(ex[1]):g}')
                    lines.append(line)
                lines.append(f"{prom(name)}_sum{{{label}}} "
                             f"{float(h.get('sum', 0.0) or 0.0):g}")
                lines.append(f"{prom(name)}_count{{{label}}} "
                             f"{int(h.get('count', 0) or 0)}")
        return "\n".join(lines) + ("\n" if lines else "")


def _bucket_order(buckets: Dict[str, Any]) -> List[str]:
    """JSON round-trips bucket bounds as strings; render them in
    numeric order with ``+Inf`` last."""
    def sort_key(b: str) -> float:
        try:
            return float(b)
        except ValueError:
            return float("inf")

    return sorted(buckets, key=sort_key)


def _le(bound: str) -> str:
    try:
        return f"{float(bound):g}"
    except ValueError:
        return "+Inf"


# -- federated slow queries / trace resolution --------------------------------
def _fleet_records(conf) -> List[Dict[str, Any]]:
    """(record, process) union across the local ring, every published
    snapshot (stale included — a dead process's tail is exactly what an
    operator wants), and persisted diagnostics bundles; deduplicated by
    (trace_id, request_id, ts) since a process's own ring also rides
    its published snapshot."""
    from hyperspace_tpu.telemetry import flight_recorder

    own = process_identity()
    out: List[Dict[str, Any]] = []
    seen: set = set()

    def add(rec: Dict[str, Any], proc: str) -> None:
        key = (rec.get("trace_id"), rec.get("request_id"),
               round(float(rec.get("ts", 0.0) or 0.0), 3))
        if key in seen:
            return
        seen.add(key)
        out.append({**rec, "process": proc})

    for rec in flight_recorder.recorder().records():
        add(rec, own)
    for snap in live_snapshots(conf):
        proc = str(snap.get("process", ""))
        for rec in snap.get("records") or []:
            if isinstance(rec, dict):
                add(rec, proc)
    for bundle in flight_recorder.bundles(conf):
        proc = f"bundle-{bundle.get('pid', '?')}"
        for rec in bundle.get("records") or []:
            if isinstance(rec, dict):
                add(rec, proc)
    out.sort(key=lambda r: float(r.get("ts", 0.0) or 0.0))
    return out


def fleet_slow_queries_table(conf):
    """``slow_queries(fleet=True)``: the federated record union as an
    arrow table — the single-process columns plus ``process``."""
    import pyarrow as pa

    recs = _fleet_records(conf)
    return pa.table({
        "ts": pa.array([float(r.get("ts", 0.0) or 0.0) for r in recs],
                       type=pa.float64()),
        "process": pa.array([str(r.get("process", "")) for r in recs],
                            type=pa.string()),
        "traceId": pa.array([str(r.get("trace_id", "")) for r in recs],
                            type=pa.string()),
        "requestId": pa.array([str(r.get("request_id", ""))
                               for r in recs], type=pa.string()),
        "kind": pa.array([str(r.get("kind", "")) for r in recs],
                         type=pa.string()),
        "outcome": pa.array([str(r.get("outcome", "")) for r in recs],
                            type=pa.string()),
        "latencyMs": pa.array([float(r.get("latency_ms", 0.0) or 0.0)
                               for r in recs], type=pa.float64()),
        "slow": pa.array([bool(r.get("slow")) for r in recs],
                         type=pa.bool_()),
        "reason": pa.array([str(r.get("reason", "")) for r in recs],
                           type=pa.string()),
        "error": pa.array([str(r.get("error", "")) for r in recs],
                          type=pa.string()),
        "recordJson": pa.array([json.dumps(r, default=str)
                                for r in recs], type=pa.string()),
    })


def find_trace(conf, trace_id: str) -> Optional[Dict[str, Any]]:
    """``trace(id, fleet=True)``: resolve ``trace_id`` across the local
    ring first (cheapest), then every published snapshot, then the
    persisted diagnostics bundles; the returned record carries a
    ``process`` field naming where it ran.  None when nowhere."""
    from hyperspace_tpu.telemetry import flight_recorder

    tid = trace_id.lower()
    rec = flight_recorder.recorder().find(tid)
    if rec is not None:
        return {**rec, "process": process_identity()}
    best: Optional[Dict[str, Any]] = None
    for snap in live_snapshots(conf):
        for r in snap.get("records") or []:
            if isinstance(r, dict) and r.get("trace_id") == tid:
                best = {**r, "process": str(snap.get("process", ""))}
    if best is not None:
        return best
    for bundle in flight_recorder.bundles(conf):
        for r in bundle.get("records") or []:
            if isinstance(r, dict) and r.get("trace_id") == tid:
                best = {**r,
                        "process": f"bundle-{bundle.get('pid', '?')}"}
    return best


# -- cluster doctor checks ----------------------------------------------------
def fleet_checks(session) -> List[Any]:
    """The fleet-level doctor checks (``doctor(fleet=True)``), each
    guarded like the local ones — a blind check is a warn, never a
    crash.  The worst of these grades ``health.fleet.status``."""
    from hyperspace_tpu.telemetry.doctor import _guarded

    conf = session.conf
    return [
        _guarded("fleet.heartbeats",
                 lambda: _check_heartbeats(conf)),
        _guarded("fleet.daemons", lambda: _check_daemons(conf)),
        _guarded("fleet.serving", lambda: _check_fleet_serving(conf)),
        _guarded("fleet.skew", lambda: _check_fleet_skew(conf)),
        _guarded("fleet.build_claims",
                 lambda: _check_build_claims(conf)),
        _guarded("fleet.alerts", lambda: _check_fleet_alerts(session)),
    ]


def _check_fleet_alerts(session):
    """A FIRING SLO alert anywhere in the fleet grades the cluster —
    the page the engine already decided to send (telemetry/alerts.py
    owns the grading so the check and the engine cannot drift)."""
    from hyperspace_tpu.telemetry.alerts import fleet_alert_check

    return fleet_alert_check(session)


def _check_heartbeats(conf):
    from hyperspace_tpu.telemetry import metrics
    from hyperspace_tpu.telemetry.doctor import DoctorCheck

    snaps = live_snapshots(conf)
    cutoff = stale_after_s(conf)
    fresh = [s for s in snaps if s["age_s"] <= cutoff]
    stale = {str(s.get("process", "")): round(s["age_s"], 1)
             for s in snaps if s["age_s"] > cutoff}
    metrics.set_gauge("fleet.processes", len(fresh))
    if not snaps:
        return DoctorCheck(
            "fleet.heartbeats", "ok",
            "no fleet heartbeats published (enable "
            "hyperspace.fleet.telemetry.enabled per process)", {})
    if stale:
        return DoctorCheck(
            "fleet.heartbeats", "crit",
            f"{len(stale)}/{len(snaps)} process(es) stale past "
            f"{cutoff:.1f}s — dead or hung; their last published state "
            f"is still readable via fleet_status()",
            {"stale": stale, "fresh": len(fresh)})
    return DoctorCheck(
        "fleet.heartbeats", "ok",
        f"{len(fresh)} process(es) publishing fresh heartbeats",
        {"fresh": len(fresh)})


def _check_daemons(conf):
    from hyperspace_tpu.lifecycle import lease as _lease
    from hyperspace_tpu.telemetry.doctor import DoctorCheck

    fresh = fresh_snapshots(conf)
    daemons = [str(s.get("process", "")) for s in fresh
               if s.get("role") == "daemon"]
    rec = _lease.status(conf)
    if rec is None:
        # No lease record: pre-lease behavior — concurrent maintainers
        # are uncoordinated, flag them.
        if len(daemons) > 1:
            return DoctorCheck(
                "fleet.daemons", "warn",
                f"{len(daemons)} processes report the lifecycle-daemon "
                f"role with no maintenance lease — concurrent "
                f"maintainers waste work rebasing on each other (set "
                f"hyperspace.lifecycle.lease.enabled=true to elect "
                f"one)", {"daemons": daemons})
        return DoctorCheck("fleet.daemons", "ok",
                           f"{len(daemons)} lifecycle daemon(s) in the "
                           f"fleet", {"daemons": daemons})
    holder = str(rec.get("holder", ""))
    epoch = int(rec.get("epoch", 0) or 0)
    live = {str(s.get("process", "")) for s in fresh}
    data = {"holder": holder, "epoch": epoch,
            "lease_fresh": bool(rec.get("fresh")), "daemons": daemons}
    if rec.get("fresh"):
        if not live:
            # Nobody heartbeats (fleet telemetry off or all clients):
            # the lease alone proves single-execution; nothing to
            # cross-check against.
            return DoctorCheck(
                "fleet.daemons", "ok",
                f"maintenance lease epoch {epoch} held by {holder}; no "
                f"fleet heartbeats to cross-check", data)
        if holder in live:
            standbys = max(0, len(daemons) - 1)
            return DoctorCheck(
                "fleet.daemons", "ok",
                f"maintenance lease epoch {epoch} held by live process "
                f"{holder} ({standbys} standby daemon(s))", data)
        return DoctorCheck(
            "fleet.daemons", "crit",
            f"maintenance lease epoch {epoch} held by {holder}, which "
            f"publishes no live heartbeat — the holder died holding "
            f"the lease; takeover happens when it expires "
            f"(ttl {_lease.ttl_s(conf):.0f}s)", data)
    if daemons:
        return DoctorCheck(
            "fleet.daemons", "warn",
            f"maintenance lease epoch {epoch} expired with "
            f"{len(daemons)} candidate daemon(s) — takeover pending "
            f"next poll", data)
    return DoctorCheck(
        "fleet.daemons", "ok",
        f"maintenance lease epoch {epoch} expired and no daemons "
        f"running", data)


def _check_fleet_serving(conf):
    from hyperspace_tpu.telemetry.doctor import DoctorCheck, _slo_burn

    merged = merge_metrics(fresh_snapshots(conf))
    requests = float(merged["counters"].get("serve.requests", 0.0))
    shed = float(merged["counters"].get("serve.shed", 0.0))
    if requests <= 0:
        return DoctorCheck("fleet.serving", "ok",
                           "no served traffic across the fleet", {})
    shed_ratio = shed / requests
    warn_ratio = float(getattr(conf, "doctor_shed_warn_ratio", 0.05))
    slo_ms = float(getattr(conf, "doctor_latency_slo_ms", 1000.0))
    burn = _slo_burn(merged["histograms"].get("serve.latency_ms"),
                     slo_ms)
    data = {"requests": int(requests),
            "shed_ratio": round(shed_ratio, 4),
            "slo_ms": slo_ms, "slo_burn": round(burn, 4),
            "processes": len(merged["processes"])}
    if (warn_ratio > 0 and shed_ratio >= 5 * warn_ratio) or burn >= 0.5:
        return DoctorCheck(
            "fleet.serving", "crit",
            f"fleet overloaded: aggregate shed ratio {shed_ratio:.2f}, "
            f"SLO burn {burn:.2f}", data)
    if (warn_ratio > 0 and shed_ratio >= warn_ratio) or burn >= 0.1:
        return DoctorCheck(
            "fleet.serving", "warn",
            f"aggregate shed ratio {shed_ratio:.2f}, SLO burn "
            f"{burn:.2f}", data)
    return DoctorCheck(
        "fleet.serving", "ok",
        f"{int(requests)} requests fleet-wide, shed ratio "
        f"{shed_ratio:.2f}, SLO burn {burn:.2f}", data)


def skew_ratio(values: List[float]) -> float:
    """max/median over attributed kernel-ms totals — the straggler
    grade, 0.0 when there is nothing meaningful to compare (fewer than
    two lanes, or totals under the noise floor)."""
    import statistics

    vals = [float(v) for v in values if v is not None]
    if len(vals) < 2:
        return 0.0
    med = statistics.median(vals)
    mx = max(vals)
    if med <= 0 or mx - med < SKEW_FLOOR_MS:
        return 0.0
    return mx / med


def _check_fleet_skew(conf):
    from hyperspace_tpu.telemetry.doctor import DoctorCheck

    warn_at = float(getattr(conf, "doctor_device_skew_warn", 4.0))
    per_process: Dict[str, float] = {}
    per_device: Dict[str, float] = {}
    for snap in fresh_snapshots(conf):
        proc = str(snap.get("process", ""))
        dev_map = snap.get("device_kernel_ms") or {}
        total = 0.0
        for dev, ms in dev_map.items():
            try:
                ms = float(ms)
            except (TypeError, ValueError):
                continue
            total += ms
            per_device[str(dev)] = per_device.get(str(dev), 0.0) + ms
        if total > 0:
            per_process[proc] = total
    proc_ratio = skew_ratio(list(per_process.values()))
    dev_ratio = skew_ratio(list(per_device.values()))
    data = {"per_process_ms": {k: round(v, 1)
                               for k, v in per_process.items()},
            "per_device_ms": {k: round(v, 1)
                              for k, v in per_device.items()},
            "process_ratio": round(proc_ratio, 2),
            "device_ratio": round(dev_ratio, 2)}
    if warn_at > 0 and (proc_ratio >= warn_at or dev_ratio >= warn_at):
        which = "process" if proc_ratio >= warn_at else "device"
        return DoctorCheck(
            "fleet.skew", "warn",
            f"kernel-ms skew across the fleet: max/median per-{which} "
            f"ratio {max(proc_ratio, dev_ratio):.1f} >= {warn_at:g} — "
            f"a straggler {which}", data)
    return DoctorCheck("fleet.skew", "ok",
                       "no cross-process or cross-device kernel-ms "
                       "skew", data)


def _check_build_claims(conf):
    """Leftover multi-host build claims (parallel/multihost_build.py)
    graded against the heartbeats (docs/21): an EXPIRED claim with no
    live holder is routine crash debris — any claimant reclaims it and
    the next build reaps the dead coordinator's scratch (warn); a FRESH
    claim whose holder publishes no fresh heartbeat is a dead or hung
    host still fencing the item — the build stalls until the claim TTL
    runs out (crit).  Read-only like every fleet check (the doctor verb
    serves inline while the admission queue sheds, so no store writes
    here); the JOURNALED trail comes from the claim protocol itself —
    the coordinator records every expired-claim sighting and WorkClaims
    records every reclaim/fence, so post-mortems see what doctor saw."""
    from hyperspace_tpu.parallel.multihost_build import scan_build_claims
    from hyperspace_tpu.telemetry.doctor import DoctorCheck

    claims = scan_build_claims(conf)
    if not claims:
        return DoctorCheck("fleet.build_claims", "ok",
                           "no leftover multi-host build claims", {})
    fresh = {str(s.get("process", "")) for s in fresh_snapshots(conf)}
    now = time.time()
    expired_orphans, fresh_dead = [], []
    for rec in claims:
        live = str(rec.get("holder", "")) in fresh
        if float(rec.get("expires_at", 0.0)) < now:
            if not live:
                expired_orphans.append(rec)
        elif fresh and not live:
            # Only gradeable when SOMEBODY heartbeats: with fleet
            # telemetry off there is nothing to cross-check a live
            # claim against, like fleet.daemons' lease-only case.
            fresh_dead.append(rec)

    def brief(recs):
        return [{"build": r.get("build_id"), "item": r.get("item"),
                 "holder": r.get("holder")} for r in recs]

    data = {"pending": len(claims),
            "expired_no_heartbeat": brief(expired_orphans),
            "fresh_dead_holder": brief(fresh_dead)}
    if fresh_dead:
        check = DoctorCheck(
            "fleet.build_claims", "crit",
            f"{len(fresh_dead)} fresh build claim(s) held by "
            f"process(es) with no fresh heartbeat — a dead or hung "
            f"host is fencing work; the build stalls until the claim "
            f"TTL expires", data)
    elif expired_orphans:
        check = DoctorCheck(
            "fleet.build_claims", "warn",
            f"{len(expired_orphans)} expired build claim(s) with no "
            f"live holder — crash debris; survivors (or the next "
            f"build) reclaim them after the TTL", data)
    else:
        check = DoctorCheck(
            "fleet.build_claims", "ok",
            f"{len(claims)} in-flight build claim(s), every holder "
            f"heartbeating", data)
    return check


def deregister_process(conf) -> None:
    """Remove this process's heartbeat (graceful exit); fault-quiet,
    never raises."""
    from hyperspace_tpu.io import faults

    try:
        with faults.quiet():
            _store(conf).delete(_KEY_PREFIX + process_identity())
    except Exception:  # noqa: BLE001 — best-effort cleanup
        pass


def clear(conf) -> None:
    """Wipe published heartbeats (tests)."""
    from hyperspace_tpu.io import faults

    with faults.quiet():
        store = _store(conf)
        for key in store.list_keys():
            store.delete(key)
